"""obs/: span tracer (fake clock, ring buffer, Chrome export) + metrics
(exact percentiles, Prometheus rendering, registry isolation)."""

import json
import threading

import pytest

from repro.obs.metrics import (
    Counter,
    Gauge,
    Histogram,
    MetricsRegistry,
    get_registry,
)
from repro.obs.trace import NULL_TRACER, Tracer, get_tracer, set_tracer


class FakeClock:
    def __init__(self, t=100.0):
        self.t = t

    def __call__(self):
        return self.t


# ---------------------------------------------------------------- tracing


def test_span_nesting_and_timing_is_deterministic():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    with tr.span("outer", cat="compile", layers=3) as outer:
        clock.t += 1.0
        with tr.span("inner", cat="compile"):
            clock.t += 0.25
        clock.t += 0.5
    assert outer.dur == pytest.approx(1.75)
    spans = {s.name: s for s in tr.spans("compile")}
    # timestamps are relative to tracer creation, on the injected clock
    assert spans["outer"].ts == pytest.approx(0.0)
    assert spans["inner"].ts == pytest.approx(1.0)
    assert spans["inner"].dur == pytest.approx(0.25)
    assert spans["outer"].args == {"layers": 3}
    # the inner span nests inside the outer on the exported timeline
    assert (
        spans["outer"].ts <= spans["inner"].ts
        and spans["inner"].ts + spans["inner"].dur
        <= spans["outer"].ts + spans["outer"].dur
    )


def test_span_closes_and_flags_on_exception():
    tr = Tracer(clock=FakeClock())
    with pytest.raises(RuntimeError):
        with tr.span("boom"):
            raise RuntimeError("x")
    (s,) = tr.spans()
    assert s.dur is not None and s.args["error"] is True


def test_chrome_export_schema():
    clock = FakeClock()
    tr = Tracer(clock=clock, pid=7, process_name="test-proc")
    with tr.span("work", cat="execute"):
        clock.t += 0.002
    tr.instant("mark", cat="execute")
    tr.counter("depth", queued=3)
    tr.async_begin("req", 42, cat="request")
    tr.async_end("req", 42, cat="request")
    doc = tr.to_chrome()
    events = doc["traceEvents"]
    # every event carries the trace-event schema fields
    for e in events:
        assert {"ph", "ts", "pid", "tid", "name"} <= set(e)
        assert e["pid"] == 7
    meta = [e for e in events if e["ph"] == "M"]
    assert any(e["args"]["name"] == "test-proc" for e in meta)
    (x,) = [e for e in events if e["ph"] == "X"]
    assert x["name"] == "work" and x["cat"] == "execute"
    assert x["dur"] == pytest.approx(2000.0)  # 2 ms in microseconds
    assert [e["args"] for e in events if e["ph"] == "C"] == [{"queued": 3.0}]
    pair = [e for e in events if e["ph"] in "be"]
    assert [e["ph"] for e in pair] == ["b", "e"]
    assert all(e["id"] == 42 for e in pair)
    # the whole document round-trips through JSON
    assert json.loads(json.dumps(doc)) == doc


def test_ring_buffer_bounds_and_counts_drops():
    tr = Tracer(clock=FakeClock(), max_events=5)
    for i in range(12):
        tr.instant(f"e{i}")
    assert len(tr.events()) == 5
    assert tr.dropped_events == 7
    assert tr.to_chrome()["otherData"]["dropped_events"] == 7
    # the newest events survive
    assert [e["name"] for e in tr.events()] == [f"e{i}" for i in range(7, 12)]
    tr.reset()
    assert tr.events() == [] and tr.dropped_events == 0


def test_disabled_tracer_is_free_and_recordless():
    clock = FakeClock()
    tr = Tracer(clock=clock, enabled=False)
    with tr.span("x") as sp:
        clock.t += 5.0
    tr.instant("i")
    tr.counter("c", v=1)
    tr.async_begin("a", 1)
    assert sp.dur == 0.0  # the shared null span, untouched
    assert tr.events() == []
    assert NULL_TRACER.events() == []


def test_default_tracer_install_and_clear():
    tr = Tracer(clock=FakeClock())
    assert get_tracer() is NULL_TRACER
    try:
        assert set_tracer(tr) is tr and get_tracer() is tr
    finally:
        set_tracer(None)
    assert get_tracer() is NULL_TRACER


def test_tracer_is_thread_safe_and_names_threads():
    tr = Tracer()  # real clock: only counts matter here
    barrier = threading.Barrier(4)  # force all workers to overlap

    def work():
        barrier.wait(timeout=10)
        for _ in range(200):
            with tr.span("w"):
                pass

    threads = [threading.Thread(target=work) for _ in range(4)]
    for t in threads:
        t.start()
    with tr.span("main"):
        pass
    for t in threads:
        t.join()
    assert len(tr.spans()) == 4 * 200 + 1
    tids = {e["tid"] for e in tr.events()}
    assert len(tids) == 5  # stable small tids, one per thread
    names = [
        e for e in tr.to_chrome()["traceEvents"] if e["name"] == "thread_name"
    ]
    assert len(names) == 5


def test_slowest_aggregates_by_name():
    clock = FakeClock()
    tr = Tracer(clock=clock)
    for dur in (0.1, 0.1, 0.1):  # layer:a total 0.3
        with tr.span("layer:a", cat="execute"):
            clock.t += dur
    with tr.span("layer:b", cat="execute"):
        clock.t += 0.25
    with tr.span("other", cat="execute"):
        clock.t += 9.0
    top = tr.slowest(2, cat="execute", prefix="layer:")
    assert [n for n, _ in top] == ["layer:a", "layer:b"]
    assert top[0][1] == pytest.approx(0.3)


@pytest.mark.parametrize("enabled", [False, True])
def test_span_lands_on_the_profiler_timeline(tmp_path, enabled):
    """Every tracer's spans, the no-op tracer's included, are host events
    of a running profiler, args and nesting kept; only an enabled tracer
    also records them in its ring buffer."""
    import glob

    import jax
    from jax.profiler import ProfileData

    tr = Tracer(enabled=enabled) if enabled else NULL_TRACER
    with tr.span("obs.test.outside"):  # no profiler yet: not recorded
        pass
    jax.profiler.start_trace(str(tmp_path))
    try:
        with tr.span("obs.test.outer", cat="serve", live=3):
            with tr.span("obs.test.inner"):
                pass
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    events = {
        ev.name: ev
        for plane in ProfileData.from_file(path).planes
        if plane.name.startswith("/host:")
        for line in plane.lines
        for ev in line.events
        if ev.name.startswith("obs.test.")
    }
    assert set(events) == {"obs.test.outer", "obs.test.inner"}
    outer, inner = events["obs.test.outer"], events["obs.test.inner"]
    assert dict(outer.stats)["live"] == 3
    assert outer.start_ns <= inner.start_ns
    assert inner.start_ns + inner.duration_ns <= (
        outer.start_ns + outer.duration_ns
    )
    recorded = [s.name for s in tr.spans()]
    assert recorded == (
        ["obs.test.outside", "obs.test.inner", "obs.test.outer"]
        if enabled else []
    )


def test_obs_imports_without_jax():
    import subprocess
    import sys

    code = (
        "import sys; from repro.obs import NULL_TRACER\n"
        "with NULL_TRACER.span('x', a=1): pass\n"
        "assert 'jax' not in sys.modules, 'obs imported jax'\n"
    )
    out = subprocess.run(
        [sys.executable, "-c", code], capture_output=True, text=True,
        timeout=120,
    )
    assert out.returncode == 0, out.stderr


# ---------------------------------------------------------------- metrics


def test_histogram_percentiles_are_exact():
    h = Histogram(buckets=(1.0, 10.0, 100.0))
    for v in range(100, 0, -1):  # insertion order must not matter
        h.observe(float(v))
    assert h.percentile(50) == 50.0
    assert h.percentile(95) == 95.0
    assert h.percentile(99) == 99.0
    assert h.percentile(100) == 100.0
    assert h.count == 100 and h.sum == pytest.approx(5050.0)
    assert h.mean == pytest.approx(50.5)
    snap = h.snapshot()
    assert snap["p50"] == 50.0 and snap["p99"] == 99.0
    # cumulative buckets: le=1 -> 1 sample, le=10 -> 10, le=100 -> all
    assert snap["buckets"] == [[1.0, 1], [10.0, 10], [100.0, 100]]


def test_histogram_sample_ring_is_bounded():
    h = Histogram(buckets=(1e9,), max_samples=10)
    for v in range(1, 101):
        h.observe(float(v))
    # count/sum see everything; percentiles see the newest window
    assert h.count == 100
    assert h.percentile(50) == 95.0  # exact over 91..100
    assert h.percentile(100) == 100.0


def test_counter_and_gauge_basics():
    c = Counter()
    c.inc()
    c.inc(2.5)
    assert c.value == pytest.approx(3.5)
    g = Gauge()
    g.set(10)
    g.inc(5)
    g.dec(2)
    assert g.value == pytest.approx(13.0)
    assert g.prom_lines("depth") == ["# TYPE depth gauge", "depth 13"]


def test_histogram_edge_cases():
    h = Histogram()
    assert h.percentile(99) == 0.0  # empty
    with pytest.raises(ValueError):
        h.percentile(0)
    with pytest.raises(ValueError):
        h.percentile(101)
    with pytest.raises(ValueError):
        Histogram(buckets=(2.0, 1.0))  # unsorted
    with pytest.raises(ValueError):
        Counter().inc(-1)


def test_prometheus_exposition():
    h = Histogram(buckets=(0.1, 1.0))
    h.observe(0.05)
    h.observe(0.5)
    h.observe(5.0)
    lines = h.prom_lines("lat_seconds")
    assert lines[0] == "# TYPE lat_seconds histogram"
    assert 'lat_seconds_bucket{le="0.1"} 1' in lines
    assert 'lat_seconds_bucket{le="1"} 2' in lines
    assert 'lat_seconds_bucket{le="+Inf"} 3' in lines
    assert "lat_seconds_count 3" in lines


def test_registry_get_or_create_and_kind_conflicts():
    r = MetricsRegistry()
    c = r.counter("requests")
    c.inc(3)
    assert r.counter("requests") is c  # same object back
    with pytest.raises(ValueError):
        r.gauge("requests")  # kind conflict
    g = r.gauge("depth")
    g.set(4)
    r.histogram("lat", buckets=(1.0,)).observe(0.5)
    snap = r.snapshot()
    assert snap["requests"] == {"kind": "counter", "value": 3.0}
    assert snap["depth"] == {"kind": "gauge", "value": 4.0}
    assert snap["lat"]["value"]["count"] == 1
    text = r.to_prometheus()
    assert "requests 3" in text and "depth 4" in text
    # non-prometheus characters in names are sanitized in the rendering
    r.counter("scheduler/queue.depth").inc()
    assert "scheduler_queue_depth 1" in r.to_prometheus()


def test_meter_windowed_rate():
    from repro.obs.metrics import Meter

    t = {"now": 0.0}
    m = Meter(window_s=10.0, clock=lambda: t["now"])
    assert m.rate == 0.0 and m.total == 0.0
    m.mark(5)
    t["now"] = 2.0
    assert m.total == 5.0
    assert m.rate == pytest.approx(5.0 / 2.0)  # over the elapsed span
    m.mark(5)
    t["now"] = 4.0
    assert m.rate == pytest.approx(10.0 / 4.0)
    # events older than the window fall out of the rate, not the total
    t["now"] = 20.0
    assert m.rate == 0.0
    assert m.total == 10.0
    lines = m.prom_lines("serve_requests")
    assert "serve_requests_total 10" in lines
    with pytest.raises(ValueError):
        m.mark(-1)
    with pytest.raises(ValueError):
        Meter(window_s=0)


def test_meter_in_registry():
    r = MetricsRegistry()
    m = r.meter("reqs", window_s=5.0)
    assert r.meter("reqs") is m
    with pytest.raises(ValueError):
        r.counter("reqs")  # kind conflict
    m.mark(3)
    assert r.snapshot()["reqs"]["kind"] == "meter"
    assert r.snapshot()["reqs"]["value"]["total"] == 3.0
    assert "reqs_total 3" in r.to_prometheus()


def test_global_registry_reset_isolation():
    reg = get_registry()
    reg.reset()
    reg.counter("leaky").inc(7)
    assert reg.names() == ["leaky"]
    reg.reset()
    assert reg.names() == []
    # a fresh counter under the same name starts from zero
    assert reg.counter("leaky").value == 0.0
    reg.reset()
