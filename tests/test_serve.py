"""The unified ``repro.serve`` surface: session verbs, load shedding,
the asyncio HTTP front end, and per-slot mid-decode admission.

The acceptance properties pinned here:

  * **mid-decode admission** — a freed slot is refilled while other
    slots are between decode steps; every request's tokens are
    bit-identical to running it alone, and the decode forward is traced
    exactly once no matter how requests arrive;
  * **load shedding** — a full bounded queue sheds with
    :class:`~repro.serve.Overloaded` (HTTP 429 + ``Retry-After``);
    work the scheduler admitted is never dropped; the internal
    ``SchedulerFull`` never escapes the public serve path;
  * **HTTP e2e** — real sockets, concurrent clients, chunked NDJSON
    streaming, Prometheus ``/metrics``;
  * the deprecated request shims keep working while warning.
"""

import http.client
import json
import threading
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.core.pruning import (
    build_dictionaries,
    magnitude_prune,
    project_params,
)
from repro.engine import InferenceService, execute
from repro.engine import compile_network
from repro.engine.scheduler import SchedulerFull, SlotScheduler
from repro.models.cnn import conv_weight_names, init_cnn, mini_cnn_config
from repro.serve import (
    Overloaded,
    Request,
    Response,
    ServeSession,
    ServingServer,
    classify_session,
)


@pytest.fixture(scope="module")
def mini_prog():
    cfg = mini_cnn_config(num_classes=4, input_hw=12, widths=(8, 16, 16))
    params = init_cnn(cfg, jax.random.PRNGKey(0))
    names = conv_weight_names(cfg)
    params = magnitude_prune(params, names, 0.7)
    dicts = build_dictionaries(params, names, 4)
    params, bits = project_params(params, dicts)
    return cfg, compile_network(cfg, params, bits)


def _images(n, seed=7):
    return np.array(
        jax.random.normal(jax.random.PRNGKey(seed), (n, 1, 12, 12)),
        np.float32,
    )


# ---------------------------------------------------------------------------
# session verbs
# ---------------------------------------------------------------------------


def test_session_run_matches_direct_execute(mini_prog):
    cfg, prog = mini_prog
    sess = classify_session(prog, batch_slots=4)
    imgs = _images(10)
    reqs = sess.run([Request(image=img) for img in imgs])
    ref = np.asarray(execute(prog, jnp.asarray(imgs)))
    assert [r.label for r in reqs] == [int(np.argmax(l)) for l in ref]
    assert all(r.done for r in reqs)
    assert sess.trace_count() == 1


def test_session_stream_yields_completed_requests(mini_prog):
    cfg, prog = mini_prog
    sess = classify_session(prog, batch_slots=2, max_queue=2)
    imgs = _images(7, seed=11)
    submitted = [Request(image=img) for img in imgs]
    seen = []
    for req in sess.stream(submitted):
        assert req.done  # yielded the moment it completes
        seen.append(req)
    assert {id(r) for r in seen} == {id(r) for r in submitted}
    # a bounded queue throttles the drain instead of rejecting
    assert sess.scheduler.metrics.rejected == 0


def test_warmup_traces_once_and_resets_metrics(mini_prog):
    cfg, prog = mini_prog
    sess = classify_session(prog, batch_slots=4)
    sess.warmup()
    assert sess.trace_count() == 1
    assert sess.metrics["completed"] == 0 and sess.metrics["steps"] == 0
    sess.run([Request(image=img) for img in _images(9, seed=3)])
    assert sess.trace_count() == 1  # real traffic does not retrace
    assert sess.metrics["completed"] == 9


# ---------------------------------------------------------------------------
# load shedding
# ---------------------------------------------------------------------------


def test_submit_sheds_with_overloaded_never_schedulerfull(mini_prog):
    cfg, prog = mini_prog
    sess = classify_session(prog, batch_slots=2, max_queue=2)
    admitted = [sess.submit(Request(image=img)) for img in _images(2)]
    with pytest.raises(Overloaded) as ei:
        sess.submit(Request(image=_images(1)[0]))
    assert ei.value.retry_after_s > 0
    assert not isinstance(ei.value, SchedulerFull)
    assert sess.scheduler.metrics.rejected == 1
    # work the scheduler admitted is never dropped
    while sess.has_work():
        sess.step()
    assert all(r.done for r in admitted)
    assert sess.scheduler.metrics.completed == 2


def test_shed_retry_after_matches_live_backpressure(mini_prog):
    cfg, prog = mini_prog
    sess = classify_session(prog, batch_slots=1, max_queue=3)
    sess.submit(Request(image=_images(1)[0]))
    shallow = sess.scheduler.retry_after_hint()
    for img in _images(2, seed=2):
        sess.submit(Request(image=img))
    deep = sess.scheduler.retry_after_hint()
    assert deep > shallow  # deeper queue -> longer hint
    with pytest.raises(Overloaded) as ei:
        sess.submit(Request(image=_images(1)[0]))
    assert ei.value.retry_after_s == pytest.approx(
        sess.scheduler.retry_after_hint()
    )
    sess.run([])  # drain


# ---------------------------------------------------------------------------
# deprecated shims
# ---------------------------------------------------------------------------


def test_classify_request_shim_warns_and_serves(mini_prog):
    from repro.engine.service import ClassifyRequest

    cfg, prog = mini_prog
    with pytest.warns(DeprecationWarning, match="repro.serve.Request"):
        req = ClassifyRequest(_images(1)[0])
    assert isinstance(req, Request)
    svc = InferenceService(prog, batch_slots=2)
    svc.submit(req)
    svc.run()
    assert req.done and req.label is not None


def test_runtime_request_shim_warns():
    from repro.runtime.serve import Request as OldRequest

    with pytest.warns(DeprecationWarning, match="repro.serve.Request"):
        req = OldRequest(prompt=np.ones(4, np.int32), max_new_tokens=2)
    assert isinstance(req, Request)
    assert req.kind == "generate"


# ---------------------------------------------------------------------------
# per-slot mid-decode admission (generation backend)
# ---------------------------------------------------------------------------


@pytest.fixture(scope="module")
def smoke_lm():
    from repro.configs import get_smoke_config
    from repro.models.transformer import init_params

    cfg = get_smoke_config("granite_3_2b")
    params, _, statics = init_params(cfg, jax.random.PRNGKey(0))
    return cfg, params, statics


def _solo_tokens(cfg, statics, params, prompt, n):
    from repro.runtime.serve import DecodeService, ServeConfig

    svc = DecodeService(
        cfg, statics, params,
        ServeConfig(batch_slots=2, max_seq=32, eos_id=-1),
    )
    req = Request(prompt=prompt, max_new_tokens=n)
    svc.submit(req)
    svc.run()
    return list(req.output)


def test_mid_decode_admission_bit_identical_and_single_trace(smoke_lm):
    from repro.obs.trace import Tracer
    from repro.runtime.serve import DecodeService, ServeConfig

    cfg, params, statics = smoke_lm
    tr = Tracer()
    svc = DecodeService(
        cfg, statics, params,
        ServeConfig(batch_slots=2, max_seq=32, eos_id=-1),
        tracer=tr,
    )
    rng = np.random.default_rng(0)
    p1 = rng.integers(1, cfg.vocab, 6).astype(np.int32)
    p2 = rng.integers(1, cfg.vocab, 4).astype(np.int32)
    p3 = rng.integers(1, cfg.vocab, 5).astype(np.int32)
    r1 = Request(prompt=p1, max_new_tokens=10)
    r2 = Request(prompt=p2, max_new_tokens=3)
    r3 = Request(prompt=p3, max_new_tokens=4)
    svc.submit(r1)
    svc.submit(r2)
    while not r2.done:
        svc.step()
    assert not r1.done  # its neighbour finished mid-generation
    svc.submit(r3)
    svc.step()  # refills the freed slot while r1 is between decode steps
    assert r3.output and not r1.done
    svc.run()

    # the decode forward traced exactly once across all of that
    assert svc.trace_count() == 1
    # per-slot positions: each request's tokens are bit-identical to a
    # solo run in a fresh service
    for prompt, req in ((p1, r1), (p2, r2), (p3, r3)):
        assert list(req.output) == _solo_tokens(
            cfg, statics, params, prompt, req.max_new_tokens
        )
    # the trace records the mid-decode admission at r3's prefill position
    admits = [
        e for e in tr.events()
        if e.get("args", {}).get("event") == "admit_mid_decode"
    ]
    assert len(admits) == 1
    assert admits[0]["args"]["pos"] == len(p3)
    # first-result SLO latency recorded once per request
    assert svc.scheduler.metrics.first_results == 3
    assert svc.metrics["first_result_p50_s"] >= 0.0


# ---------------------------------------------------------------------------
# HTTP front end, over real sockets
# ---------------------------------------------------------------------------


def _post(host, port, path, payload, timeout=60):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request(
            "POST", path, json.dumps(payload),
            {"Content-Type": "application/json"},
        )
        resp = conn.getresponse()
        return resp.status, dict(resp.getheaders()), resp.read()
    finally:
        conn.close()


def _get(host, port, path, timeout=30):
    conn = http.client.HTTPConnection(host, port, timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def test_http_server_end_to_end(mini_prog):
    cfg, prog = mini_prog
    sess = classify_session(prog, batch_slots=4)
    srv = ServingServer(sess, admit_wait_s=0.002)
    host, port = srv.start_in_thread()
    try:
        imgs = _images(8, seed=13)
        ref = np.asarray(execute(prog, jnp.asarray(imgs)))

        status, _, body = _post(
            host, port, "/v1/run", {"image": imgs[0].tolist()}
        )
        assert status == 200
        out = json.loads(body)
        assert out["ok"] and out["label"] == int(np.argmax(ref[0]))

        # concurrent burst from real client threads
        results: list = [None] * 8
        def client(i):
            st, _, b = _post(host, port, "/v1/run",
                             {"image": imgs[i].tolist()})
            results[i] = (st, json.loads(b))
        threads = [
            threading.Thread(target=client, args=(i,)) for i in range(8)
        ]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        for i, (st, out) in enumerate(results):
            assert st == 200
            assert out["label"] == int(np.argmax(ref[i]))

        # streaming: chunked NDJSON, one line per request with its index
        status, _, body = _post(
            host, port, "/v1/stream",
            {"requests": [{"image": imgs[i].tolist()} for i in range(5)]},
        )
        assert status == 200
        lines = [json.loads(l) for l in body.decode().strip().splitlines()]
        assert sorted(l["index"] for l in lines) == list(range(5))
        for line in lines:
            assert line["ok"]
            assert line["label"] == int(np.argmax(ref[line["index"]]))

        status, body = _get(host, port, "/healthz")
        health = json.loads(body)
        assert status == 200 and health["ok"]
        assert health["batch_slots"] == 4

        status, body = _get(host, port, "/metrics")
        text = body.decode()
        assert status == 200
        assert "engine_service_completed_total" in text
        assert "engine_service_first_result_seconds" in text
        assert "engine_service_step_host_seconds_count" in text
        assert "engine_service_step_wait_seconds_count" in text
        assert "serve_http_requests_total" in text

        # error paths keep the server alive
        status, _, _ = _post(host, port, "/v1/run", {"nope": 1})
        assert status == 400
        status, _ = _get(host, port, "/nothing")
        assert status == 404

        # single trace through warmup + every socket-driven request
        assert sess.trace_count() == 1
        assert srv.completed == 14
        assert srv.meter.total == 14
    finally:
        srv.shutdown()


class _SlowBackend:
    """Protocol-conforming fake backend with a controllable step time —
    makes HTTP-level shedding deterministic without jit in the loop."""

    def __init__(self, batch_slots=1, max_queue=1, step_s=0.3):
        self.scheduler = SlotScheduler(batch_slots, max_queue=max_queue)
        self.step_s = step_s

    def try_submit(self, req):
        return self.scheduler.try_submit(req)

    def submit(self, req):
        self.scheduler.submit(req)

    def has_work(self):
        return self.scheduler.has_work()

    def step(self):
        self.scheduler.refill()
        live = list(self.scheduler.live())
        if not live:
            return []
        time.sleep(self.step_s)
        self.scheduler.record_step()
        done = []
        for slot, req in live:
            req.label = 0
            req.done = True
            self.scheduler.complete(slot)
            done.append(req)
        return done

    def trace_count(self):
        return 1

    @property
    def metrics(self):
        return self.scheduler.snapshot()

    def metrics_text(self):
        return self.scheduler.metrics.to_prometheus(prefix="fake")

    def reset_metrics(self):
        self.scheduler.reset_metrics()

    def warmup(self):
        pass


def test_http_load_shedding_429_and_admitted_never_dropped():
    backend = _SlowBackend(batch_slots=1, max_queue=1, step_s=0.4)
    srv = ServingServer(ServeSession(backend), admit_wait_s=0.0)
    host, port = srv.start_in_thread()
    outcomes = []
    lock = threading.Lock()

    def client():
        st, headers, body = _post(
            host, port, "/v1/run", {"image": [[0.0]]}, timeout=120
        )
        with lock:
            outcomes.append((st, headers, body))

    threads = [threading.Thread(target=client) for _ in range(6)]
    # admit up to capacity (1 slot + 1 queued) ...
    for t in threads[:2]:
        t.start()
    time.sleep(0.15)
    # ... then burst while the worker is mid-step: the queue is full
    for t in threads[2:]:
        t.start()
    for t in threads:
        t.join(timeout=120)
    srv.shutdown()

    ok = [o for o in outcomes if o[0] == 200]
    shed = [o for o in outcomes if o[0] == 429]
    assert len(ok) + len(shed) == 6
    assert ok and shed  # some served, some shed — never an exception
    for _, headers, body in shed:
        assert int(headers["Retry-After"]) >= 1  # honors backpressure
        payload = json.loads(body)
        assert payload["ok"] is False
        assert payload["error"] == "overloaded"
        assert payload["retry_after_s"] > 0
    # conservation: every admitted request completed, every shed request
    # was counted, nothing vanished
    m = backend.scheduler.metrics
    assert m.completed == m.admitted == len(ok)
    assert m.rejected == len(shed)


def test_http_stream_sheds_per_request_not_per_connection():
    backend = _SlowBackend(batch_slots=1, max_queue=1, step_s=0.2)
    srv = ServingServer(ServeSession(backend), admit_wait_s=0.0)
    host, port = srv.start_in_thread()
    try:
        status, _, body = _post(
            host, port, "/v1/stream",
            {"requests": [{"image": [[0.0]]} for _ in range(5)]},
            timeout=120,
        )
        assert status == 200  # the stream itself succeeds
        lines = [json.loads(l) for l in body.decode().strip().splitlines()]
        assert len(lines) == 5
        assert sorted(l["index"] for l in lines) == list(range(5))
        served = [l for l in lines if l["ok"]]
        shed = [l for l in lines if not l["ok"]]
        assert served and shed  # already-admitted work still ran
        for line in shed:
            assert line["error"] == "overloaded"
            assert line["retry_after_s"] > 0
    finally:
        srv.shutdown()


def test_response_shed_shape():
    r = Response.shed(2.5)
    out = r.to_json()
    assert out == {"ok": False, "error": "overloaded", "retry_after_s": 2.5}
