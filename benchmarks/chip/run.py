"""Run one benchmark cell once, on the chip, and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \
        --seconds <s> --trace <0|1>

From the root of a checkout. The cell (``BENCHMARK.json``) names a
configuration, whose pruned weights come from the configuration's fixed
``weight_seed``, and a traffic mix; ``--seed`` draws the images, their
order and the arrival times. Set-up compiles the program and warms every
shape the window uses; the window then runs for ``--seconds``. With
``--trace 1`` the window is traced by the profiler and the per-layer
metrics are reported; with ``--trace 0`` the end-to-end metrics.

Every answer the window produced is then compared with the plain float32
reference (``model.py``) on the same pruned weights; ``correct`` says
whether all of them lie within the configuration's limit. The last lines
on stderr give each compared number beside its limit, and the last stdout
line is the result as JSON. Off a TPU, or with fewer chips than the cell
asks for, it exits non-zero and prints no result.
"""

from __future__ import annotations

import time

T_START = time.monotonic()  # set-up is timed from here

import argparse  # noqa: E402
import base64  # noqa: E402
import contextlib  # noqa: E402
import dataclasses  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

HERE = Path(__file__).resolve().parent
ROOT = HERE.parents[1]
sys.path.insert(0, str(HERE))

import spec as cellspec  # noqa: E402
import traffic  # noqa: E402
import work  # noqa: E402
from model import logits_in_blocks, make_weights  # noqa: E402

CACHE_DIR = ROOT / ".jax_cache"
# host spans the benchmark puts around its calls into the program; they
# name the device's idle gaps in the trace
HOST_SPANS = ("step", "submit", "parse")
REF_BLOCK = 64  # reference rows per call
ANSWER_TIMEOUT_S = 60.0  # how long past the window's close an answer may take


class NoChip(RuntimeError):
    pass


@dataclasses.dataclass
class Run:
    """What a window measured; the metric readers read it."""

    cell: cellspec.Cell
    seconds: float
    setup_s: float = 0.0
    warmup_s: float = 0.0
    window_s: float = 0.0  # host clock, start of the first step to the end of the last
    images: int = 0  # images completed in the window
    forwards: int = 0  # batches the program ran in the window
    latencies_s: list | None = None  # per request, due time to response
    scheduler: object = None  # the program's SchedulerMetrics for the window
    trace: object = None  # devtrace.TraceSummary with --trace 1
    layers: list | None = None  # work.LayerWork per spmm layer
    peak: dict | None = None
    compiles: int = 0  # XLA compilations inside the window

    @property
    def batch_slots(self) -> int:
        return int(self.cell.mix["batch_slots"])

    @property
    def precision(self) -> str:
        return self.cell.config.precision


def _log(msg: str) -> None:
    print(f"[bench] {msg}", file=sys.stderr, flush=True)


def _use_checkout_cache() -> None:
    """JAX's persistent compile cache lives at a fixed path in the checkout,
    and caches every program, however quick its compile."""
    os.environ["JAX_COMPILATION_CACHE_DIR"] = str(CACHE_DIR)
    import jax

    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)


def _device_check(chips: int):
    import jax

    devs = jax.devices()
    if devs[0].platform != "tpu":
        raise NoChip(f"JAX runs on {devs[0].platform!r}, not a TPU")
    if len(devs) < chips:
        raise NoChip(f"the cell needs {chips} chips, JAX sees {len(devs)}")
    return devs[:chips]


def _annotate(fn, name: str):
    import jax

    def wrapped(*a, **k):
        with jax.profiler.TraceAnnotation(name):
            return fn(*a, **k)

    return wrapped


class _CompileCounter:
    """Counts XLA compilations (to show that none happens in the window);
    one per process, since a listener cannot be removed."""

    _one = None

    def __init__(self):
        import jax

        self.n = 0
        jax.monitoring.register_event_duration_secs_listener(self._on)

    @classmethod
    def get(cls) -> "_CompileCounter":
        if cls._one is None:
            cls._one = cls()
        return cls._one

    def _on(self, event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            self.n += 1


@contextlib.contextmanager
def _profiled(on: bool, run: Run):
    """Trace the window with the profiler when ``on``; reduce the trace
    into ``run.trace``."""
    import jax

    compiles = _CompileCounter.get()
    n0 = compiles.n
    if not on:
        yield
        run.compiles = compiles.n - n0
        return
    import devtrace

    log_dir = tempfile.mkdtemp(prefix="chip-trace-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.enable_hlo_proto = False
    try:
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            with jax.profiler.TraceAnnotation(devtrace.WINDOW_SPAN):
                yield
            run.compiles = compiles.n - n0
        finally:
            jax.profiler.stop_trace()
        run.trace = devtrace.reduce_dir(log_dir, HOST_SPANS)
    finally:
        shutil.rmtree(log_dir, ignore_errors=True)


# ------------------------------------------------------------------ drivers


def drive_offline(session, run: Run, images: np.ndarray, seed: int,
                  trace: bool) -> list:
    """Closed loop in the serving process: keep at least two batches queued,
    ``step`` until the window has run ``run.seconds``. Returns the answers
    ``[(pool index, logits)]`` completed in the window."""
    from repro.serve import Request

    b = run.batch_slots
    order = traffic.request_order(seed, len(images) * 64, len(images))
    pool_of: dict[int, int] = {}
    n = 0

    def top_up():
        nonlocal n
        while session.scheduler.queued() < 2 * b:
            req = Request(image=images[order[n % len(order)]])
            pool_of[id(req)] = int(order[n % len(order)])
            session.submit(req)
            n += 1

    # warm the window's path: host buffers, transfers, two full steps
    for _ in range(2):
        top_up()
        for req in session.step():
            pool_of.pop(id(req), None)
    session.reset_metrics()
    step, submit = session.step, top_up
    if trace:
        step, submit = _annotate(step, "step"), _annotate(top_up, "submit")
    done = []
    batches0 = session.backend.batches_run
    run.setup_s = time.monotonic() - T_START
    with _profiled(trace, run):
        t0 = time.perf_counter()
        while True:
            submit()
            done.extend(step())
            if time.perf_counter() - t0 >= run.seconds:
                break
        run.window_s = time.perf_counter() - t0
    run.forwards = session.backend.batches_run - batches0
    run.images = len(done)
    run.scheduler = session.scheduler.metrics
    return [(pool_of[id(r)], np.asarray(r.logits)) for r in done]


def start_loadgen(cell: cellspec.Cell, seed: int, seconds: float):
    """Start the client process; it builds its bodies while the program
    compiles."""
    mix, cfg = cell.mix, cell.config
    child = subprocess.Popen(
        [sys.executable, str(HERE / "loadgen.py")],
        stdin=subprocess.PIPE, stdout=subprocess.PIPE, text=True,
    )
    child.stdin.write(json.dumps({
        "seed": seed, "pool": mix["pool"], "channels": cfg.conv_channels[0][0],
        "hw": cfg.input_hw, "arrivals": mix["arrivals"], "seconds": seconds,
        "connections": mix["connections"], "warm": mix["warm"],
        "timeout_s": ANSWER_TIMEOUT_S,
    }) + "\n")
    child.stdin.flush()
    return child


def _talk(child, obj: dict | None) -> dict:
    if obj is not None:
        child.stdin.write(json.dumps(obj) + "\n")
        child.stdin.flush()
    line = child.stdout.readline()
    if not line:
        raise RuntimeError(f"load generator ended (exit {child.wait()})")
    return json.loads(line)


def drive_http(session, run: Run, child, trace: bool):
    """Open loop over HTTP: ``ServingServer`` in this process, the client in
    ``child``. Returns ``(answers, attempted, failed, unanswered)``."""
    from repro.serve import ServingServer

    server = ServingServer(session, warmup=False)
    if trace:
        svc = session.backend
        svc.step = _annotate(svc.step, "step")
        session.submit = _annotate(session.submit, "submit")
        server._load_json = _annotate(server._load_json, "parse")
    _host, port = server.start_in_thread()
    try:
        _talk(child, None)  # bodies ready
        warm = _talk(child, {"port": port})["warm"]
        if warm != run.cell.mix["warm"]:
            raise RuntimeError(f"{warm}/{run.cell.mix['warm']} warm-up requests answered")
        session.reset_metrics()
        batches0 = session.backend.batches_run
        run.setup_s = time.monotonic() - T_START
        with _profiled(trace, run):
            t0 = time.perf_counter()
            out = _talk(child, {"go": True})
            run.window_s = time.perf_counter() - t0
        run.forwards = session.backend.batches_run - batches0
        run.scheduler = session.scheduler.metrics
    finally:
        server.shutdown()
    _log(f"load generator lateness: {json.dumps(out['late'])}")
    reqs = out["requests"]
    ok = [r for r in reqs if r.get("status") == 200]
    run.images = len(ok)
    run.latencies_s = [r["done"] - r["due"] for r in reqs if "done" in r]
    answers = [
        (r["pool"], np.frombuffer(base64.b64decode(r["logits"]), np.float32))
        for r in ok
    ]
    unanswered = sum(1 for r in reqs if r.get("status", 0) == 0)
    return answers, len(reqs), len(reqs) - len(ok), unanswered


# ------------------------------------------------------------------- a run


def compare(answers: list, ref: np.ndarray) -> float:
    """Widest ``max|logit - ref| / max|ref|`` over the answers, each against
    the reference logits of its own image. An answer that is not finite
    everywhere reads ``inf``."""
    worst = 0.0
    for pool, got in answers:
        want = ref[pool]
        err = float(np.abs(got - want).max() / np.abs(want).max())
        if not np.isfinite(err):
            return float("inf")
        worst = max(worst, err)
    return worst


def build_session(cell: cellspec.Cell):
    """The cell's pruned weights and its program's serving session: the
    host compile as a user runs it, then ``classify_session`` at the mix's
    batch."""
    from repro.engine import CompileOptions, compile_network
    from repro.models.cnn import CNNConfig
    from repro.serve import classify_session

    cfg = cell.config
    params, bits = make_weights(cfg)
    net = CNNConfig(
        conv_channels=cfg.conv_channels, pool_after=cfg.pool_after,
        num_classes=cfg.num_classes, input_hw=cfg.input_hw, kernel=cfg.kernel,
    )
    prog = compile_network(net, params, bits, options=CompileOptions(
        precision=cfg.precision, **cfg.raw["compile"],
    ))
    return params, classify_session(prog, batch_slots=cell.mix["batch_slots"])


def run_cell(cell: cellspec.Cell, seed: int, seconds: float, trace: bool,
             require_chip: bool = True) -> dict:
    child = None
    if cell.mix["mode"] == "http":
        child = start_loadgen(cell, seed, seconds)
    try:
        return _run_cell(cell, seed, seconds, trace, require_chip, child)
    finally:
        if child is not None:
            child.kill()
            child.wait()


def _run_cell(cell, seed, seconds, trace, require_chip, child) -> dict:
    _use_checkout_cache()
    import jax

    devices = _device_check(cell.chips) if require_chip else jax.devices()[:1]
    cfg, mix = cell.config, cell.mix
    _log(f"set-up: JAX on {devices[0].device_kind} at "
         f"{time.monotonic() - T_START:.2f} s")
    params, session = build_session(cell)
    _log(f"set-up: weights and host compile done at "
         f"{time.monotonic() - T_START:.2f} s")
    run = Run(cell=cell, seconds=seconds)
    t = time.monotonic()
    session.warmup()
    run.warmup_s = time.monotonic() - t
    _log(f"set-up: warmup {run.warmup_s:.2f} s")
    images = traffic.make_images(
        seed, mix["pool"], cfg.conv_channels[0][0], cfg.input_hw
    )
    if mix["mode"] == "offline":
        answers = drive_offline(session, run, images, seed, trace)
        attempted, failed, unanswered = len(answers), 0, 0
    else:
        answers, attempted, failed, unanswered = drive_http(
            session, run, child, trace
        )
    _log(f"compiles in the window: {run.compiles}")
    stats = [d.memory_stats() or {} for d in devices]
    peak_bytes = max(s.get("peak_bytes_in_use", 0) for s in stats)
    _log(f"device memory stats: {stats}")
    _log(f"served program: {session.backend.lower().compile().memory_analysis()}")
    kind = devices[0].device_kind

    # the program's state goes before the reference runs
    del session
    gc.collect()
    jax.clear_caches()
    t = time.monotonic()
    ref = logits_in_blocks(cfg, params, images, min(REF_BLOCK, len(images)))
    _log(f"reference over {len(images)} images: {time.monotonic() - t:.1f} s")
    err = compare(answers, ref)
    limit = cfg.logit_rel_err_limit
    checks = {
        "logit_rel_err": {"value": err if np.isfinite(err) else str(err),
                          "limit": limit},
        "unanswered": {"value": unanswered, "limit": 0},
        "answers_min": {"value": len(answers), "limit": 1},
    }
    correct = err <= limit and unanswered == 0 and len(answers) >= 1

    run.layers = work.network_work(cfg, params, cfg.precision)
    run.peak = work.peak_for(kind) if require_chip else None
    metrics = {}
    for m in (cell.per_layer if trace else cell.end_to_end):
        value = cellspec.reader(m["name"])(run)
        if value is not None:
            metrics[m["name"]] = {"value": value, "unit": m["unit"]}
    device = {
        "platform": devices[0].platform, "kind": kind, "count": len(devices),
        "memory_peak_bytes": peak_bytes,
    }
    result = {
        "correct": bool(correct), "attempted": attempted, "failed": failed,
        "metrics": metrics, "device": device,
    }
    if trace:
        device["busy_s"] = run.trace.busy_s
        device["window_s"] = run.trace.window_s
        result["breakdown"] = {
            "device_ops": run.trace.device_ops,
            "idle_gaps": run.trace.idle_gaps,
        }
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if not (ROOT / "src" / "repro").is_dir():
        print("run.py: no src/repro in this checkout: nothing to measure",
              file=sys.stderr)
        return 2
    sys.path.insert(0, str(ROOT / "src"))
    cell = cellspec.load_cell(Path.cwd(), args.workload)
    try:
        result = run_cell(cell, args.seed, args.seconds, bool(args.trace))
    except NoChip as e:
        print(f"run.py: {e}; no result", file=sys.stderr)
        return 3
    for name, c in result["checks"].items():
        print(f"check {name}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
