"""Attribute a traced window's device time to the forward's ``<layer>/<stage>``
scopes, and its idle gaps to the program's own host spans.

    python3 benchmarks/chip/scopes.py --workload <cell> --seed <n> \
        --seconds <s> [--out FILE]

A builder's tool for the chip, beside ``run.py``, which it uses: it builds
the cell's session, warms it and runs the offline window under the
profiler exactly as ``run.py --trace 1`` does, then reduces the same trace
twice: as the harness does (``devtrace.reduce_profile``), and by scope
here. Prints the (layer, stage) table, the unscoped share, the idle gaps
named by the program's spans, the step counters, the spans' own cost and
the per-layer drift against the crossbar model, then one JSON line.

The scope of a device operation is read from its ``op_name`` in the
served executable's compiled HLO text, keyed by instruction name (the
name ``devtrace.op_name`` gives an event); the profiler's ``XLA Ops``
events carry no ``op_name`` of their own on a v5e. Per scope, time is the union of
its operations' intervals in the window, so an operation nested in
another of the same scope (a ``dynamic-update-slice`` inside a ``while``)
counts once.
"""

from __future__ import annotations

import argparse
import json
import re
import sys
import time
from pathlib import Path

import numpy as np

HERE = Path(__file__).resolve().parent
sys.path.insert(0, str(HERE))

import devtrace  # noqa: E402

STAGES = ("patches", "spmm", "permute", "epilogue", "stats")
# ``.../<layer>/<stage>/...`` in an op_name; the leftmost pair is the scope
SCOPE = re.compile(rf"(?:^|/)([^/]+)/({'|'.join(STAGES)})(?:/|$)")
UNSCOPED = "unscoped"
# the program's host spans that may name an idle gap: the serving step's
# phases and the HTTP front end's (their parent ``service.step`` would
# cover every gap the phases do)
PROGRAM_SPANS = (
    "service.refill", "service.dispatch", "service.wait", "service.complete",
    "serve.parse", "serve.admit",
)
STEP_SPANS = 5  # service.step and its four phases, per step
_COMPUTATION = re.compile(r"^(?:ENTRY\s+)?%([^\s(]+)\s*\(")
_INSTR = re.compile(r"^\s+(?:ROOT\s+)?%([^\s=]+)\s*=\s*(.*)$")
_OP_NAME = re.compile(r'op_name="([^"]*)"')
_CALLED = re.compile(r"(?:calls|body|condition|to_apply)=%([^\s,{}]+)")
_REF = re.compile(r"%([^\s,(){}]+)")


def scope_of(op_name: str) -> str | None:
    m = SCOPE.search(op_name)
    return f"{m.group(1)}/{m.group(2)}" if m else None


def op_scopes(hlo_text: str) -> dict[str, str]:
    """Instruction name -> ``layer/stage`` for a compiled HLO module's text.

    An instruction's own ``op_name`` decides. The compiler makes some
    instructions that carry none (layout copies, the loops it splits a
    large relayout into); such an instruction takes the one scope its
    operands agree on, else the one its users agree on, and one inside a
    loop or call body takes its caller's.
    """
    comp_of: dict[str, str] = {}  # instruction -> its computation
    operands: dict[str, list[str]] = {}
    calls: dict[str, list[str]] = {}  # instruction -> computations it calls
    scope: dict[str, str] = {}
    comp = None
    for line in hlo_text.splitlines():
        if m := _COMPUTATION.match(line):
            comp = m.group(1)
            continue
        m = _INSTR.match(line)
        if not m or comp is None:
            continue
        name, rest = m.group(1), m.group(2)
        comp_of[name] = comp
        calls[name] = _CALLED.findall(rest)
        operands[name] = _REF.findall(rest.split(", metadata=")[0])
        if (op := _OP_NAME.search(rest)) and (sc := scope_of(op.group(1))):
            scope[name] = sc
    users: dict[str, list[str]] = {}
    callers: dict[str, list[str]] = {}  # computation -> instructions calling it
    for name in comp_of:
        for o in operands[name]:
            if o in comp_of:
                users.setdefault(o, []).append(name)
        for c in calls[name]:
            callers.setdefault(c, []).append(name)

    def agreed(names) -> str | None:
        found = {scope[n] for n in names if n in scope}
        return found.pop() if len(found) == 1 else None

    rules = (
        lambda n: agreed(operands[n]),
        lambda n: agreed(callers.get(comp_of[n], ())),
        lambda n: agreed(users.get(n, ())),
    )
    grew = True
    while grew:  # each rule to a fixpoint, the surer ones first
        grew = False
        for rule in rules:
            changed = True
            while changed:
                changed = False
                for name in comp_of:
                    if name not in scope and (sc := rule(name)):
                        scope[name] = sc
                        changed = grew = True
    return scope


def reduce_scopes(pd, scopes: dict[str, str]) -> dict:
    """Device seconds per scope in the ``window`` span, each the union of
    its operations' intervals, averaged over the device planes, with the
    busy time (the union of all operations); an operation missing from
    ``scopes`` counts as ``UNSCOPED``."""
    window = [
        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
        for plane in pd.planes if plane.name.startswith("/host:")
        for line in plane.lines for ev in line.events
        if ev.name == devtrace.WINDOW_SPAN
    ]
    if not window:
        raise RuntimeError(f"no {devtrace.WINDOW_SPAN!r} host span in the trace")
    w0, w1 = min(a for a, _ in window), max(b for _, b in window)
    scope_s: dict[str, float] = {}
    busy = 0.0
    devices = [
        list(line.events)
        for plane in pd.planes if plane.name.startswith(devtrace.DEVICE_PREFIX)
        for line in plane.lines if line.name == devtrace.OPS_LINE
    ]
    for events in devices:
        per: dict[str, list] = {}
        for ev in events:
            s = min(max(ev.start_ns * 1e-9, w0), w1)
            e = min(max((ev.start_ns + ev.duration_ns) * 1e-9, w0), w1)
            if e <= s:
                continue
            scope = scopes.get(devtrace.op_name(ev.name), UNSCOPED)
            per.setdefault(scope, []).append((s, e))
        every = [iv for ivs in per.values() for iv in ivs]
        busy += _covered(every)
        for scope, ivs in per.items():
            scope_s[scope] = scope_s.get(scope, 0.0) + _covered(ivs)
    n = max(len(devices), 1)
    return {
        "window_s": w1 - w0,
        "busy_s": busy / n,
        "scope_s": {k: v / n for k, v in sorted(scope_s.items())},
    }


def _covered(intervals: list) -> float:
    if not intervals:
        return 0.0
    s, e = devtrace._union(*map(np.asarray, zip(*intervals)))
    return float(np.sum(e - s))


def stage_totals(scope_s: dict[str, float]) -> dict[str, float]:
    """Seconds per stage over every layer, plus ``unscoped``."""
    out: dict[str, float] = {}
    for scope, t in scope_s.items():
        stage = scope.split("/")[-1] if scope != UNSCOPED else UNSCOPED
        out[stage] = out.get(stage, 0.0) + t
    return out


def span_cost_us(n: int = 100_000) -> dict:
    """Microseconds per ``service.step``-style span of the no-op tracer,
    with no profiler running and while one records."""
    import tempfile

    import jax
    from repro.obs.trace import NULL_TRACER

    def per_span() -> float:
        t = time.perf_counter()
        for _ in range(n):
            with NULL_TRACER.span("service.step", cat="serve", batch_slots=32):
                pass
        return (time.perf_counter() - t) / n * 1e6

    off = per_span()
    with tempfile.TemporaryDirectory() as d:
        jax.profiler.start_trace(d)
        try:
            on = per_span()
        finally:
            jax.profiler.stop_trace()
    return {"off": off, "on": on}


def _log(msg: str) -> None:
    print(f"[scopes] {msg}", file=sys.stderr, flush=True)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", default=None)
    args = ap.parse_args(argv)
    sys.path.insert(0, str(HERE.parents[1] / "src"))
    import run as harness
    import spec as cellspec
    import traffic
    from jax.profiler import ProfileData

    cell = cellspec.load_cell(Path.cwd(), args.workload)
    if cell.mix["mode"] != "offline":
        raise SystemExit("scopes.py drives offline cells only")
    harness._use_checkout_cache()
    devices = harness._device_check(cell.chips)
    _log(f"device {devices[0].device_kind}")
    _params, session = harness.build_session(cell)
    session.warmup()
    hlo = session.backend.lower().compile().as_text()
    scopes = op_scopes(hlo)
    _log(f"{len(scopes)} scoped instructions in the served executable")

    # keep the window's trace: the harness deletes its directory after
    # reducing it
    kept = {}
    reduce_dir = devtrace.reduce_dir

    def reduce_and_keep(log_dir, host_spans, top=10):
        kept["pd"] = ProfileData.from_file(devtrace.find_xplane(log_dir))
        return reduce_dir(log_dir, host_spans, top)

    devtrace.reduce_dir = reduce_and_keep
    run = harness.Run(cell=cell, seconds=args.seconds)
    images = traffic.make_images(
        args.seed, cell.mix["pool"], cell.config.conv_channels[0][0],
        cell.config.input_hw,
    )
    harness.drive_offline(session, run, images, args.seed, trace=True)
    pd = kept["pd"]
    red = reduce_scopes(pd, scopes)
    program_idle = devtrace.reduce_profile(pd, PROGRAM_SPANS).idle_gaps
    harness_view = run.trace
    busy, images_n = red["busy_s"], run.images
    scope_s = red["scope_s"]
    scoped = sum(t for k, t in scope_s.items() if k != UNSCOPED)
    _log(f"window {red['window_s']:.4f} s, busy {busy:.4f} s (harness "
         f"{harness_view.busy_s:.4f} s), {images_n} images, "
         f"{run.forwards} forwards")
    _log(f"scope sum {sum(scope_s.values()):.4f} s = "
         f"{100 * sum(scope_s.values()) / busy:.2f}% of busy; scoped "
         f"{100 * scoped / busy:.2f}%, unscoped "
         f"{100 * scope_s.get(UNSCOPED, 0.0) / busy:.2f}%")
    for scope, t in sorted(scope_s.items(), key=lambda kv: -kv[1]):
        _log(f"  {scope:22s} {t:9.4f} s  {t / images_n * 1e3:8.4f} ms/image")
    stages = stage_totals(scope_s)
    for stage, t in sorted(stages.items(), key=lambda kv: -kv[1]):
        _log(f"  stage {stage:16s} {t / images_n * 1e3:8.4f} ms/image")
    _log(f"idle by program span: {program_idle}")
    m = run.scheduler
    step = {
        "steps": m.steps,
        "host_ms_mean": m.step_host_hist.mean * 1e3,
        "wait_ms_mean": m.step_wait_hist.mean * 1e3,
    }
    _log(f"steps: {step}")
    layers: dict[str, float] = {}
    for scope, t in scope_s.items():
        if scope != UNSCOPED:
            layer = scope.split("/")[0]
            layers[layer] = layers.get(layer, 0.0) + t
    drift = session.backend.program.hardware_report(observed=layers)["drift"]
    _log(f"drift vs the crossbar model: max |share drift| "
         f"{drift['max_abs_share_drift']:.3f}, rate spread "
         f"{drift['rate_spread']:.2f}")
    cost = span_cost_us()
    _log(f"span cost: {cost['off']:.3f} us with no profiler, {cost['on']:.3f} us "
         f"recording; {STEP_SPANS} spans a step")
    result = {
        "workload": args.workload, "seed": args.seed,
        "device": devices[0].device_kind,
        "trace_count": session.trace_count(),
        "images": images_n, "forwards": run.forwards,
        "window_s": red["window_s"], "busy_s": busy,
        "harness_busy_s": harness_view.busy_s,
        "scope_s": scope_s,
        "stage_ms_per_image": {k: v / images_n * 1e3 for k, v in stages.items()},
        "idle_by_program_span": program_idle,
        "harness_idle_gaps": harness_view.idle_gaps,
        "step": step,
        "span_cost_us": cost,
        "drift": drift,
    }
    line = json.dumps(result)
    if args.out:
        Path(args.out).parent.mkdir(parents=True, exist_ok=True)
        Path(args.out).write_text(line + "\n")
    print(line, flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
