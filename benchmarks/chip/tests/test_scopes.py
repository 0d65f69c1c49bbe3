"""The scope reduction (``scopes.py``) on hand-built traces and HLO text
whose answers are known, and the ``step_host_ms`` reader.

Run from the repository root (these tests sit outside tier-1's
``testpaths``):

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import importlib.util
import sys
from pathlib import Path
from types import SimpleNamespace

import pytest
from jax.profiler import ProfileData

HERE = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(HERE))
import devtrace  # noqa: E402
import scopes  # noqa: E402

MS = 1_000_000  # ns


def _plane(pid, name, line_name, events, names):
    """events: [(metadata id, start ms, duration ms)]."""
    evs = "".join(
        f"events {{ metadata_id: {m} offset_ps: {int(s * MS * 1000)} "
        f"duration_ps: {int(d * MS * 1000)} }} "
        for m, s, d in events
    )
    meta = "".join(
        f"event_metadata {{ key: {k} value {{ id: {k} name: \"{v}\" }} }} "
        for k, v in names.items()
    )
    return (
        f"planes {{ id: {pid} name: \"{name}\" "
        f"lines {{ id: 1 name: \"{line_name}\" timestamp_ns: 0 {evs} }} {meta} }} "
    )


DEV_NAMES = {
    1: "%while.6 = (u32[], f32[8]) while(%tuple.1), body=%body",
    2: "%dynamic-update-slice.9 = f32[8] dynamic-update-slice(%p, %u, %i)",
    3: "%pattern_spmm.4 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p)",
    4: "%copy.1 = f32[8,128]{1,0} copy(f32[8,128]{1,0} %pattern_spmm.4)",
    5: "%fusion.7 = f32[8,128]{1,0} fusion(%copy.1)",
}
HOST_NAMES = {
    1: "window", 2: "service.step", 3: "service.refill", 4: "service.dispatch",
    5: "service.wait", 6: "service.complete", 7: "step",
}
OP_SCOPES = {
    "while.6": "conv2/patches",
    "dynamic-update-slice.9": "conv2/patches",
    "pattern_spmm.4": "conv2/spmm",
}


def _profile(dev, host):
    text = _plane(1, "/device:TPU:0", "XLA Ops", dev, DEV_NAMES)
    text += _plane(2, "/host:CPU", "python", host, HOST_NAMES)
    return ProfileData.from_text_proto(text)


def test_a_scope_counts_the_union_of_its_ops_not_the_sum():
    # window 0-100 ms; the loop 10-50 holds its update 20-40 (same scope);
    # the kernel 50-60; copy.1 60-65 has no scope
    dev = [(1, 10, 40), (2, 20, 20), (3, 50, 10), (4, 60, 5)]
    red = scopes.reduce_scopes(_profile(dev, [(1, 0, 100)]), OP_SCOPES)
    assert red["window_s"] == pytest.approx(0.100)
    assert red["busy_s"] == pytest.approx(0.055)
    assert red["scope_s"] == {
        "conv2/patches": pytest.approx(0.040),  # not 0.060
        "conv2/spmm": pytest.approx(0.010),
        scopes.UNSCOPED: pytest.approx(0.005),
    }
    assert sum(red["scope_s"].values()) == pytest.approx(red["busy_s"])
    # the harness's own reduction of the same trace reads as it always did
    s = devtrace.reduce_profile(_profile(dev, [(1, 0, 100)]), ())
    assert s.busy_s == pytest.approx(0.055)
    assert s.kernel_s == pytest.approx(0.010)
    assert dict(s.device_ops)["while.6"] == pytest.approx(0.040)
    assert dict(s.device_ops)["dynamic-update-slice.9"] == pytest.approx(0.020)


def test_idle_gaps_are_named_by_the_program_spans():
    # the host refills 0-10, dispatches 10-20, waits 20-60 and completes
    # 60-70; the device runs 15-60 and 75-80. Each idle gap goes whole to
    # the span covering most of it: 0-15 to the refill (10 of 15 ms),
    # 60-75 to the completion; 80-100 lies under no program span
    dev = [(3, 15, 45), (5, 75, 5)]
    host = [(1, 0, 100), (2, 0, 70), (3, 0, 10), (4, 10, 10), (5, 20, 40),
            (6, 60, 10), (7, 0, 100)]
    gaps = dict(devtrace.reduce_profile(
        _profile(dev, host), scopes.PROGRAM_SPANS).idle_gaps)
    assert gaps == {
        "service.refill": pytest.approx(0.015),
        "service.complete": pytest.approx(0.015),
        devtrace.NO_SPAN: pytest.approx(0.020),
    }


HLO = """\
HloModule jit_forward

%body (p: (u32[], f32[8])) -> (u32[], f32[8]) {
  %p = (u32[], f32[8]) parameter(0)
  %gte = f32[8] get-tuple-element(%p), index=1
  ROOT %dynamic-update-slice.9 = f32[8] dynamic-update-slice(%gte, %gte, %gte)
}

%fused_computation.1 (param_0: f32[8]) -> f32[8] {
  %param_0 = f32[8] parameter(0)
  ROOT %negate.1 = f32[8] negate(%param_0), metadata={op_name="jit(forward)/conv2/epilogue/neg"}
}

ENTRY %main (x: f32[8]) -> f32[8] {
  %x = f32[8] parameter(0), metadata={op_name="x"}
  %pad.6 = f32[8] pad(%x, %x), padding=0_0, metadata={op_name="jit(forward)/conv2/patches/jit(_pad)/pad" stack_frame_id=3}
  %copy_bitcast_fusion = f32[8] fusion(%pad.6), kind=kLoop, calls=%fused_computation.1
  %tuple.1 = (u32[], f32[8]) tuple(%x, %copy_bitcast_fusion)
  %while.6 = (u32[], f32[8]) while(%tuple.1), condition=%cond, body=%body
  %gte.2 = f32[8] get-tuple-element(%while.6), index=1
  %copy.5 = f32[8] copy(%x)
  %pattern_spmm.4 = f32[8] custom-call(%gte.2, %copy.5), metadata={op_name="jit(forward)/conv2/spmm/pallas_call"}
  ROOT %add.3 = f32[8] add(%pattern_spmm.4, %x), metadata={op_name="jit(forward)/conv2/permute/gather"}
}
"""


def test_op_scopes_reads_op_names_and_fills_compiler_made_instructions():
    got = scopes.op_scopes(HLO)
    assert got["pad.6"] == "conv2/patches"  # its own op_name
    assert got["pattern_spmm.4"] == "conv2/spmm"
    assert got["add.3"] == "conv2/permute"
    # no op_name: the scope its operands agree on
    assert got["copy_bitcast_fusion"] == "conv2/patches"
    assert got["tuple.1"] == got["while.6"] == "conv2/patches"
    # inside the loop body: the caller's scope
    assert got["dynamic-update-slice.9"] == "conv2/patches"
    # the one user decides where the operands say nothing
    assert got["copy.5"] == "conv2/spmm"
    # arguments carry their own name, not a scope
    assert "x" not in got


@pytest.mark.parametrize("op_name,scope", [
    ("jit(forward)/conv1/patches/jit(_pad)/pad", "conv1/patches"),
    ("jit(forward)/conv13/spmm/shard_map/pallas_call", "conv13/spmm"),
    ("jit(forward)/gap/epilogue/reduce_sum", "gap/epilogue"),
    ("jit(forward)/fc/permute", "fc/permute"),
    ("jit(forward)/reduce_sum", None),
    ("x", None),
])
def test_scope_of(op_name, scope):
    assert scopes.scope_of(op_name) == scope


def _reader(name):
    path = HERE / "metrics" / f"{name}.py"
    spec = importlib.util.spec_from_file_location("metric_" + name, path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def test_step_host_ms_reads_the_programs_counter():
    from repro.engine.scheduler import SchedulerMetrics

    read = _reader("step_host_ms")
    m = SchedulerMetrics(batch_slots=4)
    assert read(SimpleNamespace(scheduler=m)) is None  # no steps yet
    m.record_step_times(0.004, 0.5)
    m.record_step_times(0.006, 0.5)
    assert read(SimpleNamespace(scheduler=m)) == pytest.approx(5.0)
    # a program without the counter (the parent's) reads nothing
    assert read(SimpleNamespace(scheduler=SimpleNamespace())) is None
