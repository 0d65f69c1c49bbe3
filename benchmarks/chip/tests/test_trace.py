"""The trace reduction on a hand-built trace whose answer is known.

Run from the repository root (these tests sit outside tier-1's
``testpaths``):

    PYTHONPATH=src JAX_PLATFORMS=cpu python -m pytest -q benchmarks/chip/tests
"""

import sys
from pathlib import Path

import pytest
from jax.profiler import ProfileData

sys.path.insert(0, str(Path(__file__).resolve().parents[1]))
import devtrace as chip_trace  # noqa: E402

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


def _profile(device_events, host_events):
    # device events are named by their HLO text; copy.1 reads the kernel's
    # output and is not the kernel
    dev_names = {
        1: "%pattern_spmm.4 = f32[8,128]{1,0} custom-call(f32[8,128]{1,0} %p)",
        2: "%fusion.3 = f32[8,128]{1,0} fusion(f32[8,128]{1,0} %pattern_spmm.4)",
        3: "%copy.1 = f32[8,128]{1,0} copy(f32[8,128]{1,0} %pattern_spmm.4)",
    }
    host_names = {1: "window", 2: "step", 3: "submit", 4: "TpuExecute"}
    text = _plane(1, "/device:TPU:0", "XLA Ops", device_events, dev_names)
    text += _plane(2, "/host:CPU", "python", host_events, host_names)
    return ProfileData.from_text_proto(text)


def test_busy_idle_kernel_and_gap_names():
    # window 0..100 ms; device ops: kernel 10-30 and 40-50, fusion 30-35
    # (abuts the kernel), copy 45-48 (overlaps the kernel)
    dev = [(1, 10, 20), (2, 30, 5), (1, 40, 10), (3, 45, 3)]
    # host: step covers 0-60, submit 60-90; TpuExecute is the runtime's own
    # span and must not name a gap
    host = [(1, 0, 100), (2, 0, 60), (3, 60, 30), (4, 0, 100)]
    s = chip_trace.reduce_profile(_profile(dev, host), ("step", "submit"))
    assert s.window_s == pytest.approx(0.100)
    # union: 10-35 and 40-50
    assert s.busy_s == pytest.approx(0.035)
    assert s.idle_share == pytest.approx(0.65)
    assert s.kernel_s == pytest.approx(0.030)
    assert s.kernel_events == 2
    ops = dict(s.device_ops)
    assert ops["pattern_spmm.4"] == pytest.approx(0.030)
    assert ops["fusion.3"] == pytest.approx(0.005)
    assert s.device_ops[0][0] == "pattern_spmm.4"
    # gaps: 0-10 (step), 35-40 (step), 50-100: step 50-60, submit 60-90,
    # none 90-100 -> most overlap is submit (30 of 50 ms)
    gaps = dict(s.idle_gaps)
    assert gaps["step"] == pytest.approx(0.015)
    assert gaps["submit"] == pytest.approx(0.050)
    assert "TpuExecute" not in gaps
    assert sum(gaps.values()) == pytest.approx(0.065)


def test_ops_outside_the_window_are_clipped():
    dev = [(1, 0, 20), (2, 95, 10)]
    host = [(1, 10, 90)]
    s = chip_trace.reduce_profile(_profile(dev, host), ())
    assert s.window_s == pytest.approx(0.090)
    assert s.busy_s == pytest.approx(0.015)  # 10-20 and 95-100
    assert dict(s.idle_gaps) == {chip_trace.NO_SPAN: pytest.approx(0.075)}


def test_a_trace_without_device_ops_is_an_error():
    with pytest.raises(RuntimeError, match="XLA Ops"):
        chip_trace.reduce_profile(_profile([], [(1, 0, 10)]), ())
