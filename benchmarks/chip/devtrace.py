"""Reduce a profiler trace (``.xplane.pb``) to device time and idle gaps.

What it reads:

* device planes (``/device:TPU:<n>``), line ``XLA Ops``: one event per
  operation the device ran. Busy time is the union of their intervals
  inside the window; the idle share is one minus busy over the window.
  An event's name is the HLO instruction's text (``%pattern_spmm.15 =
  f32[...] custom-call(...)``); it is known by the instruction's name, the
  text before `` = ``.
* kernel events: ops whose instruction name starts with ``pattern_spmm``
  (the Pallas kernel, fp32 and int8 variants).
* host spans: the ``jax.profiler.TraceAnnotation`` spans that the
  benchmark puts around its own calls (``window`` around the measured
  window; ``step``, ``submit``, ``parse`` and the like inside it). Each
  idle gap on the device is named by the host span that covers most of
  it, or ``no host span`` when none does.

Times are seconds; device numbers are averaged over the device planes.
"""

from __future__ import annotations

import dataclasses
import glob
import os

import numpy as np

DEVICE_PREFIX = "/device:TPU:"
OPS_LINE = "XLA Ops"
KERNEL_MARK = "pattern_spmm"
WINDOW_SPAN = "window"
NO_SPAN = "no host span"


@dataclasses.dataclass
class TraceSummary:
    window_s: float
    busy_s: float  # union of device ops inside the window
    kernel_s: float  # summed durations of the kernel's events
    kernel_events: int
    n_devices: int
    device_ops: list  # [[op name, seconds]], most time first
    idle_gaps: list  # [[host span, idle seconds]], most idle first

    @property
    def idle_share(self) -> float:
        return 1.0 - self.busy_s / self.window_s


def find_xplane(log_dir: str) -> str:
    found = glob.glob(os.path.join(log_dir, "**", "*.xplane.pb"), recursive=True)
    if len(found) != 1:
        raise RuntimeError(f"expected one .xplane.pb under {log_dir}, got {found}")
    return found[0]


def _union(starts: np.ndarray, ends: np.ndarray) -> tuple[np.ndarray, np.ndarray]:
    """Merge intervals into sorted disjoint ones."""
    if len(starts) == 0:
        return starts, ends
    order = np.argsort(starts, kind="stable")
    s, e = starts[order], np.maximum.accumulate(ends[order])
    new = np.ones(len(s), bool)
    new[1:] = s[1:] > e[:-1]
    idx = np.flatnonzero(new)
    return s[idx], np.append(e[idx[1:] - 1], e[-1])


def _covered_before(s: np.ndarray, e: np.ndarray, t: np.ndarray) -> np.ndarray:
    """Length of the disjoint sorted intervals ``(s, e)`` lying before each
    point of ``t``."""
    if len(s) == 0:
        return np.zeros_like(t)
    cum = np.concatenate([[0.0], np.cumsum(e - s)])
    k = np.searchsorted(s, t, side="right") - 1
    kk = np.clip(k, 0, None)
    part = np.clip(t - s[kk], 0.0, (e - s)[kk])
    return np.where(k < 0, 0.0, cum[kk] + part)


def op_name(text: str) -> str:
    """``%pattern_spmm.15 = f32[...] custom-call(...)`` -> ``pattern_spmm.15``."""
    return text.split(" = ", 1)[0].strip().lstrip("%")


def reduce_profile(pd, host_spans: tuple[str, ...], top: int = 10) -> TraceSummary:
    """Reduce a ``jax.profiler.ProfileData`` to a :class:`TraceSummary`.

    ``host_spans``: the names of the benchmark's own host spans that may
    name an idle gap (the runtime's own host events are left out).
    """
    spans: dict[str, list[tuple[float, float]]] = {}
    devices = []
    for plane in pd.planes:
        if plane.name.startswith(DEVICE_PREFIX):
            for line in plane.lines:
                if line.name == OPS_LINE:
                    devices.append(list(line.events))
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for ev in line.events:
                    if ev.name != WINDOW_SPAN and ev.name not in host_spans:
                        continue
                    spans.setdefault(ev.name, []).append(
                        (ev.start_ns * 1e-9, (ev.start_ns + ev.duration_ns) * 1e-9)
                    )
    if WINDOW_SPAN not in spans:
        raise RuntimeError(f"no {WINDOW_SPAN!r} host span in the trace")
    if not devices or not any(devices):
        raise RuntimeError(f"no {OPS_LINE!r} events on any {DEVICE_PREFIX} plane")
    w0 = min(a for a, _ in spans[WINDOW_SPAN])
    w1 = max(b for _, b in spans[WINDOW_SPAN])
    host = {
        name: _union(*map(np.asarray, zip(*iv)))
        for name, iv in spans.items() if name != WINDOW_SPAN
    }

    busy = kernel = 0.0
    n_kernel = 0
    op_time: dict[str, float] = {}
    idle_by_span: dict[str, float] = {}
    for events in devices:
        names = [op_name(ev.name) for ev in events]
        s = np.array([ev.start_ns for ev in events], float) * 1e-9
        e = s + np.array([ev.duration_ns for ev in events], float) * 1e-9
        s, e = np.clip(s, w0, w1), np.clip(e, w0, w1)
        dur = e - s
        for name, d in zip(names, dur):
            if d <= 0:
                continue
            op_time[name] = op_time.get(name, 0.0) + d
            if name.startswith(KERNEL_MARK):
                kernel += d
                n_kernel += 1
        us, ue = _union(s[dur > 0], e[dur > 0])
        busy += float(np.sum(ue - us))
        gs = np.concatenate([[w0], ue])
        ge = np.concatenate([us, [w1]])
        keep = ge > gs
        gs, ge = gs[keep], ge[keep]
        if not host:
            idle_by_span[NO_SPAN] = idle_by_span.get(NO_SPAN, 0.0) + float(
                np.sum(ge - gs)
            )
            continue
        span_names = sorted(host)
        overlap = np.stack([
            _covered_before(*host[n], ge) - _covered_before(*host[n], gs)
            for n in span_names
        ])
        best = overlap.argmax(axis=0)
        covered = overlap.max(axis=0) > 0
        for i, name in enumerate(span_names):
            sel = covered & (best == i)
            if sel.any():
                idle_by_span[name] = idle_by_span.get(name, 0.0) + float(
                    np.sum(ge[sel] - gs[sel])
                )
        if (~covered).any():
            idle_by_span[NO_SPAN] = idle_by_span.get(NO_SPAN, 0.0) + float(
                np.sum(ge[~covered] - gs[~covered])
            )

    n = len(devices)
    return TraceSummary(
        window_s=w1 - w0,
        busy_s=busy / n,
        kernel_s=kernel / n,
        kernel_events=n_kernel,
        n_devices=n,
        device_ops=_top(op_time, n, top),
        idle_gaps=_top(idle_by_span, n, top),
    )


def _top(acc: dict[str, float], n: int, top: int) -> list:
    ranked = sorted(acc.items(), key=lambda kv: -kv[1])[:top]
    return [[name, t / n] for name, t in ranked]


def reduce_dir(log_dir: str, host_spans: tuple[str, ...], top: int = 10) -> TraceSummary:
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(find_xplane(log_dir))
    return reduce_profile(pd, host_spans, top)
