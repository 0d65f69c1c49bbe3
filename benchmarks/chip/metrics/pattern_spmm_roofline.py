"""The spmm kernel's share of its roofline (device trace).

The chip's least time for the window's forwards, summed over the spmm
layers (every conv and the FC): each layer's least time is the larger of
its useful FLOPs over the peak and its minimal bytes over HBM bandwidth
(``work.py``), at the cell's batch. Over the summed device time of the
``pattern_spmm*`` events. Which bound sets each layer is logged.
"""

import sys


def read(run):
    if run.trace is None or run.peak is None or run.trace.kernel_s <= 0:
        return None
    b, peak, prec = run.batch_slots, run.peak, run.precision
    least = {"compute": 0.0, "memory": 0.0}
    for layer in run.layers:
        t = layer.seconds(b, peak, prec)
        bound = max(t, key=t.get)
        least[bound] += t[bound]
    print(
        f"[bench] spmm least time per forward: compute-bound layers "
        f"{least['compute']:.6e} s, memory-bound layers {least['memory']:.6e} s",
        file=sys.stderr,
    )
    return 100.0 * sum(least.values()) * run.forwards / run.trace.kernel_s
