"""The whole forward's share of the chip's peak (device trace window).

Useful FLOPs per image (``work.py``) times images completed per second of
the traced window, over the chip's peak for the program's precision.
"""

import work


def read(run):
    if run.trace is None or run.peak is None or run.trace.window_s <= 0:
        return None
    flops = sum(layer.flops_per_image for layer in run.layers)
    rate = run.images / run.trace.window_s
    return 100.0 * flops * rate / work.peak_flops(run.peak, run.precision)
