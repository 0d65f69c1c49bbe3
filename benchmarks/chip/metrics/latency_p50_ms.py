"""Median latency over all requests due in the window, from each request's
scheduled send time to its complete response (host clock, client side)."""

import numpy as np


def read(run):
    if not run.latencies_s:
        return None
    return float(np.percentile(run.latencies_s, 50, method="inverted_cdf")) * 1e3
