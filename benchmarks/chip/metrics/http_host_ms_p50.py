"""Time of a request outside the scheduler, at the median: the client's
p50 latency minus the scheduler's enqueue-to-done p50
(``SchedulerMetrics.latency_hist``). A difference of medians, not the
median of a difference: it stands for JSON transport and parsing, the
event loop and the client's own wait."""

import numpy as np


def read(run):
    hist = run.scheduler.latency_hist
    if not run.latencies_s or hist.count == 0:
        return None
    client = float(np.percentile(run.latencies_s, 50, method="inverted_cdf"))
    return (client - hist.percentile(50)) * 1e3
