"""95th-percentile wait in the scheduler's queue, enqueue to admission
(``SchedulerMetrics.queue_wait_hist`` over the window, host clock)."""


def read(run):
    hist = run.scheduler.queue_wait_hist
    if hist.count == 0:
        return None
    return hist.percentile(95) * 1e3
