"""Host time of a serving step, mean over the window's steps, from the
program's own counter ``SchedulerMetrics.step_host_hist``: the refill,
the dispatch and the completion of ``InferenceService.step``, without the
wait for the device's result (``step_wait_hist``, logged beside it). A
program without the counter reads nothing."""

import sys


def read(run):
    host = getattr(run.scheduler, "step_host_hist", None)
    if host is None or host.count == 0:
        return None
    wait = run.scheduler.step_wait_hist
    print(
        f"[bench] serving step over {host.count} steps: host "
        f"{host.mean * 1e3:.4f} ms, wait for the device {wait.mean * 1e3:.4f} ms",
        file=sys.stderr,
    )
    return host.mean * 1e3
