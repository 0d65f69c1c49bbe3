"""Set-up: process start to the window's first request (host clock).

Includes loading, weight synthesis, the host compile, the XLA compile or
its load from the persistent cache, and the warm-up steps.
"""


def read(run):
    return run.setup_s
