"""``ServeSession.warmup``: the XLA compile of the served forward, or its
load from the persistent compile cache (host clock)."""


def read(run):
    return run.warmup_s
