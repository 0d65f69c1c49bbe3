"""Device time outside the spmm kernel, per image (device trace): im2col,
transposes, the permutation gather, channel_norm, ReLU and the pools."""


def read(run):
    if run.trace is None or run.images == 0:
        return None
    return (run.trace.busy_s - run.trace.kernel_s) / run.images * 1e3
