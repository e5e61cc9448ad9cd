"""device_idle.tput: share of the traced window in which no operation
ran on the device (1 - busy / window), for the throughput cells."""


def read(ctx):
    r = ctx.reduction
    if r is None or r["busy_s"] is None:
        return None
    return 100.0 * (1.0 - r["busy_s"] / r["window_s"])
