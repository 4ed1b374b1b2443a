"""device_idle_pct.solve: share of the traced window in which no operation
ran on the device, 100 * (1 - busy / window), from the profiler's trace."""


def read(ctx):
    if ctx.trace is None:
        return None
    return 100.0 * ctx.trace.idle_share
