"""device_idle_share: share of the traced window with no device op (%)."""


def read(ctx):
    """1 - busy / window, from the profiler's trace."""
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    return 100.0 * (1.0 - ctx.trace.busy_s / ctx.trace.window_s)
