"""queue_wait_p90_s: 90th percentile of arrival to first admission (s).

Both stamped on the wall clock at the engine's ``arrival`` and
``admit`` events, over the window's requests; the scheduler's queueing.
"""

from _lib import done_or_stop, p90


def read(ctx):
    """p90 over the window's requests of first admit - arrival."""
    return p90([done_or_stop(r.admit, ctx) - r.arrival for r in ctx.window
                if r.arrival == r.arrival])
