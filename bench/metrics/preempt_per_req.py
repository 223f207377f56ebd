"""preempt_per_req: preemptions per request due in the window (count)."""


def read(ctx):
    """Preempt events of the window's requests over their number."""
    if not ctx.window:
        return None
    return sum(r.preempts for r in ctx.window) / len(ctx.window)
