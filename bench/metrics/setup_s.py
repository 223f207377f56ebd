"""setup_s: process start to the first arrival of the schedule (s)."""


def read(ctx):
    """Loading, weights, compilation and warm-up, in seconds."""
    return ctx.setup_s
