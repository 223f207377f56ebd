"""prefill_share: device time of the prefill program over busy time (%)."""

from _lib import PREFILL, program


def read(ctx):
    """Prefill program seconds over the device's busy seconds."""
    p = program(ctx, PREFILL)
    if p is None or ctx.trace.busy_s <= 0:
        return None
    return 100.0 * p[0] / ctx.trace.busy_s
