"""decode_ms_per_step: device time of one scanned decode step (ms).

The decode program's device time over its calls times k, the steps
each call scans.
"""

from _lib import DECODE, program


def read(ctx):
    """Decode program milliseconds per scanned step."""
    p = program(ctx, DECODE)
    if p is None or p[1] == 0:
        return None
    return 1e3 * p[0] / (p[1] * ctx.k)
