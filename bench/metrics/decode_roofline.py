"""decode_roofline: least time of the traced decode calls over their time (%).

The least time is the larger of bytes over the chip's memory bandwidth
and operations over its bf16 peak, counted from shapes by
``flops.decode_work``: every weight once per scanned step, and each
row's keys and values at its context. The count is the algorithm's,
whatever implements attention.
"""

from _lib import DECODE, program
from flops import decode_work


def read(ctx):
    """Roofline share of the decode program in the traced window."""
    p = program(ctx, DECODE)
    if p is None or p[1] == 0 or p[0] <= 0:
        return None
    rows = [row for st in ctx.steps for row in st.decode_rows]
    flops, nbytes = decode_work(ctx.shapes, p[1], ctx.k, rows)
    least = max(nbytes / ctx.peaks["hbm_bytes_per_s"],
                flops / ctx.peaks["bf16_flops_per_s"])
    return 100.0 * least / p[0]
