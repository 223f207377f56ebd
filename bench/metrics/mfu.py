"""mfu: useful model operations in the traced window over the bf16 peak (%).

Prompt tokens count once (positions past each request's high-water
mark, so recomputed prefill does not count) and decoded tokens count
each, at two operations per weight plus attention at their context,
with one row of logits per decoded token and per finished prompt.
"""

from flops import decode_work, prefill_flops


def read(ctx):
    """Useful operations over window seconds times peak operations/s."""
    if ctx.trace is None or ctx.trace.window_s <= 0:
        return None
    rows = [row for st in ctx.steps for row in st.decode_rows]
    spans = [sp for st in ctx.steps for sp in st.prefill]
    done = sum(st.prompts_done for st in ctx.steps)
    flops = decode_work(ctx.shapes, 0, ctx.k, rows)[0]
    flops += prefill_flops(ctx.shapes, spans, done)
    if flops <= 0:
        return None
    return 100.0 * flops / (ctx.trace.window_s * ctx.peaks["bf16_flops_per_s"])
