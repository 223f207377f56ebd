"""recompute_share: recomputed over prefilled tokens in the window (%).

``EngineStats.recomputed_tokens / prefilled_tokens``, both as they grew
over the measured window.
"""

from _lib import stat_delta


def read(ctx):
    """Share of the window's prefill that recomputed evicted KV."""
    pre = stat_delta(ctx, "prefilled_tokens")
    if not pre:
        return None
    return 100.0 * stat_delta(ctx, "recomputed_tokens") / pre
