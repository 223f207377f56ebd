"""tpot_p90_ms: 90th percentile of the time per output token (ms).

(finish - first token) / (tokens - 1) over every request due in the
window that served two tokens or more; stalls from preemption and from
prefill between decode steps land here. An unfinished request counts
with the run's end as its finish, a lower bound.
"""

from _lib import done_or_stop, p90


def read(ctx):
    """p90 of per-request time per output token, in milliseconds."""
    vals = []
    for r in ctx.window:
        n = sum(k for _, k in r.tokens)
        if r.out_len >= 2 and n >= 1:
            vals.append(1e3 * (done_or_stop(r.finish, ctx) - r.first)
                        / (r.out_len - 1))
    return p90(vals)
