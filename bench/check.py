"""The comparison that decides ``correct``.

Once the window has closed and the engine is freed, a sample of the
requests that finished, drawn from the seed with the longest among
them, runs through the configuration's float32 reference: each prompt
with its served tokens, as one row. Two numbers are compared, each
against the limit in the cell file:

- ``logit_gap``: the widest gap by which a served token's logit lies
  below the reference's best logit at its position. Served tokens are
  greedy, so a correct step serves the reference's best token or one
  that rounding put level with it.
- ``probe_gap``: the largest difference between a probe posterior the
  engine handed its predictor and the reference's posterior at the same
  position.

A sample or a token that cannot be compared (a token outside the
vocabulary, no finished request) makes ``correct`` false.
"""

from __future__ import annotations

import numpy as np


def draw_sample(finished: list, rng: np.random.Generator, want_tokens: int,
                min_rows: int, preempted: set) -> list:
    """Finished requests to compare: the longest, a preempted one, more.

    ``finished`` holds ``Request`` objects; requests are added in a
    seeded order until the sample holds at least ``min_rows`` and their
    served tokens reach ``want_tokens``, so that rows from many slots
    and steps are compared, not only the longest request.
    """
    if not finished:
        return []
    pool = sorted(finished, key=lambda r: r.rid)
    longest = max(pool, key=lambda r: (len(r.generated), -r.rid))
    picked = [longest]
    resumed = [r for r in pool if r.rid in preempted and r is not longest]
    if resumed:
        picked.append(resumed[int(rng.integers(len(resumed)))])
    rest = [r for r in pool if r not in picked]
    for i in rng.permutation(len(rest)):
        if (len(picked) >= min_rows
                and sum(len(r.generated) for r in picked) >= want_tokens):
            break
        picked.append(rest[int(i)])
    return picked


def row_arrays(req, length: int):
    """(tokens, targets, served positions) of one request's row.

    Position ``s`` of ``tokens`` predicts ``targets[s]``; a request's
    served tokens sit at positions ``len(prompt) - 1`` onwards.
    """
    seq = list(req.prompt) + list(req.generated)
    tokens = np.zeros(length, np.int32)
    targets = np.zeros(length, np.int32)
    n = min(len(seq), length)
    tokens[:n] = seq[:n]
    targets[:n - 1] = seq[1:n]
    first = len(req.prompt) - 1
    served = np.arange(first, first + len(req.generated))
    return tokens, targets, served[served < length - 1]


def readings(ref, params, c, sample, posteriors: dict, length: int,
             vocab: int, quant=None) -> dict:
    """The compared numbers over ``sample``.

    With ``quant`` set, the reference at that precision stands in the
    program's place: at each served position it picks its own best
    token, and the float32 reference reads that token's gap; its probe
    posteriors stand in for the engine's.
    """
    gap, probe, tokens_read = 0.0, 0.0, 0
    for req in sample:
        if any(not 0 <= t < vocab for t in req.generated):
            return {"logit_gap": float("inf"), "probe_gap": float("inf"),
                    "tokens": tokens_read}
        tok, tgt, served = row_arrays(req, length)
        if quant is not None:
            _, best, post = ref.row_readings(params, tok, tgt, c=c, quant=quant)
            tgt = np.where(np.isin(np.arange(length), served),
                           np.asarray(best), tgt).astype(np.int32)
        else:
            post = None
        g, _, ref_post = ref.row_readings(params, tok, tgt, c=c)
        g = np.asarray(g)
        gap = max(gap, float(g[served].max(initial=0.0)))
        tokens_read += len(served)
        ref_post = np.asarray(ref_post)
        first = len(req.prompt) - 1
        if quant is not None:
            post = np.asarray(post)
            probe = max(probe, float(np.abs(post[served] - ref_post[served]).max(
                initial=0.0)))
            continue
        for n_gen in range(1, len(req.generated) + 1):
            p = posteriors.get((req.rid, n_gen))
            if p is not None and first + n_gen - 1 < length:
                probe = max(probe, float(np.abs(p - ref_post[first + n_gen - 1]).max()))
    return {"logit_gap": gap, "probe_gap": probe, "tokens": tokens_read}


def verdict(read: dict, limits: dict, unfinished: int = 0) -> tuple[bool, dict]:
    """(correct, {name: {"value", "limit"}}) for the limited numbers.

    ``unfinished`` counts requests due in the window that had not
    finished when the drain's cap ran out: an answer that never comes.
    Its limit is 0.
    """
    checks = {k: {"value": read[k], "limit": v} for k, v in limits.items()}
    checks["unfinished"] = {"value": unfinished, "limit": 0}
    ok = read.get("tokens", 0) > 0 and all(
        c["value"] <= c["limit"] for c in checks.values())
    return ok, checks
