"""Arithmetic the metric readers share."""

from __future__ import annotations

import math

import numpy as np

PREFILL = "jit_prefill_chunk"
DECODE = "jit_decode_multi"


def p90(values) -> float | None:
    """The 90th percentile (linear between ranks), or None when empty."""
    v = [x for x in values if not math.isnan(x)]
    return float(np.percentile(v, 90)) if v else None


def done_or_stop(t: float, ctx) -> float:
    """``t``, or the end of the run where ``t`` never came (a lower bound)."""
    return ctx.t_stop if math.isnan(t) else t


def program(ctx, name: str):
    """(seconds, calls) of a program in the traced window, or None."""
    if ctx.trace is None or name not in ctx.trace.programs:
        return None
    return ctx.trace.programs[name]


def stat_delta(ctx, key: str) -> float | None:
    """Growth of an engine counter over the measured window."""
    s = ctx.stats
    if "start" not in s or "end" not in s:
        return None
    return s["end"][key] - s["start"][key]
