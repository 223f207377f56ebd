"""Open-loop arrival schedules drawn from a traffic file and a seed.

A traffic file (``bench/traffic/<name>.json``) holds parameters only:

    {"arrival": "poisson",
     "prompt": {"median": 44, "sigma": 0.6, "min": 4, "max": 512},
     "output": {"median": 48, "sigma": 1.0, "min": 1, "max": 512}}

Lengths are lognormal with the given median and sigma, truncated to
``int`` and clipped to ``[min, max]``, the arithmetic of
``repro.serving.workload.sample_{prompt,output}_length``. Arrivals are
Poisson at the cell's rate.

Every segment of a run (lead-in, measured window, tail) gets its own
streams, derived from ``--seed``: prompt sizes, output sizes, arrival
gaps and token contents each draw from a stream of their own. Draws are
stratified: a segment of ``n`` requests takes one uniform from each of
the ``n`` equal strata of (0, 1) and shuffles them, so each request's
size is still drawn from the lognormal, while the sizes of the segment
as a whole (and so the work a run offers) barely change from seed to
seed. A segment of ``n`` arrivals over ``T`` seconds is a Poisson
process conditioned on ``n`` arrivals in ``T``: ``n + 1`` exponential
gaps, scaled to sum to ``T``.
"""

from __future__ import annotations

import math
from dataclasses import dataclass
from statistics import NormalDist

import numpy as np

_NORMAL = NormalDist()
_STREAMS = {"prompt": 1, "output": 2, "gaps": 3, "tokens": 4}
_SEGMENTS = {"lead_in": 1, "window": 2, "tail": 3, "warmup": 4}


@dataclass
class Arrival:
    """One scheduled request: due time (s, window start = 0) and sizes."""

    due: float
    prompt: list[int]
    out_len: int
    segment: str

    @property
    def counted(self) -> bool:
        """Whether the request is due in the measured window."""
        return self.segment == "window"


def stream(seed: int, segment: str, name: str) -> np.random.Generator:
    """The generator of one named stream of one segment of ``seed``."""
    return np.random.default_rng(
        [seed % (1 << 64), _SEGMENTS[segment], _STREAMS[name]])


def stratified_uniforms(rng: np.random.Generator, n: int) -> np.ndarray:
    """``n`` uniforms, one from each stratum of (0, 1), shuffled."""
    u = (np.arange(n) + rng.random(n)) / max(n, 1)
    return rng.permutation(u)


def lognormal_lengths(u: np.ndarray, spec: dict) -> np.ndarray:
    """Lognormal quantiles at ``u``, truncated and clipped to the spec."""
    z = np.array([_NORMAL.inv_cdf(min(max(x, 1e-12), 1 - 1e-12)) for x in u])
    v = np.exp(math.log(spec["median"]) + spec["sigma"] * z)
    return np.clip(v.astype(np.int64), spec["min"], spec["max"])


def poisson_times(rng: np.random.Generator, n: int, span: float) -> np.ndarray:
    """``n`` sorted arrival times in ``[0, span)`` of a Poisson process."""
    gaps = -np.log1p(-stratified_uniforms(rng, n + 1))
    return span * np.cumsum(gaps)[:n] / gaps.sum()


def segment(traffic: dict, seed: int, name: str, rate: float, start: float,
            span: float, vocab: int) -> list[Arrival]:
    """The arrivals of one segment: ``round(rate * span)`` requests."""
    if traffic["arrival"] != "poisson":
        raise ValueError(f"unknown arrival process {traffic['arrival']!r}")
    n = int(round(rate * span))
    if n == 0:
        return []
    plen = lognormal_lengths(
        stratified_uniforms(stream(seed, name, "prompt"), n), traffic["prompt"])
    olen = lognormal_lengths(
        stratified_uniforms(stream(seed, name, "output"), n), traffic["output"])
    due = start + poisson_times(stream(seed, name, "gaps"), n, span)
    tok = stream(seed, name, "tokens")
    return [Arrival(float(due[i]), tok.integers(1, vocab, int(plen[i])).tolist(),
                    int(olen[i]), name) for i in range(n)]


def schedule(traffic: dict, seed: int, rate: float, seconds: float,
             lead_in: float = 0.0, tail: float = 0.0,
             vocab: int = 32000) -> list[Arrival]:
    """A run's arrivals: lead-in before 0, the window, the tail after it."""
    out = segment(traffic, seed, "lead_in", rate, -lead_in, lead_in, vocab)
    out += segment(traffic, seed, "window", rate, 0.0, seconds, vocab)
    out += segment(traffic, seed, "tail", rate, seconds, tail, vocab)
    return out
