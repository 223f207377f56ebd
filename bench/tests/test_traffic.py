"""The schedule generator: one seed gives one schedule, with the file's medians.

Runs on the CPU: ``python -m pytest bench/tests -q``.
"""

import json
import sys
from pathlib import Path

import numpy as np
import pytest

BENCH = Path(__file__).resolve().parents[1]
sys.path.insert(0, str(BENCH))

import traffic  # noqa: E402

CHAT = json.loads((BENCH / "traffic" / "paper-chat.json").read_text())
BIG_SEED = 2**31 + 12345


def _key(arrivals):
    return [(a.due, tuple(a.prompt), a.out_len, a.segment) for a in arrivals]


@pytest.mark.parametrize("seed", [0, 7, BIG_SEED, 2**40 + 3])
def test_one_seed_gives_one_schedule(seed):
    a = traffic.schedule(CHAT, seed, 4.0, 30.0, lead_in=5.0, tail=10.0,
                         vocab=49155)
    b = traffic.schedule(CHAT, seed, 4.0, 30.0, lead_in=5.0, tail=10.0,
                         vocab=49155)
    assert _key(a) == _key(b)
    c = traffic.schedule(CHAT, seed + 1, 4.0, 30.0, lead_in=5.0, tail=10.0,
                         vocab=49155)
    assert _key(a) != _key(c)


def test_segments_counts_and_order():
    arr = traffic.schedule(CHAT, BIG_SEED, 4.0, 30.0, lead_in=5.0, tail=10.0)
    seg = [a.segment for a in arr]
    assert seg.count("lead_in") == 20 and seg.count("window") == 120
    assert seg.count("tail") == 40
    for name, lo, hi in (("lead_in", -5.0, 0.0), ("window", 0.0, 30.0),
                         ("tail", 30.0, 40.0)):
        due = [a.due for a in arr if a.segment == name]
        assert due == sorted(due) and lo <= min(due) and max(due) < hi
    assert sum(a.counted for a in arr) == 120


def test_window_does_not_depend_on_lead_in_or_tail():
    a = traffic.schedule(CHAT, 11, 4.0, 30.0, lead_in=5.0, tail=10.0)
    b = traffic.schedule(CHAT, 11, 4.0, 30.0)
    assert _key([x for x in a if x.counted]) == _key(b)


@pytest.mark.parametrize("seed", [1, 2, BIG_SEED])
def test_drawn_medians_match_the_file(seed):
    arr = traffic.segment(CHAT, seed, "window", 50.0, 0.0, 40.0, 49155)
    plen = np.array([len(a.prompt) for a in arr])
    olen = np.array([a.out_len for a in arr])
    # stratified draws: the sample median sits within one stratum of the
    # lognormal's median, truncated to int as the program truncates
    assert abs(np.median(plen) - CHAT["prompt"]["median"]) <= 1
    assert abs(np.median(olen) - CHAT["output"]["median"]) <= 1
    assert plen.min() >= CHAT["prompt"]["min"] and plen.max() <= CHAT["prompt"]["max"]
    assert olen.min() >= CHAT["output"]["min"] and olen.max() <= CHAT["output"]["max"]
    # the log-sizes spread by the file's sigma
    assert abs(np.std(np.log(olen)) - CHAT["output"]["sigma"]) < 0.1
    assert abs(np.std(np.log(plen)) - CHAT["prompt"]["sigma"]) < 0.1


def test_seeds_offer_nearly_the_same_work():
    tot = [sum(a.out_len for a in traffic.segment(CHAT, s, "window", 4.0, 0.0,
                                                  40.0, 49155))
           for s in range(12)]
    assert (max(tot) - min(tot)) / np.median(tot) < 0.2


def test_poisson_gaps_have_the_rate():
    t = traffic.poisson_times(traffic.stream(3, "window", "gaps"), 2000, 500.0)
    gaps = np.diff(t)
    assert abs(gaps.mean() - 0.25) < 0.01
    assert abs(gaps.std() / gaps.mean() - 1.0) < 0.1     # exponential: cv 1
