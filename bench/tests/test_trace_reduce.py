"""The trace reduction, the operation and byte counts, and the peaks table.

The reduction is checked on intervals read from a trace recorded on one
TPU v5e (``data/v5e_trace.json.gz``: three engine steps of the
granite-3-8b-l20 cut serving chat traffic, with its ``/device:TPU:0``
``XLA Ops`` and ``XLA Modules`` lines and the ``bench.*`` host spans,
times in ns from the first span) and on hand-made intervals whose
answers are known. Runs on the CPU: ``python -m pytest bench/tests -q``.
"""

import gzip
import json
import sys
import types
from pathlib import Path

import pytest

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path[:0] = [str(BENCH)]

import flops  # noqa: E402
import spec  # noqa: E402
import trace_reduce as tr  # noqa: E402

I = tr.Interval


def test_union_merges_and_clips():
    u = tr.union([I("a", 0, 10), I("b", 5, 20), I("c", 30, 40), I("d", 38, 50)],
                 2, 45)
    assert u == [[2, 20], [30, 45]]


def test_summarize_known_intervals():
    ev = tr.Events(
        ops={"/device:TPU:0": [I("fusion.1", 100, 200), I("fusion.2", 150, 300),
                               I("copy.3", 600, 700)]},
        modules={"/device:TPU:0": [I("jit_prefill_chunk(3)", 100, 300),
                                   I("jit_decode_multi(7)", 600, 700)]},
        host=[I("bench.step", 0, 500), I("bench.idle", 500, 1000)])
    s = tr.summarize(ev)
    assert s.window_s == pytest.approx(1000e-9)
    assert s.busy_s == pytest.approx(300e-9)
    assert s.programs == {"jit_prefill_chunk": [pytest.approx(200e-9), 1],
                          "jit_decode_multi": [pytest.approx(100e-9), 1]}
    assert s.top_ops[0] == ["fusion.2", pytest.approx(150e-9)]
    # gaps: [0,100) step, [300,600) step->idle boundary at 450 -> step,
    # [700,1000) idle
    assert [g[0] for g in s.idle_gaps] == ["bench.step", "bench.idle",
                                           "bench.step"]
    assert [g[1] for g in s.idle_gaps] == pytest.approx([300e-9, 300e-9,
                                                         100e-9])


def test_program_name():
    assert tr.program_name("jit_decode_multi(12)") == "jit_decode_multi"
    assert tr.program_name("jit_prefill_chunk") == "jit_prefill_chunk"


def _fake_profile(planes):
    """A stand-in for ``jax.profiler.ProfileData``: planes, lines, events."""
    ns = types.SimpleNamespace
    return ns(planes=[ns(name=p, lines=[
        ns(name=line, events=[ns(name=n, start_ns=a, duration_ns=d)
                              for n, a, d in evs])
        for line, evs in lines.items()]) for p, lines in planes.items()])


def test_read_takes_device_lines_and_bench_spans():
    prof = _fake_profile({
        "/device:TPU:0": {"XLA Ops": [("%fusion.3 = bf16[2] fusion(x)", 10, 5)],
                          "XLA Modules": [("jit_decode_multi(4)", 8, 9)],
                          "Async XLA Ops": [("%copy-start", 0, 99)]},
        "/host:CPU": {"python3": [("bench.step", 0, 30), ("$builtins", 1, 2)],
                      "main/281": [("bench.idle", 30, 5)]},
        "/device:CUSTOM:Megascale Trace": {"x": [("y", 0, 1)]}})
    ev = tr.read(prof)
    assert ev.ops == {"/device:TPU:0": [I("fusion.3", 10, 15)]}
    assert ev.modules == {"/device:TPU:0": [I("jit_decode_multi(4)", 8, 17)]}
    assert ev.host == [I("bench.step", 0, 30), I("bench.idle", 30, 35)]


@pytest.fixture(scope="module")
def recorded():
    raw = json.loads(gzip.decompress((DATA / "v5e_trace.json.gz").read_bytes()))
    mk = lambda d: {k: [I(*x) for x in v] for k, v in d.items()}  # noqa: E731
    ev = tr.Events(mk(raw["ops"]), mk(raw["modules"]),
                   [I(*x) for x in raw["host"]])
    return ev, tr.summarize(ev)


def test_recorded_trace_busy_time(recorded):
    ev, s = recorded
    lo, hi = ev.host[0].start, ev.host[-1].end
    assert s.window_s == pytest.approx((hi - lo) * 1e-9)
    # busy time by another route: mark every covered nanosecond boundary
    edges = sorted({x for o in ev.ops["/device:TPU:0"]
                    for x in (max(o.start, lo), min(o.end, hi)) if o.end > lo})
    covered = sum(b - a for a, b in zip(edges, edges[1:])
                  if any(o.start <= a and b <= o.end
                         for o in ev.ops["/device:TPU:0"]))
    assert s.busy_s == pytest.approx(covered * 1e-9)
    assert 0 < s.busy_s <= s.window_s
    assert s.busy_s == pytest.approx(0.230767013, rel=1e-9)


def test_recorded_trace_programs_and_gaps(recorded):
    _, s = recorded
    assert s.programs["jit_decode_multi"][1] == 2
    assert s.programs["jit_prefill_chunk"][1] == 1
    assert s.programs["jit_decode_multi"][0] == pytest.approx(0.184628829)
    assert sum(v[0] for v in s.programs.values()) <= s.window_s
    # the longest idle gap lies inside a host step, the program's own time
    assert s.idle_gaps[0][0] == "bench.step"
    assert s.idle_gaps[0][1] == pytest.approx(0.374718599)
    assert [g[1] for g in s.idle_gaps] == sorted((g[1] for g in s.idle_gaps),
                                                 reverse=True)
    assert len(s.top_ops) == 10 and not any("=" in n for n, _ in s.top_ops)


def test_decode_work_counts():
    s = flops.Shapes(layers=2, d_model=8, heads=2, kv_heads=1, head_dim=4,
                     d_ff=16, vocab=10, param_bytes=2, kv_bytes=2,
                     probe_hidden=3, probe_bins=2)
    assert s.layer_params == 2 * 8 * 8 + 2 * 8 * 4 + 3 * 8 * 16
    # one call of k=2, one row at context 5 emitting 2 tokens
    f, b = flops.decode_work(s, 1, 2, [(5, 2)])
    per_tok = 2 * 2 * s.layer_params
    attn = 4 * 2 * 2 * 4
    assert f == per_tok * 2 + attn * (5 + 6) + 2 * 2 * 10 * 8
    kv = 2 * 2 * 1 * 4 * 2
    assert b == 2 * s.weight_bytes + (6 + 7) * kv
    assert s.weight_bytes == (2 * (s.layer_params + 16) + 80 + 8) * 2 + 4 * (24 + 6)


def test_prefill_flops_counts_each_position():
    s = flops.Shapes(2, 8, 2, 1, 4, 16, 10, 2, 2)
    # positions 3, 4, 5 attend 4, 5, 6 keys
    want = 3 * 2 * 2 * s.layer_params + 4 * 2 * 2 * 4 * (4 + 5 + 6)
    assert flops.prefill_flops(s, [(3, 3)], 0) == want
    assert flops.prefill_flops(s, [(3, 3)], 1) == want + 2 * 10 * 8


def test_peaks_table():
    p = spec.peaks("TPU v5 lite")
    assert p["bf16_flops_per_s"] == 197e12 and p["hbm_bytes_per_s"] == 819e9
    with pytest.raises(KeyError):
        spec.peaks("TPU v4")
