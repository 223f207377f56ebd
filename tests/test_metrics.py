"""Metrics-layer tests: streaming-percentile exactness vs numpy, event-log
invariants under hypothesis, rollup determinism, and the no-perturbation
guarantee (enabling the metrics layer changes no scheduling result)."""

import json

import numpy as np
import pytest

from _hypothesis_fallback import given, settings, st
from repro.config import get_config
from repro.metrics import (EventLog, StreamingQuantiles, check_invariants,
                           report_json, report_markdown, rollup)
from repro.metrics.events import Event
from repro.metrics.spans import PhaseRecorder
from repro.serving import engine as engine_mod
from repro.serving.costmodel import CostModel, HardwareSpec
from repro.serving.engine import run_policy
from repro.serving.workload import WorkloadConfig, generate

CFG = get_config("granite-3-8b")
HW = HardwareSpec(name="compute-bound-2tf", peak_flops=2e12, hbm_bw=819e9,
                  overhead_s=2e-4)


def _small_workload(seed=3, n=24, rate=1.2):
    wc = WorkloadConfig(n_requests=n, request_rate=rate, seed=seed,
                        vocab=1000, split_streams=True, out_median=24.0,
                        max_out=96)
    return generate(wc)


# ---------------------------------------------------------------------------
# streaming percentiles
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("n", [1, 2, 3, 17, 100, 999])
def test_streaming_percentiles_match_numpy(n):
    rng = np.random.default_rng(n)
    xs = rng.lognormal(1.0, 1.5, n)
    acc = StreamingQuantiles()
    for x in xs:
        acc.add(float(x))
    for q in (0.0, 10.0, 50.0, 90.0, 99.0, 99.9, 100.0):
        assert acc.percentile(q) == float(np.percentile(xs, q)), (n, q)
    s = acc.summary()
    assert s["n"] == n
    assert s["p99"] == float(np.percentile(xs, 99.0))
    assert s["mean"] == pytest.approx(float(np.mean(xs)))


def test_streaming_merge_and_order_invariance():
    rng = np.random.default_rng(0)
    xs = rng.normal(size=257)
    a = StreamingQuantiles(xs[:100])
    b = StreamingQuantiles(xs[100:])
    a.merge(b)
    whole = StreamingQuantiles(sorted(xs))     # different insertion order
    assert a.summary() == whole.summary()
    assert len(a) == 257


def test_streaming_attainment():
    acc = StreamingQuantiles([1.0, 2.0, 3.0, 4.0])
    assert acc.attainment(0.5) == 0.0
    assert acc.attainment(2.0) == 0.5          # <= is inclusive
    assert acc.attainment(100.0) == 1.0
    assert StreamingQuantiles().attainment(1.0) == 0.0


@settings(max_examples=40, deadline=None)
@given(st.lists(st.floats(min_value=0.0, max_value=1e6,
                          allow_nan=False), min_size=1, max_size=200),
       st.floats(min_value=0.0, max_value=100.0))
def test_streaming_percentile_property(xs, q):
    acc = StreamingQuantiles(xs)
    assert acc.percentile(q) == float(np.percentile(np.asarray(xs), q))


# ---------------------------------------------------------------------------
# event log + rollup semantics
# ---------------------------------------------------------------------------

def _hand_log():
    log = EventLog()
    log.emit(0.0, 1, "arrival")
    log.emit(1.0, 1, "admit")
    log.emit(2.0, 1, "first_token")
    log.emit(2.0, 1, "tokens", 1)
    log.emit(3.0, 1, "tokens", 2)       # megastep: 2 tokens, 1s gap
    log.emit(3.0, 1, "finish")
    return log


def test_rollup_hand_computed():
    rep = rollup(_hand_log())
    assert rep["requests"] == {"arrived": 1, "finished": 1,
                               "cancelled": 0, "goodput": 1.0,
                               "output_tokens": 3.0}
    assert rep["ttft"]["mean"] == 2.0
    assert rep["completion"]["mean"] == 3.0
    # megastep gap of 1s over 2 tokens -> two 0.5s TBT samples
    assert rep["tbt"]["n"] == 2
    assert rep["tbt"]["mean"] == 0.5
    assert rep["latency_per_token"]["mean"] == 1.0
    check_invariants(_hand_log())


def test_rollup_slowdown_needs_service_times():
    rep = rollup(_hand_log())
    assert "slowdown" not in rep
    rep = rollup(_hand_log(), service_times={1: 1.5})
    assert rep["slowdown"]["mean"] == 2.0


def test_rollup_counts_ttft_of_inflight_requests():
    """A started-but-unfinished request contributes its TTFT (it is
    determined at the first token) — mid-run rollups must not drop the
    long-stuck tail."""
    log = EventLog()
    log.emit(0.0, 1, "arrival")
    log.emit(9.0, 1, "first_token")
    log.emit(9.0, 1, "tokens", 1)       # still decoding, no finish
    rep = rollup(log)
    assert rep["requests"] == {"arrived": 1, "finished": 0,
                               "cancelled": 0, "goodput": 0.0,
                               "output_tokens": 1.0}
    assert rep["ttft"]["n"] == 1
    assert rep["ttft"]["mean"] == 9.0
    assert rep["completion"]["n"] == 0


def test_check_invariants_catches_violations():
    log = EventLog()
    log.emit(5.0, 1, "arrival")
    log.emit(4.0, 1, "admit")               # admitted before arrival
    with pytest.raises(AssertionError):
        check_invariants(log)
    log2 = EventLog()
    log2.emit(0.0, 2, "arrival")
    log2.emit(1.0, 2, "finish")             # finish without any token
    with pytest.raises(AssertionError):
        check_invariants(log2)


def test_event_log_merge_orders_by_time():
    a, b = EventLog(), EventLog()
    a.emit(2.0, 1, "admit")
    b.emit(1.0, 2, "arrival")
    a.merge(b)
    assert [e.t for e in a.events] == [1.0, 2.0]
    assert isinstance(a.events[0], Event)


# ---------------------------------------------------------------------------
# engine integration
# ---------------------------------------------------------------------------

@pytest.mark.parametrize("policy", ["trail", "fcfs", "srpt"])
def test_metrics_layer_does_not_perturb_results(policy, monkeypatch):
    """Acceptance pin: enabling the event log, or timing the engine's host
    phases, leaves every scheduling result byte-identical — latencies,
    TTFTs, preemption counts."""
    reqs = _small_workload()
    log = EventLog()
    s_with = run_policy(CFG, policy, reqs, hardware=HW, event_log=log,
                        mem_budget=1 << 26)
    s_without = run_policy(CFG, policy, reqs, hardware=HW,
                           mem_budget=1 << 26)
    assert s_with.latencies == s_without.latencies
    assert s_with.ttfts == s_without.ttfts
    assert s_with.n_preemptions == s_without.n_preemptions
    assert len(log) > 0
    # sim engines share the no-op recorder; a live one in its place
    rec = PhaseRecorder()
    monkeypatch.setattr(engine_mod, "NO_PHASES", rec)
    s_timed = run_policy(CFG, policy, reqs, hardware=HW, mem_budget=1 << 26)
    assert s_timed.summary() == s_without.summary()
    assert s_timed.latencies == s_without.latencies
    assert s_timed.ttfts == s_without.ttfts
    assert rec.snapshot()["engine.step"]["calls"] >= s_timed.iterations > 0


def test_engine_rollup_matches_engine_stats():
    """The rollup's completion/TTFT distributions are exactly the
    engine's own latency/TTFT lists — one source of truth."""
    reqs = _small_workload(seed=7)
    log = EventLog()
    stats = run_policy(CFG, "trail", reqs, hardware=HW, event_log=log)
    rep = rollup(log)
    assert rep["requests"]["finished"] == len(stats.latencies)
    assert rep["completion"]["mean"] == pytest.approx(
        float(np.mean(stats.latencies)))
    assert rep["ttft"]["mean"] == pytest.approx(float(np.mean(stats.ttfts)))
    assert rep["completion"]["p99"] == pytest.approx(
        float(np.percentile(stats.latencies, 99.0)))


def test_engine_rollup_deterministic_bytes():
    reqs = _small_workload(seed=11)
    outs = []
    for _ in range(2):
        log = EventLog()
        run_policy(CFG, "trail", reqs, hardware=HW, event_log=log)
        outs.append(report_json(rollup(log)))
    assert outs[0] == outs[1]
    json.loads(outs[0])                     # valid JSON


@settings(max_examples=15, deadline=None)
@given(seed=st.integers(0, 10_000),
       rate=st.floats(min_value=0.3, max_value=4.0),
       policy=st.sampled_from(["trail", "fcfs", "srpt", "sjf"]),
       mem_mb=st.sampled_from([192, 1 << 30]))
def test_event_log_invariants_property(seed, rate, policy, mem_mb):
    """Monotone timestamps, arrival<=admit<=first_token<=finish,
    TTFT <= completion, and exact token accounting — across random
    workloads, policies, and memory pressure."""
    reqs = generate(WorkloadConfig(n_requests=14, request_rate=rate,
                                   seed=seed, vocab=500,
                                   split_streams=True, out_median=16.0,
                                   max_out=48))
    log = EventLog()
    stats = run_policy(CFG, policy, reqs, hardware=HW, event_log=log,
                       mem_budget=mem_mb << 20)
    check_invariants(log)
    per_req = log.per_request()
    for r in reqs:
        evs = per_req[r.rid]
        toks = sum(e.value for e in evs if e.kind == "tokens")
        assert toks == min(r.true_out_len, r.max_new_tokens)
    n_preempt = sum(1 for e in log.events if e.kind == "preempt")
    assert n_preempt == stats.n_preemptions


@pytest.mark.parametrize("seed,policy,mem_mb",
                         [(0, "trail", 192), (1, "fcfs", 1 << 30),
                          (2, "srpt", 192), (3, "trail", 1 << 30)])
def test_event_log_invariants_fixed(seed, policy, mem_mb):
    """Deterministic slice of the hypothesis sweep above, so the
    invariants run even where hypothesis is unavailable."""
    reqs = generate(WorkloadConfig(n_requests=14, request_rate=1.5,
                                   seed=seed, vocab=500,
                                   split_streams=True, out_median=16.0,
                                   max_out=48))
    log = EventLog()
    stats = run_policy(CFG, policy, reqs, hardware=HW, event_log=log,
                       mem_budget=mem_mb << 20)
    check_invariants(log)
    per_req = log.per_request()
    for r in reqs:
        toks = sum(e.value for e in per_req[r.rid] if e.kind == "tokens")
        assert toks == min(r.true_out_len, r.max_new_tokens)
    assert sum(1 for e in log.events
               if e.kind == "preempt") == stats.n_preemptions


def test_step_result_exposes_events():
    from repro.serving.engine import Engine, EngineConfig
    reqs = _small_workload(seed=5, n=6)
    log = EventLog()
    eng = Engine(CFG, EngineConfig(policy="trail", hardware=HW),
                 event_log=log)
    for r in sorted(reqs, key=lambda r: r.arrival):
        eng.submit(r)
    seen = []
    while eng.has_work():
        seen.extend(eng.step().events)
    assert seen == log.events               # step slices cover the log


def test_markdown_emitter_renders_all_sections():
    reqs = _small_workload(seed=2)
    log = EventLog()
    run_policy(CFG, "trail", reqs, hardware=HW, event_log=log)
    md = report_markdown(rollup(log), title="t")
    assert "### t" in md
    for row in ("ttft", "tbt", "completion"):
        assert f"| {row} |" in md
    assert "SLO attainment (ttft):" in md
    assert "Counters:" in md


# ---------------------------------------------------------------------------
# cluster merge + seconds-unit backlog (satellite)
# ---------------------------------------------------------------------------

def test_cluster_event_merge_and_rollup():
    from repro.cluster import run_cluster
    reqs = _small_workload(seed=9, n=20, rate=2.0)
    stats = run_cluster(CFG, reqs, router_policy="jspw", n_replicas=2,
                        policy="trail", seed=5, hardware=HW,
                        record_events=True)
    assert stats.event_log is not None
    check_invariants(stats.event_log)
    rep = rollup(stats.event_log)
    assert rep["requests"]["finished"] == len(stats.latencies)
    assert rep["completion"]["mean"] == pytest.approx(
        float(np.mean(stats.latencies)))


def test_backlog_seconds_is_rate_normalized_backlog():
    from repro.serving.engine import Engine, EngineConfig
    eng = Engine(CFG, EngineConfig(policy="trail", hardware=HW))
    reqs = _small_workload(seed=4, n=8)
    for r in sorted(reqs, key=lambda r: r.arrival):
        eng.submit(r)
    for _ in range(4):
        eng.step()
    rate = CostModel(CFG, HW).decode_token_rate()
    assert eng.backlog_seconds() == pytest.approx(eng.backlog() / rate)
    assert eng.backlog_seconds(truncate=10.0) == pytest.approx(
        eng.backlog(truncate=10.0) / rate)


def test_jspw_dispatch_identical_across_backlog_units():
    """Satellite pin: with identical replicas, seconds-unit backlog is a
    shared positive rescale of tokens-unit backlog — the jspw dispatch
    sequence (and every latency) must be unchanged."""
    from repro.cluster import run_cluster
    reqs = _small_workload(seed=13, n=30, rate=2.5)
    runs = {}
    for unit in ("tokens", "seconds"):
        s = run_cluster(CFG, reqs, router_policy="jspw", n_replicas=3,
                        policy="trail", seed=5, hardware=HW,
                        backlog_unit=unit)
        runs[unit] = (s.dispatch_counts, sorted(s.latencies))
    assert runs["tokens"] == runs["seconds"]


def test_router_rejects_unknown_backlog_unit():
    from repro.cluster.router import Router, RouterConfig
    from repro.serving.engine import Engine, EngineConfig
    eng = Engine(CFG, EngineConfig(policy="trail", hardware=HW))
    with pytest.raises(ValueError, match="backlog_unit"):
        Router([eng], RouterConfig(n_replicas=1, backlog_unit="minutes"))


# ---------------------------------------------------------------------------
# tail counters + per-tenant splits (PR 8)
# ---------------------------------------------------------------------------

def test_rollup_max_wait_tracks_worst_first_token():
    log = EventLog()
    log.emit(0.0, 1, "arrival")
    log.emit(2.0, 1, "first_token")
    log.emit(2.0, 1, "tokens", 1)
    log.emit(2.5, 1, "finish")
    log.emit(1.0, 2, "arrival")
    log.emit(9.0, 2, "first_token")     # worst wait: 8s
    log.emit(9.0, 2, "tokens", 1)
    log.emit(9.5, 2, "finish")
    rep = rollup(log)
    assert rep["counters"]["max_wait_s"] == 8.0


def test_rollup_max_wait_charges_unstarted_requests():
    """A never-started request's wait runs to the log's last event —
    otherwise a starving request would vanish from the starvation
    metric exactly while it starves."""
    log = EventLog()
    log.emit(0.0, 1, "arrival")
    log.emit(1.0, 1, "first_token")
    log.emit(1.0, 1, "tokens", 1)
    log.emit(2.0, 1, "finish")
    log.emit(0.5, 2, "arrival")         # still waiting at t_end=12
    log.emit(12.0, 3, "arrival")
    rep = rollup(log)
    assert rep["counters"]["max_wait_s"] == 11.5


def test_rollup_preemptions_per_request():
    log = EventLog()
    for rid in (1, 2):
        log.emit(0.0, rid, "arrival")
        log.emit(1.0, rid, "first_token")
        log.emit(1.0, rid, "tokens", 1)
    log.emit(2.0, 1, "preempt")
    log.emit(3.0, 1, "preempt")
    log.emit(4.0, 1, "preempt")
    rep = rollup(log)
    assert rep["counters"]["preemptions"] == 3
    assert rep["counters"]["preemptions_per_request"] == 1.5


def test_rollup_per_tenant_split():
    log = EventLog()
    for rid, (t0, t1) in {1: (0.0, 2.0), 2: (0.0, 10.0),
                          3: (1.0, 4.0)}.items():
        log.emit(t0, rid, "arrival")
        log.emit(t1, rid, "first_token")
        log.emit(t1, rid, "tokens", 1)
        log.emit(t1 + 1.0, rid, "finish")
    rep = rollup(log, tenants={1: "chat", 2: "batch", 3: "chat"})
    per = rep["per_tenant"]
    assert set(per) == {"chat", "batch"}
    assert per["chat"]["ttft"]["n"] == 2
    assert per["batch"]["completion"]["mean"] == 11.0
    # absent by default: existing report structure is untouched
    assert "per_tenant" not in rollup(log)
