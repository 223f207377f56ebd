"""Host-phase spans and counters of ``Engine.step``, and the model's scopes.

The recorder (`repro.metrics.spans`) is observation only: sim-mode
engines share the no-op, real-mode phase times nest inside their step,
and every program JAX builds during a step is credited to the phase that
built it. The named scopes on the device programs change nothing the
compiler emits apart from metadata.
"""

import re
import time

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_smoke_config
from repro.metrics.spans import NO_PHASES, PhaseRecorder
from repro.models.model import Model
from repro.serving.engine import Engine, EngineConfig
from repro.serving.request import Request

ARCH = "granite-3-8b"
INNER = ("engine.admit", "engine.schedule", "engine.apply", "engine.pages",
         "engine.flush", "engine.dispatch", "engine.fetch", "engine.tokens",
         "engine.book")


def _real_engine(model, params):
    ecfg = EngineConfig(mode="real", policy="trail", kv_layout="paged",
                        max_batch=2, max_len=64, page_size=4,
                        prefill_chunk=32, probe_interval=2)
    return Engine(model.cfg, ecfg, model=model, params=params)


def _request(rid, prompt_len, out_len, vocab):
    rng = np.random.default_rng(rid)
    return Request(rid=rid, arrival=0.0,
                   prompt=rng.integers(1, vocab, prompt_len).tolist(),
                   max_new_tokens=out_len, true_out_len=out_len)


@pytest.fixture(scope="module")
def smoke():
    model = Model(get_smoke_config(ARCH))
    return model, jax.jit(model.init)(jax.random.key(0))


# ---------------------------------------------------------------------------
# the recorder alone
# ---------------------------------------------------------------------------

def test_recorder_counts_nested_phases():
    rec = PhaseRecorder()
    with rec("outer"):
        with rec("inner"):
            pass
        with rec("inner"):
            jax.jit(lambda x: x * 3 + 1)(jnp.arange(7))    # a new program
    s = rec.snapshot()
    assert s["outer"]["calls"] == 1 and s["inner"]["calls"] == 2
    assert s["inner"]["seconds"] <= s["outer"]["seconds"]
    assert s["inner"]["max_s"] <= s["inner"]["seconds"]
    # the program counts in the innermost phase only
    assert s["inner"]["compiles"] >= 1 and s["outer"]["compiles"] == 0


def test_recorder_closes_a_phase_that_raises():
    rec = PhaseRecorder()
    with pytest.raises(ValueError):
        with rec("engine.step"):
            raise ValueError("boom")
    # nothing is left open: a later program is credited to no phase
    jax.jit(lambda x: x - 5)(jnp.arange(9))
    s = rec.snapshot()["engine.step"]
    assert s["calls"] == 1 and s["compiles"] == 0


def test_sim_engine_records_nothing():
    from repro.config import get_config
    from repro.serving.engine import run_policy
    from repro.serving.workload import WorkloadConfig, generate
    cfg = get_config(ARCH)
    eng = Engine(cfg, EngineConfig())
    assert eng.phases is NO_PHASES
    reqs = generate(WorkloadConfig(n_requests=8, request_rate=2.0, seed=1,
                                   vocab=1000, split_streams=True))
    run_policy(cfg, "trail", reqs)
    assert NO_PHASES.snapshot() == {}


# ---------------------------------------------------------------------------
# the engine's phases in real mode
# ---------------------------------------------------------------------------

@pytest.mark.real
def test_phases_nest_in_each_step_and_change_nothing(smoke):
    model, params = smoke
    vocab = model.cfg.vocab_size
    shapes = [(5, 6), (11, 3), (3, 9), (7, 5)]
    out = []
    for timed in (True, False):
        # four requests on two slots: the scheduler queues and may preempt
        eng = _real_engine(model, params)
        assert isinstance(eng.phases, PhaseRecorder)
        if not timed:
            eng.phases = NO_PHASES
        reqs = [_request(rid, p, o, vocab) for rid, (p, o) in enumerate(shapes)]
        for r in reqs:
            eng.submit(r)
        while eng.has_work():
            before = eng.phases.snapshot()
            t = time.perf_counter()
            eng.step()
            wall = time.perf_counter() - t
            after = eng.phases.snapshot()
            if timed:
                def grew(name, key="seconds"):
                    return (after.get(name, {}).get(key, 0)
                            - before.get(name, {}).get(key, 0))
                assert grew("engine.step", "calls") == 1
                inner = sum(grew(n) for n in INNER)
                assert inner <= grew("engine.step") <= wall
        assert set(eng.phases.snapshot()) == (
            {"engine.step", *INNER} if timed else set())
        s = eng.stats
        out.append(([r.generated for r in reqs], s.latencies, s.ttfts,
                    s.n_preemptions, s.iterations))
    # the recorder is observation only: same tokens, same schedule
    assert out[0] == out[1]


@pytest.mark.real
def test_new_programs_count_in_the_phase_that_built_them(smoke):
    model, params = smoke
    vocab = model.cfg.vocab_size
    # a model of its own, so its jitted steps compile afresh, and no
    # program of the process's in-memory caches left to reuse
    model = Model(model.cfg)
    eng = _real_engine(model, params)
    jax.clear_caches()
    seen = []

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            seen.append(duration)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        eng.submit(_request(0, 5, 3, vocab))            # prefill width 8
        while eng.has_work():
            eng.step()
        first = eng.phases.snapshot()
        assert first["engine.dispatch"]["compiles"] >= 2   # prefill, decode
        # the first request's pages are reset in the next step's flush (a
        # count of freed pages not seen since the caches were cleared),
        # and a 19-token prefill needs width 32, a program not built yet
        eng.submit(_request(1, 20, 3, vocab))
        while eng.has_work():
            eng.step()
        second = eng.phases.snapshot()
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    for name in ("engine.dispatch", "engine.flush"):
        assert second[name]["compiles"] > first[name]["compiles"], name
    # every program built while the engine stepped is credited to a phase
    credited = sum(v["compiles"] for v in second.values())
    assert credited == len(seen)


# ---------------------------------------------------------------------------
# named scopes on the device programs
# ---------------------------------------------------------------------------

def _hlo_body(text: str) -> str:
    """Compiled HLO text without source metadata or stack-frame tables."""
    blocks = [b for b in text.split("\n\n")
              if b.split("\n", 1)[0] not in ("FileNames", "FunctionNames",
                                             "FileLocations", "StackFrames")]
    return re.sub(r",?\s*metadata=\{[^}]*\}", "", "\n\n".join(blocks))


def _compiled(model):
    params = jax.eval_shape(model.init, jax.random.key(0))
    cache = jax.eval_shape(
        lambda: model.init_cache(2, 32, kv_layout="paged", page_size=8))
    i32 = jnp.int32
    row = jax.ShapeDtypeStruct((2,), i32)
    dec = jax.jit(lambda p, c, t, a, b: model.decode_multi(p, c, t, a, b, k=2))
    pre = jax.jit(lambda p, c, t, v: model.prefill_chunk(p, c, t, valid=v))
    return (dec.lower(params, cache, jax.ShapeDtypeStruct((2, 1), i32),
                      jax.ShapeDtypeStruct((2,), bool), row).compile().as_text(),
            pre.lower(params, cache, jax.ShapeDtypeStruct((2, 8), i32),
                      jax.ShapeDtypeStruct((2, 8), bool)).compile().as_text())


def test_named_scopes_change_only_metadata(monkeypatch):
    model = Model(get_smoke_config(ARCH))
    scoped = _compiled(model)
    dec, pre = scoped
    for scope in ("decode/", "/attention/", "/mlp/", "/probe/", "/lm_head/",
                  "/sample/"):
        assert scope in dec, scope
    for scope in ("prefill/", "/attention/", "/mlp/", "/lm_head/"):
        assert scope in pre, scope
    import contextlib
    monkeypatch.setattr(jax, "named_scope",
                        lambda name: contextlib.nullcontext())
    bare = _compiled(model)
    assert "/probe/" not in bare[0]
    for a, b in zip(scoped, bare):
        assert _hlo_body(a) == _hlo_body(b)
