"""The comparison that decides ``correct``, at a size a CPU test can hold.

Drives ``run.run`` end to end without the look for a chip, on a
three-layer stand-in of the Granite configurations
(``data/tiny-granite.json``) under a small chat mix:

- a sound run is correct;
- the control (the reference at int8, in the program's place) is not;
- each fault planted in the timed path under the engine makes
  ``correct`` false: a decode step that returns its cache unchanged,
  half of the batch left out of decode (its rows handed the other
  half's tokens), a token altered where the
  decode step produces it. The cells run on one chip, so there is no
  exchange between chips to leave out.

    JAX_PLATFORMS=cpu python -m pytest bench/tests -q
"""

import json
import os
import sys
from pathlib import Path

os.environ.setdefault("JAX_PLATFORMS", "cpu")

import pytest  # noqa: E402

BENCH = Path(__file__).resolve().parents[1]
DATA = BENCH / "tests" / "data"
sys.path[:0] = [str(BENCH), str(BENCH.parent / "src")]

SEED = 2**31 + 77


def tiny_cell():
    import spec
    bench = json.loads((BENCH.parent / "BENCHMARK.json").read_text())
    lat = "g8b-l20.chat"
    return spec.Cell(
        name=lat, workload={"name": lat, "chips": 1},
        config=json.loads((DATA / "tiny-granite.json").read_text()),
        traffic=json.loads((DATA / "tiny.traffic.json").read_text()),
        params=json.loads((DATA / "tiny.cell.json").read_text()),
        end_to_end=[m for m in bench["end_to_end"]
                    if lat in m.get("workloads", [lat])],
        per_layer=[])


def run_tiny(control=None):
    import run
    return run.run(tiny_cell(), SEED, 3.0, False, platform=None,
                   log=lambda msg: None, control=control)


@pytest.fixture(scope="module")
def sound():
    return run_tiny(control="int8")


def test_sound_run_is_correct(sound):
    assert sound["correct"], sound["checks"]
    assert sound["attempted"] > 0 and sound["failed"] == 0
    assert sound["program"]["tokens"] >= 50


def test_control_is_not_correct(sound):
    import check
    limits = json.loads((DATA / "tiny.cell.json").read_text())["limits"]
    ok, checks = check.verdict(sound["control"], limits)
    assert not ok, checks


def _unchanged_state(orig):
    def decode_multi(self, params, cache, tokens, active=None, budget=None,
                     *, k=1, eos_id=-1):
        toks, _, probs, n = orig(self, params, cache, tokens, active, budget,
                                 k=k, eos_id=eos_id)
        return toks, cache, probs, n
    return decode_multi


def _half_batch(orig):
    def decode_multi(self, params, cache, tokens, active=None, budget=None,
                     *, k=1, eos_id=-1):
        toks, cache, probs, n = orig(self, params, cache, tokens, active,
                                     budget, k=k, eos_id=eos_id)
        half = tokens.shape[0] // 2
        # rows of the first half get the second half's tokens, not theirs
        return toks.at[:half].set(toks[half:2 * half]), cache, probs, n
    return decode_multi


def _token_altered(orig):
    import jax.numpy as jnp

    def decode_multi(self, params, cache, tokens, active=None, budget=None,
                     *, k=1, eos_id=-1):
        toks, cache, probs, n = orig(self, params, cache, tokens, active,
                                     budget, k=k, eos_id=eos_id)
        first = toks[:, :1]
        bent = jnp.where(first >= 0, (first + 1) % self.cfg.vocab_size, first)
        return toks.at[:, :1].set(bent), cache, probs, n
    return decode_multi


@pytest.mark.parametrize("fault", [_unchanged_state, _half_batch,
                                   _token_altered])
def test_fault_in_the_timed_path_is_not_correct(fault, monkeypatch):
    from repro.models import model as model_mod
    monkeypatch.setattr(model_mod.Model, "decode_multi",
                        fault(model_mod.Model.decode_multi))
    model_mod._build_cached.cache_clear()
    try:
        res = run_tiny()
    finally:
        model_mod._build_cached.cache_clear()
    assert not res["correct"], res["checks"]


def test_warm_resets_leaves_no_reset_mask_to_build():
    """After the warm-up, freeing any count of pages or slots builds nothing."""
    import jax
    import run
    import serve
    import spec
    import weights
    from repro.models.model import build_model
    conf = tiny_cell().config
    cfg = spec.model_config(conf)
    model = build_model(cfg)
    params = weights.make_params(jax.eval_shape(model.init, jax.random.key(0)),
                                 SEED)
    pool = serve.build_engine(cfg, conf, model, params, SEED).pool
    run.warm_resets(pool)
    compiles = run.Compiles()
    for n in (1, 3, 2 * pool.pages_per_seq):
        pool.blocks.ensure(-7, n * pool.page_size)
        pool.blocks.free_request(-7)
        pool.flush_resets()
    for n in (1, pool.n_slots):
        for r in range(-1, -1 - n, -1):
            pool.assign(r)
        for r in range(-1, -1 - n, -1):
            pool.release(r)
        pool.flush_resets()
    jax.block_until_ready(pool.cache)
    assert compiles.times == []
