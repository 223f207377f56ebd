"""Decode-megastep parity and donation-safety tests.

``Model.decode_multi(k)`` must be bit-for-bit (tokens, cache lengths) and
fp-tolerance (probe posteriors, KV/SSM state) identical to ``k`` sequential
``decode_step`` calls on both cache layouts, including inactive rows,
per-row budget/EOS halting, and SSM ``_mask_recurrent`` state. Donation
(engine jits donate the cache pytree) must never resurrect stale buffers
across preemption resets.
"""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.config import get_smoke_config
from repro.models import transformer as tfm
from repro.models.model import Model
from repro.serving.engine import run_policy
from repro.serving.kv_cache import PagedSlotPool, SlotPool, donating_jit
from repro.serving.predictors import ProbePredictor
from repro.serving.workload import WorkloadConfig, generate


def _build(arch):
    cfg = get_smoke_config(arch)
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    return cfg, m, params


def _sequential(m, params, cache, tok, active, budget, k):
    """k decode_step calls with the same halting semantics as decode_multi."""
    dec = jax.jit(m.decode_step)
    emitted = jnp.zeros_like(budget)
    toks, probes = [], []
    for _ in range(k):
        act = active & (emitted < budget)
        logits, cache, _, pl = dec(params, cache, tok, active=act)
        nxt = jnp.argmax(logits, -1).astype(jnp.int32)
        toks.append(np.where(np.asarray(act), np.asarray(nxt), -1))
        probes.append(np.asarray(jax.nn.softmax(pl.astype(jnp.float32), -1)))
        tok = jnp.where(act, nxt, tok[:, 0])[:, None]
        emitted = emitted + act.astype(jnp.int32)
    return cache, np.stack(toks, 1), np.stack(probes, 1), np.asarray(emitted)


def _assert_cache_close(got, ref, tol=1e-5):
    assert bool(jnp.all(got["lengths"] == ref["lengths"]))
    for key in ref:
        if not key.startswith("run_"):
            continue
        for s_got, s_ref in zip(got[key], ref[key]):
            for leaf in s_ref:
                err = float(jnp.max(jnp.abs(
                    jnp.asarray(s_got[leaf], jnp.float32)
                    - jnp.asarray(s_ref[leaf], jnp.float32))))
                assert err < tol, (key, leaf, err)


@pytest.mark.real
@pytest.mark.parametrize("arch", ["trail-llama", "mamba2-370m"])
def test_decode_multi_matches_sequential_contig(arch):
    """Contig layout, incl. an inactive row, a short budget, and (for
    mamba2) the SSM ``_mask_recurrent`` state of halted rows."""
    cfg, m, params = _build(arch)
    B, k = 3, 5
    cache = m.init_cache(B, 32)
    prompts = jax.random.randint(jax.random.key(1), (B, 8), 4, cfg.vocab_size)
    logits, cache, *_ = jax.jit(m.prefill_chunk)(params, cache, prompts)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    active = jnp.array([True, True, False])
    budget = jnp.array([k, 3, k], jnp.int32)

    c_ref, t_ref, p_ref, n_ref = _sequential(m, params, cache, tok,
                                             active, budget, k)
    toks, c_got, probs, n_got = jax.jit(
        m.decode_multi, static_argnames=("k", "eos_id"))(
            params, cache, tok, active, budget, k=k)

    assert np.array_equal(np.asarray(toks), t_ref)
    assert np.array_equal(np.asarray(n_got), n_ref)
    assert np.asarray(n_got).tolist() == [5, 3, 0]
    # rows halted by budget / inactivity emit -1 sentinels past their halt
    assert np.all(np.asarray(toks)[1, 3:] == -1)
    assert np.all(np.asarray(toks)[2] == -1)
    assert float(np.max(np.abs(np.asarray(probs) - p_ref))) < 1e-5
    _assert_cache_close(c_got, c_ref)


@pytest.mark.real
def test_decode_multi_matches_sequential_paged():
    """Paged layout: same block table for both paths, so the page pool
    (pk/pv/pkpos) must come out identical too."""
    cfg, m, params = _build("trail-llama")
    B, k, ps = 2, 4, 8
    cache = m.init_cache(B, 32, kv_layout="paged", page_size=ps)
    # rows 0/1 get disjoint scrambled pages covering 8 prompt + k new tokens
    table = np.zeros((B, 4), np.int32)
    table[0, :2] = [3, 5]
    table[1, :2] = [1, 7]
    cache["block_table"] = jnp.asarray(table)
    prompts = jax.random.randint(jax.random.key(2), (B, 8), 4, cfg.vocab_size)
    logits, cache, *_ = jax.jit(m.prefill_chunk)(params, cache, prompts)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    active = jnp.ones((B,), bool)
    budget = jnp.full((B,), k, jnp.int32)

    c_ref, t_ref, p_ref, n_ref = _sequential(m, params, cache, tok,
                                             active, budget, k)
    toks, c_got, probs, n_got = jax.jit(
        m.decode_multi, static_argnames=("k", "eos_id"))(
            params, cache, tok, active, budget, k=k)

    assert np.array_equal(np.asarray(toks), t_ref)
    assert np.array_equal(np.asarray(n_got), n_ref)
    assert float(np.max(np.abs(np.asarray(probs) - p_ref))) < 1e-5
    _assert_cache_close(c_got, c_ref)


@pytest.mark.real
def test_decode_multi_eos_halting():
    """A row that emits ``eos_id`` halts there: no further KV writes or
    length growth, later outputs are -1."""
    cfg, m, params = _build("trail-llama")
    B, k = 2, 5
    cache = m.init_cache(B, 32)
    prompts = jax.random.randint(jax.random.key(3), (B, 8), 4, cfg.vocab_size)
    logits, cache, *_ = jax.jit(m.prefill_chunk)(params, cache, prompts)
    tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
    dm = jax.jit(m.decode_multi, static_argnames=("k", "eos_id"))
    free_toks, _, _, _ = dm(params, cache, tok, k=k)
    eos = int(np.asarray(free_toks)[0, 2])        # row 0's 3rd token as EOS
    toks, c_got, _, n_got = dm(params, cache, tok, k=k, eos_id=eos)
    toks = np.asarray(toks)
    n = np.asarray(n_got)
    assert n[0] == 3                              # EOS is emitted, then halt
    assert toks[0, 2] == eos and np.all(toks[0, 3:] == -1)
    lengths = np.asarray(c_got["lengths"])
    assert lengths[0] == 8 + 3
    assert lengths[1] == 8 + int(n[1])


@pytest.mark.real
@pytest.mark.parametrize("paged", [False, True])
def test_donation_no_stale_buffer_after_preemption_reset(paged):
    """Engine-style donating jits + the reset queue: after a preempted
    request's slot is released, reset, and reassigned, the new occupant's
    generation must match a run on a fresh pool (no stale-KV reuse through
    the donated/aliased buffers)."""
    cfg, m, params = _build("trail-llama")
    prefill = donating_jit(m.prefill_chunk)
    decode = donating_jit(m.decode_multi, static_argnames=("k", "eos_id"))
    prompts = jax.random.randint(jax.random.key(4), (2, 8), 4, cfg.vocab_size)

    def make_pool():
        if paged:
            return PagedSlotPool(m, slots=2, max_len=32, page_size=8,
                                 retain=False)
        return SlotPool(m, slots=2, max_len=32)

    def run_request(pool, slot_tokens, slot):
        if paged:
            pool.ensure_pages(pool_rid[slot], 8 + 4)
        pool.flush_resets()
        toks = np.zeros((2, 8), np.int32)
        valid = np.zeros((2, 8), bool)
        toks[slot] = slot_tokens
        valid[slot] = True
        logits, pool.cache, *_ = prefill(params, pool.cache,
                                         jnp.asarray(toks),
                                         valid=jnp.asarray(valid))
        tok = np.zeros((2, 1), np.int32)
        active = np.zeros((2,), bool)
        tok[slot, 0] = int(jnp.argmax(logits[slot]))
        active[slot] = True
        out, pool.cache, _, _ = decode(params, pool.cache, jnp.asarray(tok),
                                       jnp.asarray(active), k=4)
        return np.asarray(out)[slot]

    pool_rid = {}
    # fresh pool: rid 9 alone
    pool = make_pool()
    pool_rid[pool.assign(9)] = 9
    ref = run_request(pool, np.asarray(prompts)[1], pool.slot_of[9])

    # dirty pool: rid 7 runs first, is preempted (discard), slot reused by 9
    pool = make_pool()
    pool_rid = {}
    s7 = pool.assign(7)
    pool_rid[s7] = 7
    _ = run_request(pool, np.asarray(prompts)[0], s7)
    pool.release(7)                     # queues the device reset
    s9 = pool.assign(9)
    pool_rid[s9] = 9
    assert s9 == s7                     # same physical slot
    got = run_request(pool, np.asarray(prompts)[1], s9)
    assert np.array_equal(ref, got)


@pytest.mark.real
@pytest.mark.parametrize("kv_layout", ["contig", "paged"])
def test_engine_real_megastep_end_to_end(kv_layout):
    """probe_interval=4 megasteps: every request still finishes, and the
    engine consults the scheduler ~4x less often than per-token."""
    cfg, m, params = _build("trail-llama")
    wc = WorkloadConfig(n_requests=6, request_rate=100.0, seed=1,
                        vocab=cfg.vocab_size, prompt_mean=8.0,
                        out_median=6.0, max_out=16)
    pred = ProbePredictor(cfg.probe, probe_params=params["probe"],
                          embed_table=params["embed"])
    per_tok = run_policy(cfg, "trail", generate(wc), max_batch=3,
                         mode="real", model=m, params=params, predictor=pred,
                         probe_interval=1, kv_layout=kv_layout,
                         page_size=8, max_len=64)
    mega = run_policy(cfg, "trail", generate(wc), max_batch=3,
                      mode="real", model=m, params=params, predictor=pred,
                      probe_interval=4, kv_layout=kv_layout,
                      page_size=8, max_len=64)
    assert len(per_tok.latencies) == 6
    assert len(mega.latencies) == 6
    assert mega.iterations < per_tok.iterations


@pytest.mark.real
def test_model_paged_kernels_parity():
    """use_kernels=True routes the paged path through the Pallas single-
    and multi-query flash-decode kernels (interpret mode on CPU); prefill
    + a decode megastep must match the gather+attend reference path."""
    cfg = get_smoke_config("trail-llama")
    m_ref = Model(cfg, use_kernels=False)
    m_ker = Model(cfg, use_kernels=True)
    params = m_ref.init(jax.random.key(0))
    B, ps, k = 2, 8, 2
    table = np.zeros((B, 4), np.int32)
    table[0, :2] = [2, 4]
    table[1, :2] = [6, 1]
    prompts = jax.random.randint(jax.random.key(5), (B, 8), 4, cfg.vocab_size)

    outs = []
    for m in (m_ref, m_ker):
        cache = m.init_cache(B, 32, kv_layout="paged", page_size=ps)
        cache["block_table"] = jnp.asarray(table)
        logits, cache, *_ = m.prefill_chunk(params, cache, prompts)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        toks, cache, probs, _ = m.decode_multi(params, cache, tok, k=k)
        outs.append((np.asarray(logits), np.asarray(toks), np.asarray(probs)))
    assert float(np.max(np.abs(outs[0][0] - outs[1][0]))) < 2e-4
    assert np.array_equal(outs[0][1], outs[1][1])
    assert float(np.max(np.abs(outs[0][2] - outs[1][2]))) < 2e-4


class _LayerByLayer(Model):
    """The cached trunk with each layer on its own slice of the run's
    cache: the layer scan takes the stacked cache as ``xs``, hands each
    layer a one-layer slice, and stacks the slices back as ``ys``.

    A scan, not an unrolled loop: the hidden state is rounded to the
    compute dtype between layers exactly as in the model's scan, so the
    comparison can be bit for bit."""

    def _cached_trunk(self, params, cache, h, q_pos, decode):
        new_cache = dict(cache)
        tap = None
        for ri, (kinds, nb) in enumerate(self.runs):
            def body(h, xs, _kinds=kinds):
                p_blk, c_blk = xs
                new_blk = []
                for j, kind in enumerate(_kinds):
                    one = jax.tree.map(lambda x: x[None], c_blk[j])
                    h, one, _ = tfm.block_cached(
                        self.cfg, kind, p_blk[j], h, one, 0, q_pos,
                        decode=decode, block_table=cache.get("block_table"))
                    new_blk.append(jax.tree.map(lambda x: x[0], one))
                return h, tuple(new_blk)
            h, new_cache[f"run_{ri}"] = jax.lax.scan(
                body, h, (params["layers"][ri], cache[f"run_{ri}"]))
            if ri == self.tap_run:
                tap = h
        return h, new_cache, tap, jnp.float32(0)


def _six_layers(arch: str, **changes):
    """The arch's smoke layer pattern repeated to six layers, probe tapped
    at layer 3: runs of several layers (alternating kinds scan as
    multi-layer blocks), so the scan hands out layer indices past 0."""
    cfg = get_smoke_config(arch)
    kinds = (cfg.layer_kinds * 6)[:6]
    return dataclasses.replace(
        cfg, num_layers=6, layer_kinds=kinds,
        probe=dataclasses.replace(cfg.probe, tap_layer=3), **changes)


_KINDS = {                                  # each layer kind's smoke config
    "dense": ("trail-llama", {}),
    "local": ("gemma2-9b", {}),             # ring buffer + global layers
    "moe": ("olmoe-1b-7b", {}),
    "ssm": ("mamba2-370m", {}),
    "hybrid": ("hymba-1.5b", {}),           # windowed attention + SSM
    "cross": ("whisper-tiny", {}),          # cross-attention cache
    "int8": ("trail-llama", {"kv_quant": True}),   # K/V with scales
}
_CASES = [(kind, layout) for kind in _KINDS for layout in ("contig", "paged")
          if not (kind == "int8" and layout == "paged")]   # no paged int8


@pytest.mark.real
@pytest.mark.parametrize("kind,kv_layout", _CASES,
                         ids=[f"{k}-{lay}" for k, lay in _CASES])
def test_scan_carried_cache_matches_layer_by_layer(kind, kv_layout):
    """prefill_chunk then decode_multi with each run's cache carried
    through the layer scan give the same tokens, probe posteriors and
    cache leaves, bit for bit, as the layers run one at a time on
    per-layer slices."""
    arch, changes = _KINDS[kind]
    cfg = _six_layers(arch, **changes)
    B, max_len, ps, k = 3, 32, 8, 3
    m = Model(cfg)
    params = m.init(jax.random.key(0))
    prompts = jax.random.randint(jax.random.key(1), (B, 12), 4,
                                 cfg.vocab_size)
    valid = jnp.arange(12)[None, :] < jnp.array([[12], [7], [0]])
    extra = {}
    if cfg.cross_attention:
        extra["enc_embeds"] = jax.random.normal(
            jax.random.key(2), (B, cfg.encoder_seq, cfg.d_model))
    active = jnp.array([True, True, False])
    budget = jnp.array([k, 2, k], jnp.int32)

    outs = []
    for model in (m, _LayerByLayer(cfg)):
        cache = model.init_cache(B, max_len, kv_layout=kv_layout,
                                 page_size=ps)
        if kv_layout == "paged":      # scrambled, disjoint pages per row
            cache["block_table"] = jnp.asarray(
                [[3, 9, 0, 0], [7, 1, 0, 0], [0, 0, 0, 0]], jnp.int32)
        logits, cache, tap_sum, _ = jax.jit(model.prefill_chunk)(
            params, cache, prompts, valid, **extra)
        tok = jnp.argmax(logits, -1)[:, None].astype(jnp.int32)
        toks, cache, probs, _ = jax.jit(
            model.decode_multi, static_argnames=("k",))(
                params, cache, tok, active, budget, k=k)
        outs.append(jax.tree.map(np.asarray,
                                 (logits, tap_sum, toks, probs, cache)))
    got, ref = outs
    assert (got[2] >= 0).any()                      # some row decoded
    assert jax.tree.structure(got) == jax.tree.structure(ref)
    for (path, a), b in zip(jax.tree_util.tree_leaves_with_path(got),
                            jax.tree.leaves(ref)):
        assert a.dtype == b.dtype and np.array_equal(a, b), \
            jax.tree_util.keystr(path)
