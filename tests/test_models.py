"""Model-layer unit/property tests: attention paths, SSD, MoE, RoPE, CE."""

import dataclasses

import jax
import jax.numpy as jnp
import numpy as np
import pytest
from _hypothesis_fallback import given, settings, st

from repro.config import get_smoke_config
from repro.models import attention as A
from repro.models import moe as moe_mod
from repro.models.layers import apply_rope, softcap
from repro.models.model import Model, _chunked_ce
from repro.models.ssm import ssd_chunked
from repro.kernels.ref import ssd_scan_ref

CFG = get_smoke_config("granite-3-8b")
KEY = jax.random.key(0)


@given(st.integers(1, 3), st.integers(8, 64), st.integers(0, 40))
@settings(max_examples=25, deadline=None)
def test_blocked_equals_dense_attention(B, S, win):
    H, KH, hd = 4, 2, 16
    M = S + 16
    ks = jax.random.split(jax.random.fold_in(KEY, S * 7 + win), 3)
    q = jax.random.normal(ks[0], (B, S, H, hd))
    k = jax.random.normal(ks[1], (B, M, KH, hd))
    v = jax.random.normal(ks[2], (B, M, KH, hd))
    qp = jnp.broadcast_to(jnp.arange(S), (B, S))
    kp = jnp.broadcast_to(jnp.arange(M), (B, M))
    kp = jnp.where(kp < M - 5, kp, -1)            # some invalid slots
    d = A._attend_dense(CFG, q, k, v, qp, kp, window=win, causal=True)
    b = A._attend_blocked(CFG, q, k, v, qp, kp, window=win, causal=True,
                          block=16)
    assert float(jnp.max(jnp.abs(d - b))) < 1e-5


def test_rope_relative_property():
    """RoPE: <rot(q,n), rot(k,m)> depends only on n - m."""
    hd = 32
    q = jax.random.normal(KEY, (1, 1, 1, hd))
    k = jax.random.normal(jax.random.fold_in(KEY, 1), (1, 1, 1, hd))
    def dot(n, m):
        qr = apply_rope(q, jnp.asarray([[n]]), 10000.0)
        kr = apply_rope(k, jnp.asarray([[m]]), 10000.0)
        return float(jnp.sum(qr * kr))
    assert dot(3, 1) == pytest.approx(dot(10, 8), rel=1e-4)
    assert dot(5, 5) == pytest.approx(dot(0, 0), rel=1e-4)


def test_softcap():
    x = jnp.asarray([-1e5, 0.0, 1e5])
    y = softcap(x, 30.0)
    assert float(y[0]) == pytest.approx(-30.0, rel=1e-3)
    assert float(y[1]) == 0.0
    assert float(y[2]) == pytest.approx(30.0, rel=1e-3)
    assert softcap(x, 0.0) is x


def test_cache_write_drop_semantics():
    cache = A.init_kv_cache(CFG, batch=2, max_len=8, n_layers=2)
    k_new = jnp.ones((2, 3, CFG.num_kv_heads, CFG.head_dim))
    pos = jnp.asarray([[0, 1, 2], [-1, 5, 99]])   # -1 and overflow dropped
    out = A.cache_write(cache, 1, k_new, k_new, pos, window=0)
    layer = jax.tree.map(lambda x: x[1], out)
    assert bool(jnp.all(layer["kpos"][0, :3] == jnp.asarray([0, 1, 2])))
    assert int(layer["kpos"][1, 5]) == 5
    assert int(layer["kpos"][1, 0]) == -1         # -1 write dropped
    assert bool(jnp.all(layer["k"][1, 0] == 0))
    # the other layer of the stack is untouched
    assert bool(jnp.all(out["kpos"][0] == -1))
    assert bool(jnp.all(out["k"][0] == 0))


def test_ring_buffer_wraparound():
    win = 4
    cache = A.init_kv_cache(CFG, batch=1, max_len=16, n_layers=1, window=win)
    k_new = jnp.arange(6, dtype=jnp.float32)[None, :, None, None] * jnp.ones(
        (1, 6, CFG.num_kv_heads, CFG.head_dim))
    pos = jnp.arange(6)[None]
    out = A.cache_write(cache, 0, k_new, k_new, pos, window=win)
    # slots hold positions 4,5,2,3 (ring of width 4)
    assert sorted(np.asarray(out["kpos"][0, 0]).tolist()) == [2, 3, 4, 5]


def test_ssd_chunk_invariance():
    """Chunk size must not change the SSD result."""
    B, L, nh, hp, N = 1, 96, 2, 16, 8
    ks = jax.random.split(KEY, 5)
    x = jax.random.normal(ks[0], (B, L, nh, hp))
    dt = jax.nn.softplus(jax.random.normal(ks[1], (B, L, nh)))
    Aa = -jnp.exp(jax.random.normal(ks[2], (nh,)) * 0.3)
    Bm = jax.random.normal(ks[3], (B, L, N))
    Cm = jax.random.normal(ks[4], (B, L, N))
    y_ref, s_ref = ssd_scan_ref(x, dt, Aa, Bm, Cm)
    for chunk in (8, 16, 32, 96):
        y, s = ssd_chunked(x, dt, Aa, Bm, Cm, chunk)
        assert float(jnp.max(jnp.abs(y - y_ref))) < 1e-3, chunk
        assert float(jnp.max(jnp.abs(s - s_ref))) < 1e-3, chunk


def test_moe_sort_matches_dense_dropless():
    cfg = dataclasses.replace(get_smoke_config("olmoe-1b-7b"),
                              capacity_factor=2.0)
    p = moe_mod.init_moe(KEY, cfg)
    h = jax.random.normal(jax.random.fold_in(KEY, 2),
                          (2, 16, cfg.d_model)).astype(jnp.bfloat16)
    y1, a1 = moe_mod.moe_mlp(cfg, p, h)
    y2, a2 = moe_mod.moe_mlp_dense(cfg, p, h)
    err = float(jnp.max(jnp.abs(y1.astype(jnp.float32)
                                - y2.astype(jnp.float32))))
    assert err < 3e-2
    assert float(a1) == pytest.approx(float(a2), rel=1e-5)
    assert float(a1) >= 1.0 - 1e-3     # Switch aux lower bound is 1 at balance


def test_moe_capacity_drops_are_identity():
    """Tokens dropped by capacity contribute zero delta (residual intact)."""
    cfg = dataclasses.replace(get_smoke_config("olmoe-1b-7b"),
                              capacity_factor=0.01)   # drop almost everything
    p = moe_mod.init_moe(KEY, cfg)
    h = jax.random.normal(jax.random.fold_in(KEY, 3), (1, 8, cfg.d_model))
    y, _ = moe_mod.moe_mlp(cfg, p, h)
    # capacity floor is 4 slots/expert; most tokens dropped -> tiny norm
    assert float(jnp.mean(jnp.abs(y))) < float(jnp.mean(jnp.abs(h)))


def test_int8_kv_cache_close_to_fp():
    """kv_quant=True: decode logits within quantization tolerance of fp."""
    cfg = get_smoke_config("granite-3-8b")
    qcfg = dataclasses.replace(cfg, kv_quant=True)
    m_fp = Model(cfg)
    m_q = Model(qcfg)
    params = m_fp.init(KEY)
    B, S = 2, 24
    tokens = jax.random.randint(jax.random.fold_in(KEY, 9), (B, S), 0,
                                cfg.vocab_size)
    nt = jax.random.randint(jax.random.fold_in(KEY, 10), (B, 1), 0,
                            cfg.vocab_size)
    outs = {}
    for name, m in (("fp", m_fp), ("q", m_q)):
        cache = m.init_cache(B, 64)
        _, cache, *_ = m.prefill_chunk(params, cache, tokens)
        ld, cache, *_ = m.decode_step(params, cache, nt)
        outs[name] = ld
        if name == "q":
            run0 = cache["run_0"][0]
            assert run0["k"].dtype == jnp.int8
            assert "k_scale" in run0
    err = float(jnp.max(jnp.abs(outs["fp"] - outs["q"])))
    scale = float(jnp.max(jnp.abs(outs["fp"])))
    assert err < 0.05 * scale + 0.3, (err, scale)   # int8: small perturbation
    # top-1 prediction must agree
    assert bool(jnp.all(jnp.argmax(outs["fp"], -1)
                        == jnp.argmax(outs["q"], -1)))
    # and the accounting reflects the ~2x saving
    from repro.serving.kv_cache import bytes_for_context
    assert bytes_for_context(qcfg, 1024) < 0.6 * bytes_for_context(cfg, 1024)


def test_chunked_ce_matches_dense():
    cfg = get_smoke_config("trail-llama")
    m = Model(cfg)
    params = m.init(KEY)
    B, S = 2, 32
    h = jax.random.normal(jax.random.fold_in(KEY, 4), (B, S, cfg.d_model))
    labels = jax.random.randint(jax.random.fold_in(KEY, 5), (B, S), -1,
                                cfg.vocab_size)
    loss8, n8 = _chunked_ce(cfg, params, h, labels, chunk=8)
    loss32, n32 = _chunked_ce(cfg, params, h, labels, chunk=32)
    assert float(jnp.abs(loss8 - loss32)) < 1e-4
    assert float(n8) == float(n32)
