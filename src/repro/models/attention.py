"""Attention: GQA / MQA / MHA with unified position-based masking.

One code path serves training (no cache), chunked prefill, and single-token
decode.  All masks are derived from absolute positions:

  * query positions  ``q_pos``  (B, S)   — absolute index of each query token
  * key positions    ``k_pos``  (B, M)   — absolute index of each cache slot
                                           (-1 marks an empty slot)

Causality is ``k_pos <= q_pos``; sliding windows add ``k_pos > q_pos - W``.
Ring-buffer caches (sliding-window layers) therefore need no special-case
masking: the stored ``k_pos`` of an overwritten slot simply moves forward.

The reference path is pure jnp; ``repro.kernels`` provides Pallas TPU
kernels with identical semantics (``use_kernels`` flag on the model).
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp

# §Perf A/B switch: REPRO_DECODE_CONCAT=1 restores the pre-hillclimb
# concat-based decode attention for baseline measurements.
_DECODE_CONCAT = os.environ.get("REPRO_DECODE_CONCAT", "") == "1"

from repro.config import ModelConfig
from repro.models.hints import BATCH, hint
from repro.models.layers import apply_rope, cdtype, dense_init, pdtype, softcap

NEG_INF = -2.0 ** 30   # large-negative instead of -inf: keeps softmax NaN-free
                       # for all-masked rows (empty cache slots at step 0)


# ---------------------------------------------------------------------------
# Params
# ---------------------------------------------------------------------------

def init_attention(key, cfg: ModelConfig, cross: bool = False):
    """Initialize q/k/v/o projection params (plus optional qkv bias)."""
    d, qd, kvd = cfg.d_model, cfg.q_dim, cfg.kv_dim
    dt = pdtype(cfg)
    ks = jax.random.split(key, 4)
    p = {"wq": dense_init(ks[0], d, qd, dt),
         "wk": dense_init(ks[1], d, kvd, dt),
         "wv": dense_init(ks[2], d, kvd, dt),
         "wo": dense_init(ks[3], qd, d, dt, scale=qd ** -0.5)}
    if cfg.qkv_bias:
        p["bq"] = jnp.zeros((qd,), dt)
        p["bk"] = jnp.zeros((kvd,), dt)
        p["bv"] = jnp.zeros((kvd,), dt)
    return p


def project_qkv(cfg: ModelConfig, p, hq, hkv=None):
    """hq: (B,S,d) queries source; hkv: (B,M,d) keys/values source."""
    hkv = hq if hkv is None else hkv
    q = hq @ p["wq"].astype(hq.dtype)
    k = hkv @ p["wk"].astype(hq.dtype)
    v = hkv @ p["wv"].astype(hq.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    B = hq.shape[0]
    q = q.reshape(B, hq.shape[1], cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, hkv.shape[1], cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, hkv.shape[1], cfg.num_kv_heads, cfg.head_dim)
    return q, k, v


# ---------------------------------------------------------------------------
# Core attention with position masks (pure-jnp reference)
# ---------------------------------------------------------------------------

ATTEND_BLOCK_K = 1024          # KV block for the online-softmax path
ATTEND_DENSE_LIMIT = 1 << 24   # use dense scores below S*M of ~16M elements


def _mask(q_pos, k_pos, window: int, causal: bool):
    valid = (k_pos >= 0)[:, None, None, None, :]
    if causal:
        valid &= k_pos[:, None, None, None, :] <= q_pos[:, None, None, :, None]
    if window:
        valid &= (k_pos[:, None, None, None, :]
                  > (q_pos[:, None, None, :, None] - window))
    return valid


def attend(cfg: ModelConfig, q, k, v, q_pos, k_pos, *, window: int = 0,
           causal: bool = True, k_scale=None, v_scale=None):
    """q: (B,S,H,hd); k/v: (B,M,KH,hd); q_pos: (B,S); k_pos: (B,M).

    Returns (B,S,H,hd). float32 softmax; GQA via head grouping. Large S*M
    takes the blocked online-softmax path (flash-attention schedule in pure
    jnp — O(S*block) memory instead of O(S*M); same semantics as the Pallas
    kernels). k_scale/v_scale: per-(token, kv-head) int8 dequant scales
    (lazy per-block dequant keeps the int8 memory win).
    """
    S, M = q.shape[1], k.shape[1]
    if S * M > ATTEND_DENSE_LIMIT and M > ATTEND_BLOCK_K:
        return _attend_blocked(cfg, q, k, v, q_pos, k_pos, window=window,
                               causal=causal, k_scale=k_scale,
                               v_scale=v_scale)
    return _attend_dense(cfg, q, k, v, q_pos, k_pos, window=window,
                         causal=causal, k_scale=k_scale, v_scale=v_scale)


def _deq(x, scale):
    x = x.astype(jnp.float32)
    if scale is not None:
        x = x * scale[..., None].astype(jnp.float32)
    return x


def _attend_dense(cfg: ModelConfig, q, k, v, q_pos, k_pos, *, window: int,
                  causal: bool, k_scale=None, v_scale=None):
    B, S, H, hd = q.shape
    M, KH = k.shape[1], k.shape[2]
    G = H // KH
    qg = q.reshape(B, S, KH, G, hd)
    if S > 1:   # shard the query-seq dim of the big score tensors (hints.py)
        qg = hint(qg, BATCH, "model")
    scores = jnp.einsum("bskgh,bmkh->bkgsm", qg.astype(jnp.float32),
                        _deq(k, k_scale)) * (hd ** -0.5)
    if S > 1:
        scores = hint(scores, BATCH, None, None, "model")
    scores = softcap(scores, cfg.attn_logit_softcap)
    valid = _mask(q_pos, k_pos, window, causal)
    scores = jnp.where(valid, scores, NEG_INF)
    m = jnp.max(scores, axis=-1, keepdims=True)
    p = jnp.where(valid, jnp.exp(scores - m), 0.0)
    l = jnp.maximum(jnp.sum(p, axis=-1, keepdims=True), 1e-30)
    out = jnp.einsum("bkgsm,bmkh->bskgh", p / l, _deq(v, v_scale))
    return out.reshape(B, S, H, hd).astype(q.dtype)


def _attend_blocked(cfg: ModelConfig, q, k, v, q_pos, k_pos, *, window: int,
                    causal: bool, block: int = ATTEND_BLOCK_K,
                    k_scale=None, v_scale=None):
    B, S, H, hd = q.shape
    M, KH = k.shape[1], k.shape[2]
    G = H // KH
    pad = (-M) % block
    if pad:
        k = jnp.pad(k, ((0, 0), (0, pad), (0, 0), (0, 0)))
        v = jnp.pad(v, ((0, 0), (0, pad), (0, 0), (0, 0)))
        k_pos = jnp.pad(k_pos, ((0, 0), (0, pad)), constant_values=-1)
        if k_scale is not None:
            k_scale = jnp.pad(k_scale, ((0, 0), (0, pad), (0, 0)))
            v_scale = jnp.pad(v_scale, ((0, 0), (0, pad), (0, 0)))
    nb = (M + pad) // block
    qg = q.reshape(B, S, KH, G, hd).astype(jnp.float32)
    qg = hint(qg, BATCH, "model")        # shard query-seq dim (hints.py)
    kb = jnp.moveaxis(k.reshape(B, nb, block, KH, hd), 1, 0)
    vb = jnp.moveaxis(v.reshape(B, nb, block, KH, hd), 1, 0)
    pb = jnp.moveaxis(k_pos.reshape(B, nb, block), 1, 0)
    if k_scale is not None:
        ksb = jnp.moveaxis(k_scale.reshape(B, nb, block, KH), 1, 0)
        vsb = jnp.moveaxis(v_scale.reshape(B, nb, block, KH), 1, 0)
    else:
        ksb = vsb = jnp.zeros((nb, B, 0, KH), jnp.float32)

    def body(carry, inp):
        """Online-softmax update over one KV block."""
        m_run, l_run, acc = carry
        kc, vc, pc, ksc, vsc = inp
        kc = _deq(kc, ksc if k_scale is not None else None)
        vc = _deq(vc, vsc if v_scale is not None else None)
        s = jnp.einsum("bskgh,bmkh->bkgsm", qg, kc)
        s = hint(s, BATCH, None, None, "model")
        s = s * (hd ** -0.5)
        s = softcap(s, cfg.attn_logit_softcap)
        valid = _mask(q_pos, pc, window, causal)
        s = jnp.where(valid, s, NEG_INF)
        m_new = jnp.maximum(m_run, jnp.max(s, axis=-1))
        p = jnp.where(valid, jnp.exp(s - m_new[..., None]), 0.0)
        alpha = jnp.exp(m_run - m_new)
        l_new = l_run * alpha + jnp.sum(p, axis=-1)
        acc = (acc * alpha[..., None]
               + jnp.einsum("bkgsm,bmkh->bkgsh", p, vc.astype(jnp.float32)))
        return (m_new, l_new, acc), None

    m0 = hint(jnp.full((B, KH, G, S), NEG_INF, jnp.float32),
              BATCH, None, None, "model")
    l0 = hint(jnp.zeros((B, KH, G, S), jnp.float32),
              BATCH, None, None, "model")
    a0 = hint(jnp.zeros((B, KH, G, S, hd), jnp.float32),
              BATCH, None, None, "model")
    (m_f, l_f, acc), _ = jax.lax.scan(body, (m0, l0, a0),
                                      (kb, vb, pb, ksb, vsb))
    out = acc / jnp.maximum(l_f, 1e-30)[..., None]
    out = jnp.moveaxis(out, 3, 1).reshape(B, S, H, hd)
    return out.astype(q.dtype)


# ---------------------------------------------------------------------------
# Self-attention over a slot cache (prefill chunk / decode)
# ---------------------------------------------------------------------------

def init_kv_cache(cfg: ModelConfig, batch: int, max_len: int, n_layers: int,
                  window: int = 0):
    """A stacked cache for a run of ``n_layers`` identical layers.

    With ``cfg.kv_quant`` the K/V payload is int8 with per-(token, kv-head)
    float32 scales — 2x HBM vs bf16 (the §Perf fix for MHA decode shapes
    whose bf16 cache exceeds HBM, e.g. qwen1.5-32b decode_32k).
    """
    slots = min(window, max_len) if window else max_len
    shape = (n_layers, batch, slots, cfg.num_kv_heads, cfg.head_dim)
    cache = {"kpos": jnp.full((n_layers, batch, slots), -1, jnp.int32)}
    if cfg.kv_quant:
        cache["k"] = jnp.zeros(shape, jnp.int8)
        cache["v"] = jnp.zeros(shape, jnp.int8)
        cache["k_scale"] = jnp.zeros(shape[:4], jnp.float32)
        cache["v_scale"] = jnp.zeros(shape[:4], jnp.float32)
    else:
        dt = cdtype(cfg)
        cache["k"] = jnp.zeros(shape, dt)
        cache["v"] = jnp.zeros(shape, dt)
    return cache


def _quantize(x):
    """x: (B,S,KH,hd) -> (int8 values, per-(token,head) f32 scales)."""
    scale = jnp.max(jnp.abs(x.astype(jnp.float32)), axis=-1) / 127.0
    scale = jnp.maximum(scale, 1e-12)
    q = jnp.clip(jnp.round(x.astype(jnp.float32) / scale[..., None]),
                 -127, 127).astype(jnp.int8)
    return q, scale


def dequantize(q, scale):
    """Invert `quantize`: int8 values x per-(token,head) scales -> f32."""
    return q.astype(jnp.float32) * scale[..., None].astype(jnp.float32)


def cache_write(cache, layer, k_new, v_new, positions, window: int):
    """Write S new entries per sequence into one layer of a stacked cache.

    cache: {"k": (L,B,slots,KH,hd), ...}; layer: scalar index into L;
    k_new: (B,S,KH,hd); positions: (B,S) absolute positions. Returns the
    updated stacked cache: only the written entries change, so a cache
    carried through the layer scan is updated in place.
    """
    slots = cache["k"].shape[2]
    B = k_new.shape[0]
    slot_idx = positions % slots if window else positions
    # invalid (-1) or overflowing positions -> index `slots` (out of bounds),
    # dropped by scatter mode="drop": no special-case masking anywhere else.
    ok = positions >= 0
    if not window:
        ok &= positions < slots
    slot_idx = jnp.where(ok, slot_idx, slots)
    b_idx = jnp.arange(B)[:, None]
    out = dict(cache)
    if "k_scale" in cache:
        k_q, k_s = _quantize(k_new)
        v_q, v_s = _quantize(v_new)
        out["k"] = cache["k"].at[layer, b_idx, slot_idx].set(k_q, mode="drop")
        out["v"] = cache["v"].at[layer, b_idx, slot_idx].set(v_q, mode="drop")
        out["k_scale"] = cache["k_scale"].at[layer, b_idx, slot_idx].set(
            k_s, mode="drop")
        out["v_scale"] = cache["v_scale"].at[layer, b_idx, slot_idx].set(
            v_s, mode="drop")
    else:
        out["k"] = cache["k"].at[layer, b_idx, slot_idx].set(
            k_new.astype(cache["k"].dtype), mode="drop")
        out["v"] = cache["v"].at[layer, b_idx, slot_idx].set(
            v_new.astype(cache["v"].dtype), mode="drop")
    out["kpos"] = cache["kpos"].at[layer, b_idx, slot_idx].set(
        positions, mode="drop")
    return out


def self_attention_cached(cfg: ModelConfig, p, h, cache, layer, q_pos, *,
                          window: int = 0):
    """One layer of cached self-attention on a token chunk.

    h: (B,S,d); cache holds the run's stacked slots (L,B,slots,...) and
    ``layer`` picks this layer's; q_pos: (B,S) absolute positions of the
    chunk tokens. Returns (out (B,S,d), new stacked cache).
    """
    q, k, v = project_qkv(cfg, p, h)
    if cfg.use_rope:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)
    B, S = h.shape[:2]
    if S == 1 and not _DECODE_CONCAT:
        # Decode fast path (EXPERIMENTS.md §Perf): write the single token
        # FIRST, then attend over the updated cache in place. The concat
        # path below copies the whole cache every step (qwen decode_32k:
        # +50 GB/dev of transients). Safe at S=1: a ring slot overwritten by
        # the new token held a position <= q_pos - window, already masked.
        new_cache = cache_write(cache, layer, k, v, q_pos, window)
        lay = {key: x[layer] for key, x in new_cache.items()}
        out = attend(cfg, q, lay["k"], lay["v"], q_pos, lay["kpos"],
                     window=window, k_scale=lay.get("k_scale"),
                     v_scale=lay.get("v_scale"))
        return out.reshape(B, S, cfg.q_dim) @ p["wo"].astype(h.dtype), new_cache
    # Chunk path: attend over the PRE-write cache plus the fresh in-chunk
    # K/V. Writing first would let ring-buffer slots be clobbered by later
    # in-chunk tokens that earlier queries still need (and would
    # double-count global slots).
    lay = {key: x[layer] for key, x in cache.items()}
    if "k_scale" in lay:         # int8 cache: dequantize the prefix (chunk
        k_cache = dequantize(lay["k"], lay["k_scale"]).astype(k.dtype)
        v_cache = dequantize(lay["v"], lay["v_scale"]).astype(v.dtype)
    else:
        k_cache, v_cache = lay["k"], lay["v"]
    k_all = jnp.concatenate([k_cache, k], axis=1)
    v_all = jnp.concatenate([v_cache, v], axis=1)
    kpos_all = jnp.concatenate([lay["kpos"], q_pos], axis=1)
    out = attend(cfg, q, k_all, v_all, q_pos, kpos_all, window=window)
    new_cache = cache_write(cache, layer, k, v, q_pos, window)
    return out.reshape(B, S, cfg.q_dim) @ p["wo"].astype(h.dtype), new_cache


# ---------------------------------------------------------------------------
# Paged self-attention (block-table indirection over a shared page pool)
# ---------------------------------------------------------------------------

def init_paged_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                        n_layers: int):
    """A paged cache for a run of ``n_layers`` global-attention layers.

    K/V live in a pool of ``num_pages`` fixed-size pages shared by every
    sequence; per-sequence block tables (held at the cache's top level)
    map logical page ``pos // page_size`` to a physical page. Physical
    page 0 is the null page: its ``pkpos`` stays -1, so block-table rows
    can point unallocated logical pages at it and masking does the rest.
    Leaves are named pk/pv/pkpos so the cached path can tell the layouts
    apart structurally (jit-safe — no static flags in the pytree).
    """
    if cfg.kv_quant:
        raise NotImplementedError("paged KV does not support kv_quant yet")
    shape = (n_layers, num_pages, page_size, cfg.num_kv_heads, cfg.head_dim)
    return {
        "pkpos": jnp.full((n_layers, num_pages, page_size), -1, jnp.int32),
        "pk": jnp.zeros(shape, cdtype(cfg)),
        "pv": jnp.zeros(shape, cdtype(cfg)),
    }


def gather_pages(x_pages, layer, block_table):
    """x_pages: (L,P,ps,...); block_table: (B,pmax) -> (B,pmax*ps,...).

    One gather of the pages each row maps, in layer ``layer`` of a run's
    stacked pool: no whole-layer slice in between. The gathered view is
    ordered by logical position (block tables map logical page i of a
    sequence to entry i), so downstream position masking sees a plain
    per-sequence cache."""
    g = x_pages[layer, block_table]
    return g.reshape((g.shape[0], g.shape[1] * g.shape[2]) + g.shape[3:])


def paged_cache_write(cache, layer, k_new, v_new, positions, block_table):
    """Write S new entries per sequence through the block table.

    cache: {"pk": (L,P,ps,KH,hd), "pv": ..., "pkpos": (L,P,ps)}, a run's
    stacked pool, written in layer ``layer``; k_new/v_new: (B,S,KH,hd);
    positions: (B,S) absolute (-1 = inactive); block_table: (B, pmax).
    Writes resolving to the null page (0) or past the table are dropped,
    like the contiguous path's mode="drop"."""
    L, P, ps, KH, hd = cache["pk"].shape
    pmax = block_table.shape[1]
    pidx = positions // ps
    page_ids = jnp.take_along_axis(
        block_table, jnp.clip(pidx, 0, pmax - 1), axis=1)
    ok = (positions >= 0) & (pidx < pmax) & (page_ids > 0)
    flat = jnp.where(ok, page_ids * ps + positions % ps, P * ps)
    out = dict(cache)
    for key, new in (("pk", k_new), ("pv", v_new)):
        pool = cache[key]
        out[key] = pool.reshape(L, P * ps, KH, hd).at[layer, flat].set(
            new.astype(pool.dtype), mode="drop").reshape(pool.shape)
    out["pkpos"] = cache["pkpos"].reshape(L, P * ps).at[layer, flat].set(
        positions, mode="drop").reshape(L, P, ps)
    return out


def self_attention_paged(cfg: ModelConfig, p, h, cache, layer, q_pos,
                         block_table, use_kernels: bool = False):
    """One layer of paged cached self-attention (global attention only).

    Same semantics as ``self_attention_cached`` with window=0, but K/V are
    read and written through the block table in layer ``layer`` of the
    run's stacked pool. Decode (S=1) writes first and attends over the
    updated pool (pages are request-exclusive, so no in-chunk clobber
    hazard exists); prefill chunks attend over the gathered prefix plus
    the fresh in-chunk K/V, then write.

    With ``use_kernels`` the gather+attend reference is replaced by the
    Pallas flash-decode kernels, which stream pages HBM->VMEM through the
    scalar-prefetched block table instead of materializing the gathered
    cache: single-query for decode, multi-query (write-first, one page
    stream for all S chunk tokens) for prefill chunks. The kernels read
    one layer's pool, sliced out of the stack.
    """
    q, k, v = project_qkv(cfg, p, h)
    if cfg.use_rope:
        q = apply_rope(q, q_pos, cfg.rope_theta)
        k = apply_rope(k, q_pos, cfg.rope_theta)
    B, S = h.shape[:2]
    if use_kernels:
        from repro.kernels import ops
        new_cache = paged_cache_write(cache, layer, k, v, q_pos, block_table)
        pk, pv, pkpos = (new_cache[key][layer] for key in ("pk", "pv", "pkpos"))
        if S == 1:
            out = ops.paged_decode_attention(
                q[:, 0], pk, pv, pkpos, block_table, q_pos[:, 0],
                softcap=cfg.attn_logit_softcap)[:, None]
        else:
            out = ops.paged_decode_attention_multi(
                q, pk, pv, pkpos, block_table, q_pos,
                softcap=cfg.attn_logit_softcap)
        return out.reshape(B, S, cfg.q_dim) @ p["wo"].astype(h.dtype), new_cache
    if S == 1:
        new_cache = paged_cache_write(cache, layer, k, v, q_pos, block_table)
        out = attend(cfg, q, gather_pages(new_cache["pk"], layer, block_table),
                     gather_pages(new_cache["pv"], layer, block_table), q_pos,
                     gather_pages(new_cache["pkpos"], layer, block_table))
        return out.reshape(B, S, cfg.q_dim) @ p["wo"].astype(h.dtype), new_cache
    k_all = jnp.concatenate(
        [gather_pages(cache["pk"], layer, block_table), k], axis=1)
    v_all = jnp.concatenate(
        [gather_pages(cache["pv"], layer, block_table), v], axis=1)
    kpos_all = jnp.concatenate(
        [gather_pages(cache["pkpos"], layer, block_table), q_pos], axis=1)
    out = attend(cfg, q, k_all, v_all, q_pos, kpos_all)
    new_cache = paged_cache_write(cache, layer, k, v, q_pos, block_table)
    return out.reshape(B, S, cfg.q_dim) @ p["wo"].astype(h.dtype), new_cache


def self_attention_full(cfg: ModelConfig, p, h, *, window: int = 0,
                        positions=None, causal: bool = True):
    """Training-path attention (no cache): full (causal) over (B,S,d)."""
    B, S = h.shape[:2]
    if positions is None:
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
    q, k, v = project_qkv(cfg, p, h)
    if cfg.use_rope:
        q = apply_rope(q, positions, cfg.rope_theta)
        k = apply_rope(k, positions, cfg.rope_theta)
    out = attend(cfg, q, k, v, positions, positions, window=window,
                 causal=causal)
    return out.reshape(B, S, cfg.q_dim) @ p["wo"].astype(h.dtype)


# ---------------------------------------------------------------------------
# Cross-attention (whisper decoder -> encoder output)
# ---------------------------------------------------------------------------

def cross_kv(cfg: ModelConfig, p, enc_out):
    """Precompute encoder K/V once per request batch. enc_out: (B,T,d)."""
    B, T = enc_out.shape[:2]
    k = (enc_out @ p["wk"].astype(enc_out.dtype))
    v = (enc_out @ p["wv"].astype(enc_out.dtype))
    if "bk" in p:
        k = k + p["bk"].astype(k.dtype)
        v = v + p["bv"].astype(v.dtype)
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    return k, v


def cross_attention(cfg: ModelConfig, p, h, ck, cv):
    """h: (B,S,d) decoder states; ck/cv: (B,T,KH,hd). Non-causal."""
    B, S = h.shape[:2]
    q = h @ p["wq"].astype(h.dtype)
    if "bq" in p:
        q = q + p["bq"].astype(q.dtype)
    q = q.reshape(B, S, cfg.num_heads, cfg.head_dim)
    T = ck.shape[1]
    qp = jnp.zeros((B, S), jnp.int32)
    kp = jnp.zeros((B, T), jnp.int32)
    out = attend(cfg, q, ck, cv, qp, kp, causal=False)
    return out.reshape(B, S, cfg.q_dim) @ p["wo"].astype(h.dtype)
