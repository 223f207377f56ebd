"""Per-layer blocks (dense / local / moe / ssm / hybrid) + run utilities.

A "run" is a maximal stretch of layers of identical kind (split additionally
at the probe tap boundary so the tap is always a run boundary). Each run's
parameters are stacked along a leading axis and executed with ``lax.scan``,
which keeps HLO size flat in depth for 40–64-layer configs.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.config import (KIND_ATTN, KIND_HYBRID, KIND_LOCAL, KIND_MOE,
                          KIND_SSM, ModelConfig)
from repro.models import attention as attn
from repro.models import moe as moe_mod
from repro.models import ssm as ssm_mod
from repro.models.layers import apply_norm, init_mlp, init_norm, apply_mlp


# ---------------------------------------------------------------------------
# Run computation
# ---------------------------------------------------------------------------

MAX_PATTERN = 8          # longest repeating block we scan as one step


def _segment_runs(kinds: tuple[str, ...]) -> list[tuple[tuple[str, ...], int]]:
    """Greedy periodic decomposition of one segment.

    Returns runs of (pattern_kinds, n_blocks): a run executes
    ``pattern_kinds`` n_blocks times via one lax.scan (alternating-layer
    archs like gemma2's LGLG... become 21 two-layer blocks instead of 42
    unrolled layers — compile time stays flat in depth).
    """
    runs: list[tuple[tuple[str, ...], int]] = []
    i = 0
    n = len(kinds)
    while i < n:
        best_p, best_cover = 1, 1
        for p in range(1, min(MAX_PATTERN, n - i) + 1):
            pat = kinds[i:i + p]
            nb = 1
            while kinds[i + nb * p:i + (nb + 1) * p] == pat:
                nb += 1
            cover = nb * p
            # multi-layer patterns must actually repeat, else p=1 runs win
            if cover > best_cover and (p == 1 or nb >= 2):
                best_p, best_cover = p, cover
        nb = best_cover // best_p
        runs.append((tuple(kinds[i:i + best_p]), nb))
        i += best_cover
    return runs


def split_runs(cfg: ModelConfig) -> tuple[tuple[tuple[str, ...], int], ...]:
    """Periodic-pattern runs, split so tap_layer ends a segment."""
    tap = cfg.probe.tap_layer
    seg1 = cfg.layer_kinds[:tap + 1]
    seg2 = cfg.layer_kinds[tap + 1:]
    runs = _segment_runs(seg1)
    if seg2:
        runs += _segment_runs(seg2)
    return tuple(runs)


def run_layers(run) -> int:
    """Total layer count of one (kinds, n_blocks) run."""
    kinds, nb = run
    return len(kinds) * nb


def tap_run_index(cfg: ModelConfig) -> int:
    """Index of the run whose last layer is the probe tap."""
    runs = split_runs(cfg)
    n = 0
    for ri, run in enumerate(runs):
        n += run_layers(run)
        if n - 1 >= cfg.probe.tap_layer:
            return ri
    return len(runs) - 1


# ---------------------------------------------------------------------------
# Single-layer init / apply
# ---------------------------------------------------------------------------

def init_block(key, cfg: ModelConfig, kind: str, cross: bool = False):
    """Initialize one decoder block of the given layer kind."""
    ks = jax.random.split(key, 8)
    dt = cfg.param_dtype
    p: dict = {"norm1": init_norm(cfg, jnp.dtype(dt))}
    if kind == KIND_SSM:
        p["ssm"] = ssm_mod.init_ssm(ks[0], cfg)
        return p
    p["attn"] = attn.init_attention(ks[0], cfg)
    p["norm2"] = init_norm(cfg, jnp.dtype(dt))
    if kind == KIND_MOE:
        p["moe"] = moe_mod.init_moe(ks[1], cfg)
        if cfg.moe_dense_residual:
            p["mlp"] = init_mlp(ks[2], cfg)
    elif kind == KIND_HYBRID:
        p["ssm"] = ssm_mod.init_ssm(ks[1], cfg)
        p["mlp"] = init_mlp(ks[2], cfg)
    else:
        p["mlp"] = init_mlp(ks[1], cfg)
    if cross:
        p["cross"] = attn.init_attention(ks[3], cfg)
        p["norm_cross"] = init_norm(cfg, jnp.dtype(dt))
    return p


def _kind_window(cfg: ModelConfig, kind: str) -> int:
    if kind == KIND_LOCAL:
        return cfg.sliding_window
    if kind == KIND_HYBRID:
        return cfg.sliding_window    # hymba SWA attention heads
    return 0


def _mlp_part(cfg: ModelConfig, kind: str, p, h):
    """Post-attention feed-forward (dense MLP / MoE / none). Returns (delta, aux)."""
    if kind == KIND_MOE:
        y, aux = moe_mod.moe_mlp(cfg, p["moe"], apply_norm(cfg, p["norm2"], h))
        if cfg.moe_dense_residual:
            y = y + apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["norm2"], h))
        return y, aux
    return apply_mlp(cfg, p["mlp"], apply_norm(cfg, p["norm2"], h)), jnp.float32(0)


def block_train(cfg: ModelConfig, kind: str, p, h, enc_out=None, positions=None):
    """Training-path block (no cache). Returns (h, aux)."""
    hn = apply_norm(cfg, p["norm1"], h)
    if kind == KIND_SSM:
        y, _ = ssm_mod.ssm_forward(cfg, p["ssm"], hn)
        return h + y, jnp.float32(0)
    window = _kind_window(cfg, kind)
    a = attn.self_attention_full(cfg, p["attn"], hn, window=window,
                                 positions=positions)
    if kind == KIND_HYBRID:
        s, _ = ssm_mod.ssm_forward(cfg, p["ssm"], hn)
        a = 0.5 * (a + s)
    h = h + a
    if enc_out is not None and "cross" in p:
        ck, cv = attn.cross_kv(cfg, p["cross"], enc_out)
        h = h + attn.cross_attention(cfg, p["cross"],
                                     apply_norm(cfg, p["norm_cross"], h),
                                     ck, cv)
    y, aux = _mlp_part(cfg, kind, p, h)
    return h + y, aux


def _ssm_cached(cfg: ModelConfig, p, hn, cache, layer, decode: bool):
    """SSM part of a cached block on layer ``layer`` of the run's stacked
    state. Returns (y, {"ssm_state", "conv_buf"} with that layer updated)."""
    state, conv_buf = cache["ssm_state"][layer], cache["conv_buf"][layer]
    if decode:
        y, (st, cb) = ssm_mod.ssm_decode_step(cfg, p, hn, state, conv_buf)
    else:
        y, (st, cb) = ssm_mod.ssm_forward(cfg, p, hn, state=state,
                                          conv_buf=conv_buf)
    return y, {"ssm_state": cache["ssm_state"].at[layer].set(st),
               "conv_buf": cache["conv_buf"].at[layer].set(cb)}


def block_cached(cfg: ModelConfig, kind: str, p, h, cache, layer, q_pos,
                 decode: bool = False, block_table=None,
                 use_kernels: bool = False):
    """Cached-path block (prefill chunk or decode). Returns (h, cache, aux).

    h: (B,S,d); q_pos: (B,S) absolute positions (-1 = inactive slot).
    ``cache`` is the run's stacked cache (every leaf has a leading layer
    axis) and ``layer`` this block's index into it: reads take that layer,
    writes touch only what the step changes, so the run scan carries the
    whole cache and updates it in place.
    ``block_table`` (B, pmax) routes K/V through the shared page pool when
    this run's cache is paged (pk/pv/pkpos leaves). ``use_kernels`` swaps
    the paged gather+attend reference for the Pallas flash-decode kernels
    (single-query for decode, multi-query for prefill chunks).
    """
    hn = apply_norm(cfg, p["norm1"], h)
    new_cache = dict(cache)
    if kind == KIND_SSM:
        y, st = _ssm_cached(cfg, p["ssm"], hn, cache, layer, decode)
        new_cache.update(st)
        return h + y, new_cache, jnp.float32(0)

    window = _kind_window(cfg, kind)
    with jax.named_scope("attention"):
        if "pk" in cache:
            kvcache = {k: cache[k] for k in ("pk", "pv", "pkpos")}
            a, kv_new = attn.self_attention_paged(cfg, p["attn"], hn, kvcache,
                                                  layer, q_pos, block_table,
                                                  use_kernels=use_kernels)
        else:
            kv_keys = ("k", "v", "kpos", "k_scale", "v_scale")
            kvcache = {k: cache[k] for k in kv_keys if k in cache}
            a, kv_new = attn.self_attention_cached(cfg, p["attn"], hn, kvcache,
                                                   layer, q_pos, window=window)
    new_cache.update(kv_new)
    if kind == KIND_HYBRID:
        s, st = _ssm_cached(cfg, p["ssm"], hn, cache, layer, decode)
        new_cache.update(st)
        a = 0.5 * (a + s)
    h = h + a
    if "cross" in p and "ck" in cache:
        h = h + attn.cross_attention(cfg, p["cross"],
                                     apply_norm(cfg, p["norm_cross"], h),
                                     cache["ck"][layer], cache["cv"][layer])
    with jax.named_scope("mlp"):
        y, aux = _mlp_part(cfg, kind, p, h)
    return h + y, new_cache, aux


# ---------------------------------------------------------------------------
# Per-run cache init
# ---------------------------------------------------------------------------

def init_run_cache(cfg: ModelConfig, kind: str, n_layers: int, batch: int,
                   max_len: int, enc_seq: int = 0, kv_layout: str = "contig",
                   num_pages: int = 0, page_size: int = 0):
    """Allocate the decode cache for one homogeneous layer run."""
    cache: dict = {}
    window = _kind_window(cfg, kind)
    if kind != KIND_SSM:
        # Windowed runs keep their ring buffers even under kv_layout="paged":
        # they are already bounded at the window, so paging buys nothing.
        if kv_layout == "paged" and not window:
            cache.update(attn.init_paged_kv_cache(cfg, num_pages, page_size,
                                                  n_layers))
        else:
            cache.update(attn.init_kv_cache(cfg, batch, max_len, n_layers,
                                            window=window))
    if kind in (KIND_SSM, KIND_HYBRID):
        cache.update(ssm_mod.init_ssm_state(cfg, batch, n_layers))
    if cfg.cross_attention and enc_seq:
        dt = jnp.dtype(cfg.dtype)
        cache["ck"] = jnp.zeros((n_layers, batch, enc_seq, cfg.num_kv_heads,
                                 cfg.head_dim), dt)
        cache["cv"] = jnp.zeros_like(cache["ck"])
    return cache
