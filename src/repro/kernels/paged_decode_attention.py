"""Flash-decode over a paged KV cache (Pallas TPU).

The paged sibling of ``decode_attention``: K/V live in a pool of
fixed-size pages shared by every sequence, and each sequence streams its
pages HBM->VMEM through a *block-table* indirection instead of a
contiguous slot stripe. The block table rides in as the one
scalar-prefetch operand (``PrefetchScalarGridSpec``, held in SMEM), so
the page index of the next DMA is known before the kernel body runs — the
gather costs nothing beyond the streaming the contiguous kernel already
does.

Grid (B, pmax): the innermost axis walks the sequence's logical pages;
``index_map`` resolves logical page p of sequence b to physical page
``block_table[b, p]``. Each step DMAs one page with *all* KV heads — the
block ``(1, ps, KH, hd)`` spans the pool's two minor dims, the only
head-axis block the TPU's (8, 128) tiling accepts for any KH — and the
body loops over the heads statically. Unallocated table entries point at
physical page 0, the null page, whose per-token positions ``pkpos`` are
pinned to -1 — the standard position mask (kpos >= 0, kpos <= q_pos) then
drops them, and stale data from a page's previous owner is likewise
invisible because page resets set pkpos=-1.

The T query tokens of a sequence (T=1 for a decode step, the chunk width
for chunked prefill) ride in VMEM together with all G grouped heads as
``T*G`` rows per KV head, so every page is streamed HBM->VMEM *once* per
call for all of them. Their positions arrive as a VMEM block of one
column, ``(T*G, 1)``, so the mask is a plain broadcast against the page's
``(1, ps)`` position row. Causality within a chunk comes from that mask
alone (the in-flight tokens' K/V must already be written to their pages —
pages are request-exclusive, so write-first is safe).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

NEG_INF = -2.0 ** 30
# The (T*G, 1) m/l scratch pads its lane dim to 128, so VMEM grows with
# the chunk: a 256-token prefill chunk at G=4, KH=8, hd=128 needs ~16.4 MiB,
# just over the 16 MiB default scoped limit. Half of a v5e's 128 MiB.
VMEM_LIMIT_BYTES = 64 * 1024 * 1024


def _paged_kernel(bt_ref, q_ref, qpos_ref, k_ref, v_ref, kpos_ref, o_ref,
                  m_scr, l_scr, acc_scr, *, scale: float, window: int,
                  softcap: float, npages: int):
    i_p = pl.program_id(1)

    @pl.when(i_p == 0)
    def _init():
        m_scr[...] = jnp.full_like(m_scr, NEG_INF)
        l_scr[...] = jnp.zeros_like(l_scr)
        acc_scr[...] = jnp.zeros_like(acc_scr)

    kpos = kpos_ref[0]                                       # (1, ps)
    q_pos = qpos_ref[0]                                      # (T*G, 1)
    valid = (kpos >= 0) & (kpos <= q_pos)                    # (T*G, ps)
    if window:
        valid &= kpos > q_pos - window

    for h in range(k_ref.shape[2]):                          # KV heads
        q = q_ref[0, h].astype(jnp.float32)                  # (T*G, hd)
        k = k_ref[0, :, h, :].astype(jnp.float32)            # (ps, hd)
        v = v_ref[0, :, h, :].astype(jnp.float32)
        s = jax.lax.dot_general(q, k, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale
        if softcap:
            s = jnp.tanh(s / softcap) * softcap
        s = jnp.where(valid, s, NEG_INF)

        m_prev = m_scr[h]                                    # (T*G, 1)
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        p = jnp.where(valid, jnp.exp(s - m_new), 0.0)
        alpha = jnp.exp(m_prev - m_new)
        l_scr[h] = l_scr[h] * alpha + jnp.sum(p, axis=1, keepdims=True)
        acc_scr[h] = acc_scr[h] * alpha + jax.lax.dot_general(
            p, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        m_scr[h] = m_new

    @pl.when(i_p == npages - 1)
    def _finish():
        l = jnp.maximum(l_scr[...], 1e-30)
        o_ref[0] = (acc_scr[...] / l).astype(o_ref.dtype)


@functools.partial(jax.jit, static_argnames=(
    "window", "softcap", "interpret"))
def paged_decode_attention_multi(q, k_pages, v_pages, kpos_pages,
                                 block_table, q_pos, *, window: int = 0,
                                 softcap: float = 0.0,
                                 interpret: bool = False):
    """q: (B,T,H,hd); k/v_pages: (P,ps,KH,hd); kpos_pages: (P,ps);
    block_table: (B,pmax) int32 (0 = null page); q_pos: (B,T) int32
    (-1 = inactive query) -> (B,T,H,hd).

    The T queries of each sequence attend over the pool together: one
    block-table prefetch and one page stream per sequence per call, not
    per token or per head. K/V of the T in-flight tokens must already be
    written through the block table (write-first; causality is enforced
    by the position mask alone)."""
    B, T, H, hd = q.shape
    P, ps, KH = k_pages.shape[:3]
    pmax = block_table.shape[1]
    G = H // KH
    TG = T * G
    # row t*G + g of KV head h holds query head h*G + g of token t
    qg = jnp.moveaxis(q.reshape(B, T, KH, G, hd), 1, 2).reshape(B, KH, TG, hd)
    qpos_rows = jnp.repeat(q_pos.astype(jnp.int32), G, axis=1)[..., None]
    kpos_rows = kpos_pages.reshape(P, 1, ps)

    kernel = functools.partial(
        _paged_kernel, scale=hd ** -0.5, window=window, softcap=softcap,
        npages=pmax)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=1,                   # block_table
        grid=(B, pmax),
        in_specs=[
            pl.BlockSpec((1, KH, TG, hd), lambda b, p, bt: (b, 0, 0, 0)),
            pl.BlockSpec((1, TG, 1), lambda b, p, bt: (b, 0, 0)),
            pl.BlockSpec((1, ps, KH, hd), lambda b, p, bt: (bt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, ps, KH, hd), lambda b, p, bt: (bt[b, p], 0, 0, 0)),
            pl.BlockSpec((1, 1, ps), lambda b, p, bt: (bt[b, p], 0, 0)),
        ],
        out_specs=pl.BlockSpec((1, KH, TG, hd), lambda b, p, bt: (b, 0, 0, 0)),
        scratch_shapes=[
            pltpu.VMEM((KH, TG, 1), jnp.float32),
            pltpu.VMEM((KH, TG, 1), jnp.float32),
            pltpu.VMEM((KH, TG, hd), jnp.float32),
        ],
    )
    out = pl.pallas_call(
        kernel,
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((B, KH, TG, hd), q.dtype),
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
            vmem_limit_bytes=VMEM_LIMIT_BYTES),
        interpret=interpret,
    )(block_table, qg, qpos_rows, k_pages, v_pages, kpos_rows)
    return jnp.moveaxis(out.reshape(B, KH, T, G, hd), 2, 1).reshape(B, T, H, hd)


def paged_decode_attention(q, k_pages, v_pages, kpos_pages, block_table,
                           q_pos, *, window: int = 0, softcap: float = 0.0,
                           interpret: bool = False):
    """q: (B,H,hd); k/v_pages: (P,ps,KH,hd); kpos_pages: (P,ps);
    block_table: (B,pmax) int32 (0 = null page); q_pos: (B,) -> (B,H,hd).

    The single-query (decode step) case of
    ``paged_decode_attention_multi``, T=1."""
    return paged_decode_attention_multi(
        q[:, None], k_pages, v_pages, kpos_pages, block_table,
        q_pos[:, None], window=window, softcap=softcap,
        interpret=interpret)[:, 0]
