"""Model factory: one composable bundle per architecture config.

``build_model(cfg)`` returns a :class:`Model` exposing:

  init(rng)                          -> params   (includes the TRAIL probe)
  init_cache(batch, max_len)         -> cache    (per-run KV / SSM state)
  forward_train(params, batch)       -> (loss, aux)   aux: {"tap": (B,S,d), ...}
  encode(params, enc_embeds)         -> enc_out       (enc-dec only)
  prefill_chunk(params, cache, ...)  -> (logits_last, cache, tap_sum, tap_cnt)
  decode_step(params, cache, ...)    -> (logits, cache, tap, probe_logits)

The decode step *fuses the paper's probe* (Section 3.1/3.2): the tap layer's
hidden state feeds the 2-layer MLP classifier inside the same jitted program
— the TPU-native replacement for vLLM's CPU-offloaded predictor.
"""

from __future__ import annotations

import functools
from typing import Any

import jax
import jax.numpy as jnp

from repro.config import KIND_SSM, ModelConfig
from repro.core import predictor
from repro.models import attention as attn_mod
from repro.models import transformer as tfm
from repro.models.layers import (apply_norm, cdtype, embed_init, embed_tokens,
                                 init_norm, pdtype, unembed)

MAX_LEARNED_POS = 32768


class Model:
    """Stateless forward passes over a params dict for one ModelConfig."""

    def __init__(self, cfg: ModelConfig, use_kernels: bool = False):
        self.cfg = cfg
        self.use_kernels = use_kernels
        self.runs = tfm.split_runs(cfg)
        self.tap_run = tfm.tap_run_index(cfg)

    # ------------------------------------------------------------------
    # Init
    # ------------------------------------------------------------------
    def init(self, rng) -> dict:
        """Initialize the full parameter dict (embed/blocks/probe/...)."""
        cfg = self.cfg
        keys = jax.random.split(rng, len(self.runs) + 6)
        dt = pdtype(cfg)
        params: dict[str, Any] = {
            "embed": embed_init(keys[0], cfg.vocab_size, cfg.d_model, dt),
            "final_norm": init_norm(cfg, dt),
        }
        if not cfg.tie_embeddings:
            params["unembed"] = embed_init(keys[1], cfg.vocab_size, cfg.d_model, dt)
        if not cfg.use_rope:
            params["pos_embed"] = embed_init(
                keys[2], min(MAX_LEARNED_POS, 1 << 16), cfg.d_model, dt)
        cross = cfg.cross_attention
        layer_params = []
        for ri, (kinds, nb) in enumerate(self.runs):
            sub = []
            for j, kind in enumerate(kinds):
                ks = jax.random.split(
                    jax.random.fold_in(keys[3], ri * 64 + j), nb)
                sub.append(jax.vmap(
                    lambda k, _kind=kind: tfm.init_block(
                        k, cfg, _kind, cross=cross))(ks))
            layer_params.append(tuple(sub))
        params["layers"] = tuple(layer_params)
        if cfg.num_encoder_layers:
            params["encoder"] = self._init_encoder(keys[4])
        params["probe"] = predictor.init_probe(keys[5], cfg.d_model, cfg.probe)
        return params

    def _init_encoder(self, key):
        cfg = self.cfg
        ks = jax.random.split(key, 3)
        enc_layers = jax.vmap(
            lambda k: tfm.init_block(k, cfg, "attn", cross=False))(
                jax.random.split(ks[0], cfg.num_encoder_layers))
        return {
            "pos": embed_init(ks[1], cfg.encoder_seq, cfg.d_model, pdtype(cfg)),
            "layers": enc_layers,
            "final_norm": init_norm(cfg, pdtype(cfg)),
        }

    # ------------------------------------------------------------------
    # Cache
    # ------------------------------------------------------------------
    def init_cache(self, batch: int, max_len: int,
                   kv_layout: str = "contig", page_size: int = 16) -> dict:
        """kv_layout="paged" stores global-attention K/V in a shared page
        pool (1 null page + batch * ceil(max_len/page_size) pages) behind a
        (batch, pages_per_seq) block table; windowed/SSM/cross state keeps
        the per-slot layout (already bounded)."""
        cfg = self.cfg
        cache: dict[str, Any] = {
            "lengths": jnp.zeros((batch,), jnp.int32),
        }
        num_pages = 0
        if kv_layout == "paged":
            pages_per_seq = -(-max_len // page_size)
            num_pages = 1 + batch * pages_per_seq      # page 0 = null page
            cache["block_table"] = jnp.zeros((batch, pages_per_seq),
                                             jnp.int32)
        for ri, (kinds, nb) in enumerate(self.runs):
            cache[f"run_{ri}"] = tuple(
                tfm.init_run_cache(cfg, kind, nb, batch, max_len,
                                   enc_seq=cfg.encoder_seq,
                                   kv_layout=kv_layout, num_pages=num_pages,
                                   page_size=page_size)
                for kind in kinds)
        return cache

    # ------------------------------------------------------------------
    # Embedding helpers
    # ------------------------------------------------------------------
    def _embed(self, params, tokens, positions):
        cfg = self.cfg
        h = embed_tokens(cfg, params, tokens)
        if not cfg.use_rope and "pos_embed" in params:
            table = params["pos_embed"]
            idx = jnp.clip(positions, 0, table.shape[0] - 1)
            h = h + table[idx].astype(h.dtype)
        return h

    # ------------------------------------------------------------------
    # Encoder (whisper; stub frontend supplies enc_embeds)
    # ------------------------------------------------------------------
    def encode(self, params, enc_embeds):
        """Run the non-causal encoder stack over frontend embeddings."""
        cfg = self.cfg
        enc = params["encoder"]
        h = enc_embeds.astype(cdtype(cfg))
        h = h + enc["pos"][None, : h.shape[1]].astype(h.dtype)

        def body(carry, p_l):
            """One encoder block: self-attention + MLP residuals."""
            hn = apply_norm(cfg, p_l["norm1"], carry)
            a = attn_mod.self_attention_full(cfg, p_l["attn"], hn, causal=False)
            carry = carry + a
            from repro.models.layers import apply_mlp
            carry = carry + apply_mlp(
                cfg, p_l["mlp"], apply_norm(cfg, p_l["norm2"], carry))
            return carry, None

        h, _ = jax.lax.scan(body, h, enc["layers"])
        return apply_norm(cfg, enc["final_norm"], h)

    def build_cross_cache(self, params, cache, enc_out):
        """Fill each run's ck/cv from the encoder output."""
        cfg = self.cfg
        new = dict(cache)
        for ri, (kinds, nb) in enumerate(self.runs):
            subs = []
            changed = False
            for j, sub in enumerate(new[f"run_{ri}"]):
                sub = dict(sub)
                if "ck" in sub:
                    p_run = params["layers"][ri][j]
                    ck, cv = jax.vmap(
                        lambda pl: attn_mod.cross_kv(
                            cfg, pl["cross"], enc_out))(p_run)
                    sub["ck"], sub["cv"] = ck, cv
                    changed = True
                subs.append(sub)
            if changed:
                new[f"run_{ri}"] = tuple(subs)
        return new

    # ------------------------------------------------------------------
    # Training forward
    # ------------------------------------------------------------------
    def forward_train(self, params, batch) -> tuple[jax.Array, dict]:
        """batch: {"tokens": (B,S), "labels": (B,S)} (+ enc/prefix embeds).

        Returns (loss, {"aux_loss", "tap" (B,S,d), "logits_sample"}).
        """
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        h = self._embed(params, tokens, positions)
        labels = batch["labels"]

        prefix = batch.get("prefix_embeds")
        if prefix is not None:                      # VLM: vision prefix
            P = prefix.shape[1]
            h = jnp.concatenate([prefix.astype(h.dtype), h], axis=1)
            positions = jnp.broadcast_to(
                jnp.arange(P + S, dtype=jnp.int32), (B, P + S))
            labels = jnp.concatenate(
                [jnp.full((B, P), -1, labels.dtype), labels], axis=1)

        enc_out = None
        if "enc_embeds" in batch:                   # audio: encoder pass
            enc_out = self.encode(params, batch["enc_embeds"])

        tap = None
        aux_total = jnp.float32(0)
        for ri, (kinds, nb) in enumerate(self.runs):
            def body(carry, p_blk, _kinds=kinds):
                aux = jnp.float32(0)
                for j, kind in enumerate(_kinds):
                    carry, a = tfm.block_train(cfg, kind, p_blk[j], carry,
                                               enc_out=enc_out,
                                               positions=positions)
                    aux = aux + a
                return carry, aux
            if cfg.remat:
                body = jax.checkpoint(body)
            h, auxs = jax.lax.scan(body, h, params["layers"][ri])
            aux_total = aux_total + jnp.sum(auxs)
            if ri == self.tap_run:
                tap = h
        h = apply_norm(cfg, params["final_norm"], h)
        loss, n_tok = _chunked_ce(cfg, params, h, labels)
        aux = {"aux_loss": aux_total, "tap": tap, "n_tok": n_tok}
        total = loss + cfg.router_aux_weight * aux_total
        return total, aux

    def forward_all_taps(self, params, batch):
        """Profiling pass (paper Section 3.1 'we profile embeddings across
        all 32 layers'): returns hidden states after EVERY layer,
        shape (num_layers, B, S, d). Train-path semantics, no loss."""
        cfg = self.cfg
        tokens = batch["tokens"]
        B, S = tokens.shape
        positions = jnp.broadcast_to(jnp.arange(S, dtype=jnp.int32), (B, S))
        h = self._embed(params, tokens, positions)
        enc_out = None
        if "enc_embeds" in batch:
            enc_out = self.encode(params, batch["enc_embeds"])
        taps = []
        for ri, (kinds, nb) in enumerate(self.runs):
            def body(carry, p_blk, _kinds=kinds):
                outs = []
                for j, kind in enumerate(_kinds):
                    carry, _ = tfm.block_train(cfg, kind, p_blk[j], carry,
                                               enc_out=enc_out,
                                               positions=positions)
                    outs.append(carry)
                return carry, jnp.stack(outs)
            h, per_block = jax.lax.scan(body, h, params["layers"][ri])
            # (nb, p, B, S, d) -> (nb*p, B, S, d) in layer order
            taps.append(per_block.reshape((-1,) + per_block.shape[2:]))
        return jnp.concatenate(taps, axis=0)

    # ------------------------------------------------------------------
    # Cached forward (chunked prefill; decode is the S=1 case)
    # ------------------------------------------------------------------
    def _cached_trunk(self, params, cache, h, q_pos, decode: bool):
        """Run every layer over ``h``. Each run's stacked cache rides in its
        layer scan's carry beside ``h``; the scan hands each layer its index,
        so a layer reads its slice of the stack and writes only the entries
        the step changes, and XLA updates the donated cache in place."""
        cfg = self.cfg
        new_cache = dict(cache)
        tap = None
        aux_total = jnp.float32(0)
        block_table = cache.get("block_table")     # shared across all layers
        for ri, (kinds, nb) in enumerate(self.runs):
            def body(carry, xs, _kinds=kinds):
                h, run_cache = carry
                p_blk, layer = xs
                new_blk = []
                aux = jnp.float32(0)
                for j, kind in enumerate(_kinds):
                    h, c_new, a = tfm.block_cached(
                        cfg, kind, p_blk[j], h, run_cache[j], layer, q_pos,
                        decode=decode, block_table=block_table,
                        use_kernels=self.use_kernels)
                    new_blk.append(c_new)
                    aux = aux + a
                return (h, tuple(new_blk)), aux
            (h, run_cache), auxs = jax.lax.scan(
                body, (h, cache[f"run_{ri}"]),
                (params["layers"][ri], jnp.arange(nb, dtype=jnp.int32)))
            new_cache[f"run_{ri}"] = run_cache
            aux_total = aux_total + jnp.sum(auxs)
            if ri == self.tap_run:
                tap = h
        return h, new_cache, tap, aux_total

    def prefill_chunk(self, params, cache, tokens, valid=None,
                      prefix_embeds=None, enc_embeds=None):
        """Process a chunk of prompt tokens for every active row.

        tokens: (B,C); valid: (B,C) bool (contiguous prefixes) or None.
        Returns (next_logits (B,V), cache, tap_sum (B,d), tap_cnt (B,)).
        """
        with jax.named_scope("prefill"):
            cfg = self.cfg
            B, C = tokens.shape
            if valid is None:
                valid = jnp.ones((B, C), bool)
            offsets = cache["lengths"]

            if enc_embeds is not None:
                enc_out = self.encode(params, enc_embeds)
                cache = self.build_cross_cache(params, cache, enc_out)

            q_pos = jnp.where(valid, offsets[:, None] + jnp.arange(C, dtype=jnp.int32),
                              -1)
            h = self._embed(params, tokens, q_pos)
            if prefix_embeds is not None:
                P = prefix_embeds.shape[1]
                ppos = offsets[:, None] + jnp.arange(P, dtype=jnp.int32)
                h = jnp.concatenate([prefix_embeds.astype(h.dtype), h], axis=1)
                q_pos = jnp.concatenate(
                    [ppos, jnp.where(valid, q_pos + P, -1)], axis=1)
                valid = jnp.concatenate([jnp.ones((B, P), bool), valid], axis=1)

            h, new_cache, tap, _ = self._cached_trunk(params, cache, h, q_pos,
                                                      decode=False)
            n_new = jnp.sum(valid, axis=1).astype(jnp.int32)
            new_cache["lengths"] = offsets + n_new

            # next-token logits from the last valid position of each row
            last_idx = jnp.maximum(jnp.sum(valid, axis=1) - 1, 0)
            h_last = h[jnp.arange(B), last_idx]
            with jax.named_scope("lm_head"):
                h_last = apply_norm(cfg, params["final_norm"], h_last)
                logits = unembed(cfg, params, h_last)

            # paper: prompt-phase probe input = mean of prompt-token taps
            with jax.named_scope("probe"):
                vmask = valid[..., None].astype(jnp.float32)
                tap_sum = jnp.sum(tap.astype(jnp.float32) * vmask, axis=1)
            return logits, new_cache, tap_sum, n_new

    def decode_step(self, params, cache, tokens, active=None):
        """One iteration: generate-one-token for every active row.

        tokens: (B,1) int32; active: (B,) bool. Fuses the probe classifier.
        Returns (logits (B,V), cache, tap (B,d), probe_logits (B,k)).
        """
        cfg = self.cfg
        B = tokens.shape[0]
        if active is None:
            active = jnp.ones((B,), bool)
        lengths = cache["lengths"]
        q_pos = jnp.where(active, lengths, -1)[:, None]
        h = self._embed(params, tokens, q_pos)
        h, new_cache, tap, _ = self._cached_trunk(params, cache, h, q_pos,
                                                  decode=True)
        # inactive rows must not mutate recurrent state (KV writes already
        # dropped via position -1; SSM state needs an explicit select)
        new_cache = _mask_recurrent(cache, new_cache, active)
        new_cache["lengths"] = lengths + active.astype(jnp.int32)
        with jax.named_scope("lm_head"):
            hn = apply_norm(cfg, params["final_norm"], h[:, 0])
            logits = unembed(cfg, params, hn)
        with jax.named_scope("probe"):
            tap = tap[:, 0]
            probe_logits = predictor.apply_probe(params["probe"], tap)
        return logits, new_cache, tap, probe_logits

    def decode_multi(self, params, cache, tokens, active=None, budget=None,
                     *, k: int = 1, eos_id: int = -1):
        """Decode megastep: ``k`` fused decode+probe steps under one
        ``lax.scan`` with on-device greedy sampling and per-row halting.

        The (B, vocab) logits never leave the device — each step argmaxes
        on device and feeds the winner back as the next query, so the host
        round-trip per megastep is O(B*k) token ids plus O(B*k*num_bins)
        probe posteriors instead of k transfers of O(B*vocab) logits.

        tokens: (B,1) int32 — last known token per row; active: (B,) bool;
        budget: (B,) int32 — max tokens each row may still emit (rows halt
        early on budget exhaustion or, when ``eos_id >= 0``, after emitting
        the EOS token; halted rows stop writing KV / advancing ``lengths``
        exactly like inactive rows). ``k`` and ``eos_id`` must be static
        under jit.

        Returns (tokens (B,k) int32 with -1 past each row's halt point,
        cache, probe_probs (B,k,num_bins) f32 softmax posteriors,
        n_emitted (B,) int32).
        """
        B = tokens.shape[0]
        if active is None:
            active = jnp.ones((B,), bool)
        if budget is None:
            budget = jnp.full((B,), k, jnp.int32)
        budget = jnp.minimum(budget.astype(jnp.int32), k)

        def step(carry, _):
            """One scanned decode step over the active rows."""
            cache, tok, emitted, halted = carry
            act = active & ~halted & (emitted < budget)
            logits, cache, _, probe_logits = self.decode_step(
                params, cache, tok, active=act)
            with jax.named_scope("sample"):
                nxt = jnp.argmax(logits, axis=-1).astype(jnp.int32)
            if eos_id >= 0:
                halted = halted | (act & (nxt == eos_id))
            emitted = emitted + act.astype(jnp.int32)
            with jax.named_scope("probe"):
                probs = jax.nn.softmax(probe_logits.astype(jnp.float32), axis=-1)
            tok_out = jnp.where(act, nxt, -1)
            tok_next = jnp.where(act, nxt, tok[:, 0])[:, None]
            return (cache, tok_next, emitted, halted), (tok_out, probs)

        with jax.named_scope("decode"):
            carry0 = (cache, tokens, jnp.zeros((B,), jnp.int32),
                      jnp.zeros((B,), bool))
            (cache, _, emitted, _), (toks, probs) = jax.lax.scan(
                step, carry0, None, length=k)
        return (jnp.moveaxis(toks, 0, 1), cache,
                jnp.moveaxis(probs, 0, 1), emitted)


def _mask_recurrent(old_cache, new_cache, active):
    out = dict(new_cache)
    for key, run_new in new_cache.items():
        if not key.startswith("run_"):
            continue
        run_old = old_cache[key]
        merged_run = []
        for sub_new, sub_old in zip(run_new, run_old):
            merged = dict(sub_new)
            for leaf in ("ssm_state", "conv_buf"):
                if leaf in merged:
                    a = active.reshape(
                        (1, -1) + (1,) * (merged[leaf].ndim - 2))
                    merged[leaf] = jnp.where(a, merged[leaf], sub_old[leaf])
            merged_run.append(merged)
        out[key] = tuple(merged_run)
    return out


def _chunked_ce(cfg: ModelConfig, params, h, labels, chunk: int = 256):
    """Cross-entropy without materializing (B,S,V) logits: lax.scan over
    sequence chunks (vocab up to 262k makes full logits impossible at 4k seq).
    The body is remat'd so the backward holds one chunk's softmax at a time.
    Returns (mean loss over labels>=0, number of such tokens)."""
    B, S, d = h.shape
    while S % chunk:
        chunk //= 2
    nc = S // chunk
    hs = jnp.moveaxis(h.reshape(B, nc, chunk, d), 1, 0)
    ls = jnp.moveaxis(labels.reshape(B, nc, chunk), 1, 0)

    @jax.checkpoint
    def body(acc, xs):
        """Accumulate masked NLL over one rematerialized logit chunk."""
        hc, lc = xs
        logits = unembed(cfg, params, hc)                  # (B,chunk,V) f32
        lse = jax.nn.logsumexp(logits, axis=-1)
        gold = jnp.take_along_axis(
            logits, jnp.maximum(lc, 0)[..., None], axis=-1)[..., 0]
        mask = (lc >= 0).astype(jnp.float32)
        nll = jnp.sum((lse - gold) * mask)
        return (acc[0] + nll, acc[1] + jnp.sum(mask)), None

    (nll, n), _ = jax.lax.scan(body, (jnp.float32(0), jnp.float32(0)), (hs, ls))
    return nll / jnp.maximum(n, 1.0), n


@functools.lru_cache(maxsize=None)
def _build_cached(cfg: ModelConfig, use_kernels: bool) -> Model:
    return Model(cfg, use_kernels=use_kernels)


def build_model(cfg: ModelConfig, use_kernels: bool = False) -> Model:
    """Return the (cached) `Model` wrapper for ``cfg``."""
    return _build_cached(cfg, use_kernels)
