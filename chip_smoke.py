#!/usr/bin/env python3
"""Bring-up smoke of the TRAIL serving path on one TPU chip.

    python3 chip_smoke.py [--seed N]

Runs in one process, in this order, and exits non-zero at the first
phase that fails:

  (a) prints the device and the model cut: granite-3-8b at its published
      widths, cut by depth only to 20 of 40 layers (the first pipeline
      stage of a two-chip deployment), bf16 weights, probe tap at layer 14;
  (b) runs both paged Pallas kernels compiled for the chip
      (``interpret=False``) at granite decode shapes against the
      ``repro.kernels.ref`` oracles;
  (c) runs one ``prefill_chunk`` and one ``decode_multi(k=4)`` of the cut
      model with the kernels on against the jnp path, compared on prefill
      logits and probe posteriors, and checks that the kernels-on decode
      program holds a ``tpu_custom_call``;
  (d) serves 12 seeded requests through the real engine (TRAIL policy,
      paged KV, ``ProbePredictor``) twice, kernels off and on, and checks
      that every request finishes its budget with in-vocabulary tokens,
      that at least one preemption happened and that no page leaked;
  (e) prints ``{"ok": true, "device": {...}}`` as its last line.

Where JAX finds no TPU it exits non-zero before any phase. Weights and
requests are random, drawn from ``--seed``. Wall times are cold smoke
times that include compilation; they are not benchmark numbers, and no
cost-model time is printed.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import time
import traceback
import warnings

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

DEPTH = 20            # of granite-3-8b's 40 layers
BATCH = 16            # engine slots (max_batch) and kernel batch
MAX_LEN = 1024
PAGE = 16
K = 4                 # decode megastep length (probe_interval)
CHUNK = 256           # largest prefill chunk (EngineConfig.prefill_chunk)
N_REQUESTS = 12
WAVE = 4              # requests per arrival wave

# (b) kernel vs ref.py, elementwise |kernel - ref| <= REL*|ref| + ABS. Both
# round their float32 result to bf16 once: up to one bf16 ulp apart, 2^-7
# relative at worst. The kernel's float32 probabilities may enter the MXU
# rounded to bf16 (2^-9 relative per term); over values v ~ N(0, 1) that
# is below 2^-9 * E|v| < 2^-8 absolute.
KERNEL_REL, KERNEL_ABS = 2.0 ** -7, 2.0 ** -8
# (c) kernels on vs off through 20 bf16 layers: both paths share every op
# but attention, whose float32 sums differ in order and precision; a
# resulting one-ulp flip (2^-8 relative) of a bf16 activation travels
# through the residual stream of every later layer. Logits are compared
# relative to their largest magnitude, posteriors (probabilities) absolutely.
LOGIT_REL_TOL = 5e-2
POSTERIOR_TOL = 5e-2


class SmokeFailure(Exception):
    """A phase's check did not hold."""


def check(ok: bool, what: str):
    """Raise `SmokeFailure` with ``what`` unless ``ok``."""
    if not ok:
        raise SmokeFailure(what)


def cut_config():
    """granite-3-8b at published widths, cut to DEPTH layers, bf16 weights."""
    from repro.config import get_config
    full = get_config("granite-3-8b")
    return dataclasses.replace(full, num_layers=DEPTH,
                               layer_kinds=full.layer_kinds[:DEPTH],
                               param_dtype="bfloat16")


def describe(cfg) -> dict:
    """The configuration fields phase (a) prints."""
    return {"name": cfg.name, "source": cfg.source,
            "layers": f"{cfg.num_layers} (depth cut only)",
            "d_model": cfg.d_model, "heads": cfg.num_heads,
            "kv_heads": cfg.num_kv_heads, "head_dim": cfg.head_dim,
            "d_ff": cfg.d_ff, "vocab": cfg.vocab_size,
            "param_dtype": cfg.param_dtype, "dtype": cfg.dtype,
            "probe_tap_layer": cfg.probe.tap_layer}


# ---------------------------------------------------------------------------
# (b) paged kernels against ref.py
# ---------------------------------------------------------------------------

def kernel_phase(cfg, seed: int, interpret: bool = False) -> dict:
    """Both paged kernels at ``cfg``'s decode shapes against ref.py.

    A scrambled page pool: every sequence owns MAX_LEN/PAGE physical pages
    in random order and holds a random length; unwritten slots are -1.
    Returns the largest error of each case.
    """
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.kernels import ops, ref

    rng = np.random.default_rng(seed)
    B, KH, H, hd = BATCH, cfg.num_kv_heads, cfg.num_heads, cfg.head_dim
    pmax = MAX_LEN // PAGE
    P = 1 + B * pmax
    ks = jax.random.split(jax.random.key(seed), 4)
    dt = jnp.dtype(cfg.dtype)
    k = jax.random.normal(ks[0], (P, PAGE, KH, hd), jnp.float32).astype(dt)
    v = jax.random.normal(ks[1], (P, PAGE, KH, hd), jnp.float32).astype(dt)
    table = (1 + rng.permutation(B * pmax)).reshape(B, pmax).astype(np.int32)
    lengths = rng.integers(1, MAX_LEN + 1, size=B)
    kpos = np.full((P, PAGE), -1, np.int32)
    for b in range(B):
        pos = np.arange(lengths[b])
        kpos[table[b, pos // PAGE], pos % PAGE] = pos
    kpos, table = jnp.asarray(kpos), jnp.asarray(table)
    last = jnp.asarray(lengths - 1, jnp.int32)

    errs = {}
    for T in (1, K, CHUNK):
        q = jax.random.normal(jax.random.fold_in(ks[2], T), (B, T, H, hd),
                              jnp.float32).astype(dt)
        q_pos = last[:, None] - jnp.arange(T - 1, -1, -1, dtype=jnp.int32)
        q_pos = jnp.where(q_pos >= 0, q_pos, -1)
        with jax.default_matmul_precision("highest"):
            if T == 1:
                want = ref.paged_decode_attention_ref(
                    q[:, 0], k, v, kpos, table, q_pos[:, 0])[:, None]
            else:
                want = ref.paged_decode_attention_multi_ref(
                    q, k, v, kpos, table, q_pos)
        if T == 1:
            name = "paged_decode_attention"
            got = ops.paged_decode_attention(
                q[:, 0], k, v, kpos, table, q_pos[:, 0],
                interpret=interpret)[:, None]
        else:
            name = f"paged_decode_attention_multi[T={T}]"
            got = ops.paged_decode_attention_multi(
                q, k, v, kpos, table, q_pos, interpret=interpret)
        got = np.asarray(got.astype(jnp.float32))
        want = np.asarray(want.astype(jnp.float32))
        check(bool(np.all(np.isfinite(got))), f"{name}: non-finite output")
        err = np.abs(got - want)
        bound = KERNEL_REL * np.abs(want) + KERNEL_ABS
        errs[name] = float(err.max())
        print(f"  {name}: max |kernel-ref| {err.max():.3e}, worst "
              f"err/bound {float((err / bound).max()):.3f}, "
              f"max |ref| {np.abs(want).max():.3f}", flush=True)
        check(bool(np.all(err <= bound)),
              f"{name} differs from ref.py beyond {KERNEL_REL:g}*|ref| + "
              f"{KERNEL_ABS:g}")
    return errs


# ---------------------------------------------------------------------------
# (c) one prefill chunk + one decode megastep, kernels on vs off
# ---------------------------------------------------------------------------

def model_phase(cfg, params, seed: int, require_custom_call: bool) -> dict:
    """prefill_chunk + decode_multi(k=K) with the kernels on vs off.

    Both runs get the same scrambled block table, prompt rows of
    64..CHUNK tokens and the same first decode token (the kernels-off
    argmax), so the posteriors of the first decode step compare
    directly; later steps compare only on rows whose sampled tokens
    agree so far.
    """
    import jax.numpy as jnp
    import numpy as np

    from repro.models.model import build_model
    from repro.serving.kv_cache import donating_jit

    rng = np.random.default_rng(seed + 1)
    pmax = MAX_LEN // PAGE
    table = (1 + rng.permutation(BATCH * pmax)).reshape(BATCH, pmax)
    lens = rng.integers(64, CHUNK + 1, size=BATCH)
    tokens = jnp.asarray(rng.integers(1, cfg.vocab_size, (BATCH, CHUNK)),
                         jnp.int32)
    valid = jnp.asarray(np.arange(CHUNK)[None, :] < lens[:, None])
    active = jnp.ones((BATCH,), bool)
    budget = jnp.full((BATCH,), K, jnp.int32)

    out, tok0 = {}, None
    for use_kernels in (False, True):
        model = build_model(cfg, use_kernels=use_kernels)
        cache = model.init_cache(BATCH, MAX_LEN, kv_layout="paged",
                                 page_size=PAGE)
        cache["block_table"] = jnp.asarray(table, jnp.int32)  # donated
        prefill = donating_jit(model.prefill_chunk)
        logits, cache, _, _ = prefill(params, cache, tokens, valid=valid)
        if tok0 is None:
            tok0 = jnp.argmax(logits, -1).astype(jnp.int32)[:, None]
        decode = donating_jit(model.decode_multi,
                              static_argnames=("k", "eos_id")).lower(
            params, cache, tok0, active, budget, k=K).compile()
        if use_kernels and require_custom_call:
            check("tpu_custom_call" in decode.as_text(),
                  "the kernels-on decode program holds no tpu_custom_call: "
                  "the Pallas kernels did not compile for the chip")
            print("  kernels-on decode program holds tpu_custom_call: yes",
                  flush=True)
        toks, cache, probs, _ = decode(params, cache, tok0, active, budget)
        out[use_kernels] = (np.asarray(logits), np.asarray(toks),
                            np.asarray(probs))
        del cache

    (l_off, t_off, p_off), (l_on, t_on, p_on) = out[False], out[True]
    check(np.all(np.isfinite(l_on)) and np.all(np.isfinite(p_on)),
          "non-finite kernels-on logits or posteriors")
    logit_err = float(np.abs(l_on - l_off).max())
    logit_scale = float(np.abs(l_off).max())
    # step t's posterior depends on the tokens fed at steps < t: compare
    # a row's step only while both runs fed it the same tokens
    same = np.cumprod(np.concatenate(
        [np.ones((BATCH, 1), bool), t_on[:, :-1] == t_off[:, :-1]], 1), 1)
    post_err = float((np.abs(p_on - p_off).max(-1) * same).max())
    print(f"  prefill logits: max |on-off| {logit_err:.4e} "
          f"({logit_err / logit_scale:.3e} of max |logit| {logit_scale:.3f}); "
          f"rms ratio {np.sqrt(np.mean((l_on - l_off) ** 2) / np.mean(l_off ** 2)):.3e}",
          flush=True)
    print(f"  probe posteriors: max |on-off| {post_err:.4e} over "
          f"{int(same.sum())}/{same.size} (row, step) pairs fed equal "
          f"tokens; sampled tokens equal: {int((t_on == t_off).sum())}/"
          f"{t_on.size}", flush=True)
    check(logit_err <= LOGIT_REL_TOL * logit_scale,
          f"kernels-on logits differ by {logit_err:.4e} > "
          f"{LOGIT_REL_TOL:g} * {logit_scale:.3f}")
    check(post_err <= POSTERIOR_TOL,
          f"kernels-on probe posteriors differ by {post_err:.4e} > "
          f"{POSTERIOR_TOL:g}")
    return {"logit_err": logit_err, "logit_scale": logit_scale,
            "posterior_err": post_err}


# ---------------------------------------------------------------------------
# (d) the serving engine
# ---------------------------------------------------------------------------

def make_traffic(cfg, seed: int) -> list[tuple[list[int], int]]:
    """N_REQUESTS seeded (prompt, output length) pairs.

    Prompts of 64..512 tokens, outputs of 16..128. The WAVE longest
    prompts come first: they arrive together and fill the memory budget.
    """
    import numpy as np
    rng = np.random.default_rng(seed + 2)
    plens = rng.integers(64, 513, size=N_REQUESTS)
    olens = rng.integers(16, 129, size=N_REQUESTS)
    order = np.argsort(-plens, kind="stable")
    return [(rng.integers(1, cfg.vocab_size, int(plens[i])).tolist(),
             int(olens[i])) for i in order]


def mem_budget_for(cfg, traffic) -> int:
    """KV budget that forces preemption.

    It holds the first wave at admission plus one page, and never less
    than the largest single request. The first wave's decode growth, or
    any later arrival that outranks it, then has to displace a running
    request.
    """
    from repro.serving.kv_cache import page_bytes, paged_bytes_for_context
    first = sum(paged_bytes_for_context(cfg, len(p) + K, PAGE)
                for p, _ in traffic[:WAVE])
    largest = max(paged_bytes_for_context(cfg, len(p) + n + K, PAGE)
                  for p, n in traffic)
    return max(first + page_bytes(cfg, PAGE), largest)


def serve_phase(cfg, params, traffic, use_kernels: bool) -> dict:
    """Serve ``traffic`` through the real engine; check what came out.

    The engine settings are ``run_policy(mode="real", policy="trail",
    kv_layout="paged", page_size=16, max_len=1024, max_batch=16,
    probe_interval=4)`` plus the memory budget of `mem_budget_for`. The
    engine is driven through ``submit``/``step`` so that arrivals are
    staggered by engine iterations (the clock is the same on any
    device): the first wave at the start, the second after the first
    iteration, the third once a request has finished.
    """
    import jax

    from repro.models.model import build_model
    from repro.serving.engine import Engine, EngineConfig
    from repro.serving.predictors import ProbePredictor
    from repro.serving.request import Request

    model = build_model(cfg, use_kernels=use_kernels)
    predictor = ProbePredictor(cfg.probe, probe_params=params["probe"],
                               embed_table=params["embed"])
    eng = Engine(cfg, EngineConfig(
        policy="trail", mode="real", kv_layout="paged", page_size=PAGE,
        max_len=MAX_LEN, max_batch=BATCH, probe_interval=K,
        mem_budget=mem_budget_for(cfg, traffic)),
        predictor=predictor, model=model, params=params)
    waves = [traffic[i:i + WAVE] for i in range(0, len(traffic), WAVE)]
    reqs: list[Request] = []
    steps = finished = 0
    t0 = time.perf_counter()
    while waves or eng.has_work():
        w = len(reqs) // WAVE            # index of the next wave
        if waves and steps >= w and finished >= w - 1:
            for prompt, n in waves.pop(0):
                r = Request(rid=len(reqs), arrival=eng.now,
                            prompt=list(prompt), max_new_tokens=n,
                            true_out_len=n)
                reqs.append(r)
                eng.submit(r)
        finished += len(eng.step().completed)
        steps += 1
    jax.block_until_ready(eng.pool.cache)
    wall = time.perf_counter() - t0

    stats = eng.stats
    done = [r for r in reqs if r.done and len(r.generated) == r.true_out_len]
    bad_tokens = [t for r in reqs for t in r.generated
                  if not 0 <= t < cfg.vocab_size]
    leaked = eng.pool.blocks.used_pages()
    label = "on " if use_kernels else "off"
    print(f"  kernels {label}: {len(done)}/{len(reqs)} requests finished "
          f"their budgets, {stats.n_preemptions} preemptions, "
          f"{stats.recomputed_tokens} recomputed tokens, "
          f"{stats.iterations} iterations, leaked pages {leaked}; "
          f"cold smoke wall time incl. compilation {wall:.1f} s", flush=True)
    check(len(reqs) == N_REQUESTS and len(done) == N_REQUESTS,
          f"only {len(done)} of {N_REQUESTS} requests finished their budget")
    check(not bad_tokens, f"{len(bad_tokens)} token ids outside the "
                          f"vocabulary, e.g. {bad_tokens[:3]}")
    check(stats.n_preemptions >= 1, "the traffic caused no preemption")
    check(leaked == 0, f"{leaked} KV pages still in use after the run")
    return {"preemptions": stats.n_preemptions, "wall_s": wall,
            "tokens": [list(r.generated) for r in reqs]}


# ---------------------------------------------------------------------------

def main(argv=None) -> int:
    """Run phases (a)-(e); return the exit code."""
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)
    try:
        import jax

        from repro.launch.compile_cache import use_compile_cache
        from repro.models.model import build_model
    except ImportError as e:
        print(f"chip_smoke: {e}; run it from a checkout of the repository",
              file=sys.stderr)
        return 2
    dev = jax.devices()[0]
    if dev.platform != "tpu":
        print(f"chip_smoke: no TPU found (JAX's first device is "
              f"{dev.platform!r}); nothing was run", file=sys.stderr)
        return 1
    # an undonatable cache buffer means XLA copies the KV cache every
    # megastep: on the chip that is a failure, not a warning
    warnings.filterwarnings("error",
                            message="Some donated buffers were not usable")
    cache_dir = use_compile_cache()
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(jax.devices())}
    try:
        print(f"(a) device {json.dumps(device)}; compile cache {cache_dir}",
              flush=True)
        cfg = cut_config()
        print(f"    model {json.dumps(describe(cfg))}", flush=True)

        print("(b) paged kernels (interpret=False) against ref.py", flush=True)
        kernel_phase(cfg, args.seed)

        # jitted, so that each weight's float32 draw fuses with its cast
        params = jax.jit(build_model(cfg).init)(jax.random.key(args.seed))
        print("(c) prefill_chunk + decode_multi(k=4), kernels on vs off",
              flush=True)
        model_phase(cfg, params, args.seed, require_custom_call=True)

        print("(d) engine: 12 requests, TRAIL, paged KV", flush=True)
        traffic = make_traffic(cfg, args.seed)
        runs = [serve_phase(cfg, params, traffic, use_kernels=uk)
                for uk in (False, True)]
        same = sum(a == b for a, b in zip(runs[0]["tokens"],
                                          runs[1]["tokens"]))
        peak = (dev.memory_stats() or {}).get("peak_bytes_in_use")
        print(f"    token streams identical across the two runs: "
              f"{same}/{N_REQUESTS}; peak_bytes_in_use {peak}", flush=True)
    except Exception:   # noqa: BLE001 — every failure is reported, exit 1
        traceback.print_exc()
        print("chip_smoke: FAILED", file=sys.stderr)
        return 1
    print(json.dumps({"ok": True, "device": device}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
