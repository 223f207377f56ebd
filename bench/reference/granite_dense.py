"""Plain float32 reference of a Granite 3.0 dense decoder.

The published architecture (GraniteForCausalLM): token embedding, a
stack of pre-norm blocks (RMSNorm, grouped-query attention with rotary
positions, residual; RMSNorm, SiLU-gated MLP, residual), a final
RMSNorm and the tied embedding as output head. Written in straight
``jax.numpy`` at float32 and ``Precision.HIGHEST``, with no cache, no
batching and no kernels; it imports nothing of the program.

Departures from the published model, shared with the program and listed
in each configuration file under ``departures``: no embedding,
attention or residual multipliers and no logits scaling; attention
scores are scaled by ``head_dim ** -0.5``; RMSNorm gains are stored as
``1 + scale``. None of these changes a shape or a cost.

The weights are read from the tree that ``bench/weights.py`` makes,
which is laid out as the program's ``init`` lays it out: ``embed``,
``final_norm.scale``, ``probe.{w1,b1,w2,b2}`` and ``layers``, a tuple
of runs of stacked blocks (``norm1``, ``attn.{wq,wk,wv,wo}``,
``norm2``, ``mlp.{w_gate,w_up,w_down}``). Leaves are cast to float32
one layer at a time.

``quant="int8"`` is the benchmark's control: every matrix product takes
its weight rounded to int8 with one scale per output channel and its
activation rounded to int8 with one scale per row, the W8A8 step below
the bfloat16 the configurations state.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

HI = jax.lax.Precision.HIGHEST


def _q8(x, axis):
    """Round ``x`` to int8 levels with one absmax scale along ``axis``."""
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-30) / 127
    return jnp.round(x / s) * s


def matmul(x, w, quant):
    """``x @ w`` in float32; W8A8-rounded operands under ``quant``."""
    w = w.astype(jnp.float32)
    if quant == "int8":
        x, w = _q8(x, -1), _q8(w, 0)
    return jnp.matmul(x, w, precision=HI)


def rms_norm(x, scale, eps):
    """RMSNorm with gain ``1 + scale``."""
    var = jnp.mean(jnp.square(x), -1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * (1.0 + scale.astype(jnp.float32))


def rope(x, theta):
    """Rotary positions 0..S-1 on x (S, H, hd), rotate-half convention."""
    S, _, hd = x.shape
    half = hd // 2
    freq = theta ** (-jnp.arange(half, dtype=jnp.float32) / half)
    ang = jnp.arange(S, dtype=jnp.float32)[:, None] * freq
    cos, sin = jnp.cos(ang)[:, None], jnp.sin(ang)[:, None]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def block(c, p, x, quant):
    """One decoder block on x (S, d)."""
    S = x.shape[0]
    H, KH, hd = c["num_attention_heads"], c["num_key_value_heads"], c["head_dim"]
    h = rms_norm(x, p["norm1"]["scale"], c["rms_norm_eps"])
    a = p["attn"]
    q = rope(matmul(h, a["wq"], quant).reshape(S, H, hd), c["rope_theta"])
    k = rope(matmul(h, a["wk"], quant).reshape(S, KH, hd), c["rope_theta"])
    v = matmul(h, a["wv"], quant).reshape(S, KH, hd)
    q = q.reshape(S, KH, H // KH, hd)
    s = jnp.einsum("skgh,tkh->kgst", q, k, precision=HI) * hd ** -0.5
    causal = jnp.arange(S)[:, None] >= jnp.arange(S)[None, :]
    s = jnp.where(causal, s, -jnp.inf)
    o = jnp.einsum("kgst,tkh->skgh", jax.nn.softmax(s, -1), v, precision=HI)
    x = x + matmul(o.reshape(S, H * hd), a["wo"], quant)
    h = rms_norm(x, p["norm2"]["scale"], c["rms_norm_eps"])
    m = p["mlp"]
    g = jax.nn.silu(matmul(h, m["w_gate"], quant)) * matmul(h, m["w_up"], quant)
    return x + matmul(g, m["w_down"], quant)


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def _forward(params, tokens, *, c, quant):
    c = dict(c)
    x = params["embed"][tokens].astype(jnp.float32)
    taps = []
    for run in params["layers"]:
        def body(x, blk):
            for sub in blk:
                x = block(c, sub, x, quant)
            return x, x
        x, hs = jax.lax.scan(body, x, run)
        taps.append(hs)
    tap = jnp.concatenate(taps)[c["probe_tap_layer"]]
    h = rms_norm(x, params["final_norm"]["scale"], c["rms_norm_eps"])
    logits = matmul(h, params["embed"].T, quant)
    pr = params["probe"]
    ph = jax.nn.relu(jnp.matmul(tap, pr["w1"], precision=HI) + pr["b1"])
    post = jax.nn.softmax(jnp.matmul(ph, pr["w2"], precision=HI) + pr["b2"], -1)
    return logits, post


@functools.partial(jax.jit, static_argnames=("c", "quant"))
def row_readings(params, tokens, targets, *, c, quant=None):
    """Readings of one row of ``tokens`` (S,).

    Returns the gap by which ``targets[s]`` lies below the best logit at
    each position ``s``, the argmax at each position, and the probe's
    posterior at each position.
    """
    logits, post = _forward(params, tokens, c=c, quant=quant)
    best = jnp.max(logits, -1)
    got = jnp.take_along_axis(logits, targets[:, None], -1)[:, 0]
    return best - got, jnp.argmax(logits, -1), post


def settings(config: dict) -> tuple:
    """The hashable settings ``row_readings`` takes from a config file."""
    keys = ("num_attention_heads", "num_key_value_heads", "rms_norm_eps",
            "rope_theta")
    c = {k: config[k] for k in keys}
    c["head_dim"] = config["hidden_size"] // config["num_attention_heads"]
    c["probe_tap_layer"] = config["program"]["probe"]["tap_layer"]
    return tuple(sorted(c.items()))
