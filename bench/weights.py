"""Seeded random weights, made on the device in one jitted call.

The benchmark makes the weights itself, so that the reference can make
the same ones from the same seed without taking anything the program
made. The tree is laid out as the program's ``init`` lays it out (read
with ``jax.eval_shape``, which runs nothing), and each leaf is drawn by
its name:

- ``embed`` / ``unembed``: normal, std 0.02;
- norm scales and biases: zeros (RMSNorm gains are stored as
  ``1 + scale``);
- every other leaf: normal, std ``fan_in ** -0.5``, where ``fan_in`` is
  the leaf's second-to-last dimension.

Each leaf's key is the seed's key folded with the leaf's index in the
flattened tree, so a leaf does not change when another is added.
"""

from __future__ import annotations

import hashlib

import jax
import jax.numpy as jnp

ZERO_LEAVES = {"scale", "bias", "b1", "b2", "bq", "bk", "bv", "b_up", "b_down"}
EMBED_LEAVES = {"embed", "unembed"}


def seed32(seed: int, what: str) -> int:
    """A 32-bit seed for ``what``, derived from a seed of any size."""
    h = hashlib.sha256(f"{seed}:{what}".encode()).digest()
    return int.from_bytes(h[:4], "little")


def leaf_name(path) -> str:
    """The last dict key on a tree path."""
    for k in reversed(path):
        if isinstance(k, jax.tree_util.DictKey):
            return str(k.key)
    return ""


def make_params(shapes, seed: int):
    """Weights shaped like ``shapes`` (a tree of ShapeDtypeStruct).

    One jitted call; every leaf is drawn in float32 and cast to its
    dtype inside the call, so the float32 draw never reaches memory.
    """
    leaves, treedef = jax.tree_util.tree_flatten_with_path(shapes)

    def build(key):
        out = []
        for i, (path, s) in enumerate(leaves):
            name = leaf_name(path)
            if name in ZERO_LEAVES or len(s.shape) < 2:
                out.append(jnp.zeros(s.shape, s.dtype))
                continue
            std = 0.02 if name in EMBED_LEAVES else s.shape[-2] ** -0.5
            x = jax.random.normal(jax.random.fold_in(key, i), s.shape,
                                  jnp.float32)
            out.append((x * std).astype(s.dtype))
        return jax.tree_util.tree_unflatten(treedef, out)

    key = jax.random.key(seed32(seed, "weights"))
    return jax.jit(build)(key)
