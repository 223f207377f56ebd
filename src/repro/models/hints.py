"""Sharding hints: mesh-aware ``with_sharding_constraint`` that degrades to a
no-op outside a mesh context (smoke tests, single-device runs).

GSPMD's propagation leaves the big attention intermediates
(scores/accumulators, (B,KH,G,S,M)-shaped) replicated over the "model" axis,
which blows past HBM at train_4k/prefill_32k scale. Queries are independent
in attention, so we shard the *query-sequence* dim over "model" — softmax
rows stay device-local, no extra collectives inside the loop. MoE expert
buffers shard over "model" (expert parallelism).

The special token ``BATCH`` resolves to ("pod","data") or ("data",)
depending on the ambient mesh. Axes that do not divide the dim are dropped.
"""

from __future__ import annotations

import jax
from jax.sharding import PartitionSpec as P

BATCH = "__batch__"


def data_shards() -> int:
    """Number of batch-sharding ways in the ambient mesh (1 outside jit)."""
    sizes = jax.sharding.get_abstract_mesh().shape
    n = 1
    for a in ("pod", "data"):
        n *= sizes.get(a, 1)
    return n


def hint(x, *spec):
    """Annotate ``x`` with a sharding hint when a mesh is active."""
    sizes = jax.sharding.get_abstract_mesh().shape    # {} outside a mesh
    if not sizes:
        return x
    names = tuple(sizes)
    full = tuple(spec) + (None,) * (x.ndim - len(spec))
    out = []
    for dim, ax in zip(x.shape, full):
        if ax == BATCH:
            ax = ("pod", "data") if "pod" in names else ("data",)
        if ax is None:
            out.append(None)
            continue
        axes = ax if isinstance(ax, tuple) else (ax,)
        if not all(a in names for a in axes):
            out.append(None)
            continue
        size = 1
        for a in axes:
            size *= sizes[a]
        out.append(ax if (dim >= size and dim % size == 0) else None)
    return jax.lax.with_sharding_constraint(x, P(*out))
