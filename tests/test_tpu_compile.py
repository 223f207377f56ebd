"""Compile the served path's Pallas kernels for a described TPU v5e.

Nothing runs: the chip's compiler, which is installed here, compiles for
a v5e that is described and not attached. It refuses what interpret mode
accepts — blocks that break the (8, 128) tiling, scalar reads from vector
memory, more VMEM than a kernel may use — so these tests guard the
kernels at granite-3-8b widths without a chip.

The topology is described inside a fixture, never at import: only one
process at a time may load the TPU library, and every test worker
imports this file.
"""

import dataclasses
import os

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from repro.config import get_config
from repro.kernels import ops
from repro.kernels.paged_decode_attention import (
    paged_decode_attention, paged_decode_attention_multi)
from repro.models.model import Model

# granite-3-8b decode shapes: 16 sequences of up to 1024 tokens in pages
# of 16, 8 KV heads of 4 query heads each, head_dim 128, bf16 KV
B, KH, G, HD, PS, PMAX = 16, 8, 4, 128, 16, 64


@pytest.fixture(scope="module")
def one_chip():
    """One chip of a described v5e:2x2, with the persistent cache off.

    A compile for a described device is written to the persistent cache
    but cannot be read back without the chip, so the cache stays off
    while this module compiles.
    """
    from jax.experimental import topologies
    from jax.experimental.compilation_cache import compilation_cache
    # the TPU library would otherwise log under /tmp when it loads
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 — any failure means no compiler
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield SingleDeviceSharding(topo.devices[0])
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


def _spec(sharding, shape, dtype):
    return jax.ShapeDtypeStruct(shape, dtype, sharding=sharding)


@pytest.mark.parametrize("T", [1, 4, 256])
def test_paged_kernel_compiles(one_chip, T):
    """T=1 is the decode step, T=4 a megastep's worth, T=256 the largest
    prefill chunk (EngineConfig.prefill_chunk)."""
    P = 1 + B * PMAX
    pool = (_spec(one_chip, (P, PS, KH, HD), jnp.bfloat16),
            _spec(one_chip, (P, PS, KH, HD), jnp.bfloat16),
            _spec(one_chip, (P, PS), jnp.int32),
            _spec(one_chip, (B, PMAX), jnp.int32))
    if T == 1:
        fn = paged_decode_attention
        q = _spec(one_chip, (B, KH * G, HD), jnp.bfloat16)
        q_pos = _spec(one_chip, (B,), jnp.int32)
    else:
        fn = paged_decode_attention_multi
        q = _spec(one_chip, (B, T, KH * G, HD), jnp.bfloat16)
        q_pos = _spec(one_chip, (B, T), jnp.int32)
    compiled = jax.jit(lambda *a: fn(*a, interpret=False)).lower(
        q, *pool, q_pos).compile()
    assert "tpu_custom_call" in compiled.as_text()


def test_decode_multi_compiles_with_kernels(one_chip, monkeypatch):
    """A decode megastep of a 2-layer granite-width model with
    use_kernels=True: the paged kernel compiles inside the scanned step."""
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    full = get_config("granite-3-8b")
    cfg = dataclasses.replace(full, num_layers=2,
                              layer_kinds=full.layer_kinds[:2],
                              param_dtype="bfloat16",
                              probe=dataclasses.replace(full.probe,
                                                        tap_layer=1))
    model = Model(cfg, use_kernels=True)

    def place(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(model.init, jax.random.key(0)))
    cache = place(jax.eval_shape(
        lambda: model.init_cache(B, PMAX * PS, kv_layout="paged",
                                 page_size=PS)))
    step = jax.jit(model.decode_multi, donate_argnames=("cache",),
                   static_argnames=("k",))
    compiled = step.lower(params, cache, _spec(one_chip, (B, 1), jnp.int32),
                          _spec(one_chip, (B,), bool),
                          _spec(one_chip, (B,), jnp.int32), k=4).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # 2 layers of bf16 weights + the page pool fit one 16 GB chip
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9
