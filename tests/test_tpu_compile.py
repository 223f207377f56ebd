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
import re

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


def _granite(layers: int):
    """granite-3-8b at published widths, cut to ``layers`` layers, bf16
    weights, the probe tapped at the last layer (one run of layers)."""
    full = get_config("granite-3-8b")
    return dataclasses.replace(full, num_layers=layers,
                               layer_kinds=full.layer_kinds[:layers],
                               param_dtype="bfloat16",
                               probe=dataclasses.replace(
                                   full.probe, tap_layer=layers - 1))


def _shapes(one_chip, model):
    """Params and a paged cache of B rows x PMAX pages, as placed shapes."""
    def place(tree):
        return jax.tree.map(
            lambda a: _spec(one_chip, a.shape, a.dtype), tree)

    params = place(jax.eval_shape(model.init, jax.random.key(0)))
    cache = place(jax.eval_shape(
        lambda: model.init_cache(B, PMAX * PS, kv_layout="paged",
                                 page_size=PS)))
    return params, cache


def test_decode_multi_compiles_with_kernels(one_chip, monkeypatch):
    """A decode megastep of a 2-layer granite-width model with
    use_kernels=True: the paged kernel compiles inside the scanned step."""
    monkeypatch.setattr(ops, "_interpret_default", lambda: False)
    model = Model(_granite(2), use_kernels=True)
    params, cache = _shapes(one_chip, model)
    step = jax.jit(model.decode_multi, donate_argnames=("cache",),
                   static_argnames=("k",))
    compiled = step.lower(params, cache, _spec(one_chip, (B, 1), jnp.int32),
                          _spec(one_chip, (B,), bool),
                          _spec(one_chip, (B,), jnp.int32), k=4).compile()
    assert "tpu_custom_call" in compiled.as_text()
    # 2 layers of bf16 weights + the page pool fit one 16 GB chip
    mem = compiled.memory_analysis()
    assert mem.argument_size_in_bytes + mem.temp_size_in_bytes < 16e9


_INSTR = re.compile(r"^\s*(?:ROOT )?%([\w.\-]+) = bf16\[([\d,]*)\]\S* "
                    r"([\w\-]+)\(")


def _scatter_computations(hlo: str) -> set[str]:
    """Names of the computations whose root instruction is a scatter."""
    names, current = set(), None
    for line in hlo.splitlines():
        head = re.match(r"^(?:ENTRY )?%?([\w.\-]+) .*\{$", line)
        if head:
            current = head.group(1)
        elif line.lstrip().startswith("ROOT ") and " scatter(" in line:
            names.add(current)
    return names


def _pool_rewrites(hlo: str, pool_pages: int) -> list[str]:
    """Instructions that copy, slice or rewrite a whole page pool.

    A bf16 result shaped like one layer's pool, (P, PS, KH, HD) or its
    flat (P * PS, KH, HD), with or without a leading layer axis, made by
    a copy, a dynamic slice, a dynamic update slice, or a fusion whose
    root is anything but a scatter. A scatter fusion of that shape writes
    its few tokens into the carried pool in place."""
    scatters = _scatter_computations(hlo)
    pool = ((pool_pages, PS, KH, HD), (pool_pages * PS, KH, HD))
    found = []
    for line in hlo.splitlines():
        m = _INSTR.match(line)
        if not m or m.group(3) not in ("copy", "fusion", "dynamic-slice",
                                       "dynamic-update-slice"):
            continue
        dims = tuple(int(d) for d in m.group(2).split(",") if d)
        if not any(dims[-len(p):] == p and len(dims) - len(p) <= 1
                   for p in pool):
            continue
        called = re.search(r"calls=%([\w.\-]+)", line)
        if m.group(3) == "fusion" and called and called.group(1) in scatters:
            continue
        found.append(f"{m.group(3)} %{m.group(1)} {dims}")
    return found


@pytest.mark.parametrize("program", ["decode_multi", "prefill_chunk"])
def test_model_step_leaves_page_pool_in_place(one_chip, program):
    """The layer scan carries the stacked page pool: a decode megastep
    (k = 4) and an 8-wide prefill chunk of a 3-layer granite-width model
    read the pages each row maps and write only the new tokens. No
    instruction copies, slices or rewrites a whole layer's or run's pool,
    and the program's scratch memory stays below one run's K/V pool."""
    model = Model(_granite(3))
    params, cache = _shapes(one_chip, model)
    if program == "decode_multi":
        step = jax.jit(model.decode_multi, donate_argnames=("cache",),
                       static_argnames=("k",))
        lowered = step.lower(params, cache,
                             _spec(one_chip, (B, 1), jnp.int32),
                             _spec(one_chip, (B,), bool),
                             _spec(one_chip, (B,), jnp.int32), k=4)
    else:
        step = jax.jit(model.prefill_chunk, donate_argnames=("cache",))
        lowered = step.lower(params, cache,
                             _spec(one_chip, (B, 8), jnp.int32),
                             _spec(one_chip, (B, 8), bool))
    compiled = lowered.compile()
    run = cache["run_0"][0]
    assert run["pk"].shape == (3, 1 + B * PMAX, PS, KH, HD)
    assert _pool_rewrites(compiled.as_text(), 1 + B * PMAX) == []
    run_pool_bytes = sum(run[k].size * run[k].dtype.itemsize
                         for k in ("pk", "pv"))
    assert compiled.memory_analysis().temp_size_in_bytes < run_pool_bytes
