"""Production mesh construction (TPU v5e pods).

Single pod: (data=16, model=16) = 256 chips. Multi-pod: (pod=2, data=16,
model=16) = 512 chips — the pod axis is pure data parallelism (gradient
all-reduce crosses the inter-pod DCN/ICI boundary; everything else stays
within a pod).

Functions, not module-level constants: importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS before first jax init).
"""

from __future__ import annotations

import jax

SINGLE_POD = (16, 16)
MULTI_POD = (2, 16, 16)


def make_production_mesh(*, multi_pod: bool = False):
    """The (data, model) single-pod or (pod, data, model) multi-pod mesh."""
    shape = MULTI_POD if multi_pod else SINGLE_POD
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    n = 1
    for s in shape:
        n *= s
    devices = jax.devices()
    if len(devices) < n:
        raise RuntimeError(
            f"need {n} devices for mesh {shape}, have {len(devices)}; "
            "run under XLA_FLAGS=--xla_force_host_platform_device_count=512")
    return jax.make_mesh(
        shape, axes, devices=devices[:n],
        axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_host_mesh():
    """A trivial 1x1 mesh for single-device smoke runs."""
    return jax.make_mesh(
        (1, 1), ("data", "model"), devices=jax.devices()[:1],
        axis_types=(jax.sharding.AxisType.Auto,) * 2)


def data_axes(mesh) -> tuple[str, ...]:
    """The batch-sharding axes for this mesh ((pod,data) or (data,))."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def n_chips(mesh) -> int:
    """Total device count of a mesh."""
    return mesh.devices.size
