"""Production mesh construction (TPU v5e pods).

A FUNCTION, not a module-level constant — importing this module must never
touch jax device state (the dry-run sets XLA_FLAGS first).
"""
from __future__ import annotations

import jax
from jax.sharding import AxisType


def make_production_mesh(*, multi_pod: bool = False):
    """Single pod: (data=16, model=16) = 256 chips.
    Multi-pod:  (pod=2, data=16, model=16) = 512 chips."""
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    # Auto axes: the model code pins activations with
    # with_sharding_constraint, which refuses make_mesh's default
    # Explicit axes
    return jax.make_mesh(shape, axes, axis_types=(AxisType.Auto,) * len(axes))


def make_serving_mesh(*, model: int, data: int = 1):
    """Tensor-parallel serving mesh: (data, model) over the first
    data*model local devices — forced host CPU devices in CI
    (XLA_FLAGS=--xla_force_host_platform_device_count=N), chips on TPU.
    Unlike make_production_mesh this takes whatever subset of the local
    devices the shape asks for, so a 4-way mesh and a 2-way mesh can be
    built in one process (the sharding-equivalence harness does)."""
    import numpy as np
    from jax.sharding import Mesh

    n = data * model
    devs = jax.devices()
    if len(devs) < n:
        raise ValueError(
            f"serving mesh ({data}, {model}) needs {n} devices, have "
            f"{len(devs)}; set XLA_FLAGS="
            f"--xla_force_host_platform_device_count={n} before jax init")
    return Mesh(np.asarray(devs[:n]).reshape(data, model), ("data", "model"))


def batch_axes(mesh) -> tuple:
    """Axes over which the global batch is sharded."""
    return ("pod", "data") if "pod" in mesh.axis_names else ("data",)


def batch_ways(mesh) -> int:
    import math
    return math.prod(mesh.shape[a] for a in batch_axes(mesh))
