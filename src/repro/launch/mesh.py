"""Production meshes.

Single pod: 16 x 16 = 256 chips -> ("data", "model").
Multi-pod:  2 x 16 x 16 = 512 chips -> ("pod", "data", "model"); the
"pod" axis carries pure data parallelism (gradient all-reduce over DCN),
"model" stays inside a pod's ICI domain — the standard multi-pod layout.

Defined as FUNCTIONS so importing this module never touches jax device
state (the dry-run must set XLA_FLAGS before any jax initialization).
"""

from __future__ import annotations

import math

import jax


def _make_mesh(shape, axes):
    need = math.prod(shape)
    have = jax.device_count()
    if need > have:
        raise ValueError(
            f"mesh shape {dict(zip(axes, shape))} needs {need} devices but "
            f"only {have} are visible; on CPU, export "
            f"XLA_FLAGS=--xla_force_host_platform_device_count={need} "
            "BEFORE jax initializes (or shrink the mesh)")
    # Auto axes: the sharding rules are PartitionSpec constraints for the
    # partitioner to propagate (jax.make_mesh defaults to Explicit axes).
    return jax.make_mesh(
        shape, axes, axis_types=(jax.sharding.AxisType.Auto,) * len(axes))


def make_production_mesh(*, multi_pod: bool = False):
    shape = (2, 16, 16) if multi_pod else (16, 16)
    axes = ("pod", "data", "model") if multi_pod else ("data", "model")
    return _make_mesh(shape, axes)


def make_host_mesh(data: int = 1, model: int = 1):
    """Small mesh over however many (host) devices exist — tests/examples."""
    return _make_mesh((data, model), ("data", "model"))
