"""Sharded serving: thread a device mesh through the engine.

The seed shipped the three ingredients — ``runtime/sharding.py`` (the
Megatron DP/TP/EP PartitionSpec rules), ``launch/mesh.py`` (mesh
factories) and ``runtime/collectives.py`` — without wiring any of them
into the request path.  This package is that wiring:

* ``build_mesh(MeshConfig)`` turns the runtime config into a live
  ``jax.sharding.Mesh`` (or ``None`` when sharding is off);
* ``shard_params`` resolves the per-arch param specs into
  ``NamedSharding``s and commits the weights (``jax.device_put``) at
  ``LLM`` init — serving uses pure TP (``fsdp=False``): there is no
  optimizer step to amortize a ZeRO all-gather against;
* ``pool_shardings`` does the same for the paged KV pool (heads over
  the "model" axis, block tables replicated so the host-side
  ``PageManager`` stays the one source of truth);
* ``make_host_mesh`` (re-exported, now device-count-validated) is the
  test/CI factory — CPU runs force devices with
  ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.

Correctness contract (test-asserted in ``tests/test_shard.py``): at
``tp=1`` the mesh adds size-1 axes only, every constraint is trivial and
greedy outputs are **bitwise identical** to the unsharded engine; at
``tp>1`` the row-parallel reductions change accumulation order, so
outputs are allclose (and greedy token streams are compared for parity,
not logits for equality).
"""

from repro.launch.mesh import make_host_mesh, make_production_mesh
from repro.shard.core import (
    build_mesh,
    init_sharded_params,
    mesh_axis_size,
    pool_shardings,
    shard_params,
    validate_mesh_config,
)
from repro.shard.memory import describe_mesh, tree_device_bytes

__all__ = [
    "build_mesh",
    "describe_mesh",
    "init_sharded_params",
    "make_host_mesh",
    "make_production_mesh",
    "mesh_axis_size",
    "pool_shardings",
    "shard_params",
    "tree_device_bytes",
    "validate_mesh_config",
]
