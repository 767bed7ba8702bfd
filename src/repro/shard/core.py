"""Mesh construction + param/pool sharding for the serving path."""

from __future__ import annotations

import functools
from typing import Optional

import jax

from repro.launch.mesh import _make_mesh
from repro.runtime.sharding import named, param_specs, pool_specs


def validate_mesh_config(mesh_cfg) -> None:
    """Static sanity checks on a ``MeshConfig`` (no jax device access)."""
    if mesh_cfg.tp < 1 or mesh_cfg.dp < 1:
        raise ValueError(f"mesh axes must be >= 1, got dp={mesh_cfg.dp} "
                         f"tp={mesh_cfg.tp}")
    if len(mesh_cfg.axes) != 2 or len(set(mesh_cfg.axes)) != 2:
        raise ValueError(f"mesh axes must be two distinct names, got "
                         f"{mesh_cfg.axes!r}")


def build_mesh(mesh_cfg) -> Optional[jax.sharding.Mesh]:
    """``MeshConfig`` -> live mesh, or None when sharding is off.

    ``enable=True`` at ``tp=1`` builds a genuine 1x1 mesh: the whole
    sharded path (committed params, pool shardings, trace-time
    constraints) runs with every axis size 1 — the bitwise-equality
    configuration the tests pin against the unsharded engine.
    """
    if mesh_cfg is None or not mesh_cfg.enabled:
        return None
    validate_mesh_config(mesh_cfg)
    # the sharding rules key on the literal axis names "data"/"model";
    # MeshConfig defaults to those and validate() in api/config warns off
    # renames that would silently disable TP
    return _make_mesh((mesh_cfg.dp, mesh_cfg.tp), tuple(mesh_cfg.axes))


def mesh_axis_size(mesh, axis: str) -> int:
    return mesh.shape.get(axis, 1) if mesh is not None else 1


def shard_params(params, mesh, cfg):
    """Commit the weights to their TP layout (Megatron rules, no FSDP).

    ``device_put`` with a NamedSharding makes every leaf *committed*:
    downstream pjit calls see the layout as an input constraint instead
    of re-deciding it per dispatch, which is what keeps decode a single
    stable program.  Serving shards pure-TP (``fsdp=False``) — weights
    are read-only, so ZeRO-style data-axis sharding would only add
    per-step all-gathers.
    """
    specs = param_specs(params, mesh, cfg, fsdp=False)
    return jax.device_put(params, named(specs, mesh))


def init_sharded_params(cfg, key, mesh=None):
    """Initialise parameters in one jitted program.  Under a mesh the
    program's outputs carry the TP layout (``out_shardings``), so each
    device builds only its own shards: a model that needs several chips is
    never materialised whole on one of them."""
    from repro.models import init_params  # lazy: models import the kernels

    init = functools.partial(init_params, cfg)
    if mesh is None:
        return jax.jit(init)(key)
    specs = param_specs(jax.eval_shape(init, key), mesh, cfg, fsdp=False)
    return jax.jit(init, out_shardings=named(specs, mesh))(key)


def pool_shardings(cache_shapes_tree, mesh, n_kv_heads: int):
    """NamedShardings for a paged pool tree (see ``sharding.pool_specs``)."""
    return named(pool_specs(cache_shapes_tree, mesh, n_kv_heads), mesh)
