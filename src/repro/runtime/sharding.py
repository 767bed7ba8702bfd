"""Sharding rules: DP / TP / EP / ZeRO-1 PartitionSpecs for every pytree.

Megatron-style tensor parallelism over the "model" axis:

* embeddings / unembedding     -> vocab-sharded
* attention q/k/v projections  -> output (head) dim sharded; wo row-sharded
* MLP in projections           -> column-sharded; down/out row-sharded
* MoE experts                  -> expert-parallel over "model" when the
  expert count divides the axis, otherwise TP inside each expert
* recurrent cells              -> state width sharded

Data parallelism over ("pod", "data") — the "pod" axis only ever carries
pure DP, which is what makes the multi-pod mesh trivially correct.
Divisibility is checked leaf-by-leaf; anything unshardable is replicated
(never an error — the dry-run must pass for every cell).

ZeRO-1 (`zero1_specs`): optimizer moments additionally shard their largest
replicated dim over "data".
"""

from __future__ import annotations

import jax
from jax.sharding import NamedSharding
from jax.sharding import PartitionSpec as P

from repro.configs.base import ModelConfig


def data_axes(mesh) -> tuple:
    return tuple(a for a in mesh.axis_names if a in ("pod", "data"))


def _axis_size(mesh, names) -> int:
    size = 1
    for n in names if isinstance(names, tuple) else (names,):
        size *= mesh.shape[n]
    return size


def _div(dim: int, mesh, axis) -> bool:
    return dim % _axis_size(mesh, axis) == 0


# ---------------------------------------------------------------------------
# Parameter specs (name-based Megatron rules)
# ---------------------------------------------------------------------------

_COL = ("wq", "wk", "wv", "w_gate", "w_up", "w_in", "w_q", "w_uq", "w_uk", "w_uv",
        "w_gate_branch", "w_x_branch", "w_rec_gate", "w_in_gate", "w_ogate",
        "w_zifo")
_ROW = ("wo", "w_down", "w_out")
_VOCAB = ("embed", "head")
_REPL = ("norm1", "norm2", "final_norm", "enc_final_norm", "norm_x", "q_norm",
         "kv_norm", "gamma", "beta", "router", "w_dq", "w_dkv", "w_kr",
         "w_igate", "w_fgate")


def _leaf_name(path) -> str:
    for entry in reversed(path):
        if hasattr(entry, "key"):
            return str(entry.key)
        if hasattr(entry, "name"):
            return str(entry.name)
    return ""


def _path_names(path) -> tuple:
    return tuple(
        str(getattr(e, "key", getattr(e, "name", ""))) for e in path
    )


def _param_rule(path, shape, mesh, cfg: ModelConfig, fsdp: bool) -> P:
    name = _leaf_name(path)
    names = _path_names(path)
    nd = len(shape)
    stacked = 1 if ("blocks" in names or "cross_blocks" in names or
                    "enc_blocks" in names) and name not in _VOCAB else 0
    # effective (un-stacked) shape
    eff = shape[stacked:]
    pre = (None,) * stacked
    has_data = "data" in mesh.axis_names

    def spec(*axes):
        return P(*(pre + axes))

    def maybe_fsdp(dim: int):
        """FSDP (ZeRO-3): shard this dim over "data" if enabled+divisible."""
        return "data" if (fsdp and has_data and _div(dim, mesh, "data")) else None

    if name in ("experts_gate", "experts_up", "experts_down"):
        e, d_in, d_out = eff
        if _div(e, mesh, "model"):                     # expert parallelism
            return spec("model", maybe_fsdp(d_in), None)
        if name == "experts_down" and _div(d_in, mesh, "model"):
            return spec(maybe_fsdp(e), "model", None)  # TP inside experts
        if name != "experts_down" and _div(d_out, mesh, "model"):
            return spec(maybe_fsdp(e), None, "model")
        return spec(None, None, None)
    if name == "r_zifo":                               # (4, H, dh, dh)
        return spec(None, None, None, "model") if _div(eff[-1], mesh, "model") else spec(
            None, None, None, None
        )
    if name == "lam":
        return spec("model") if _div(eff[0], mesh, "model") else spec(None)
    if name == "conv_w":
        return spec(None, "model") if _div(eff[-1], mesh, "model") else spec(None, None)
    if name in _VOCAB and nd - stacked == 2:
        v, d = eff
        if _div(v, mesh, "model"):
            return spec("model", maybe_fsdp(d))
        if _div(d, mesh, "model"):
            return spec(maybe_fsdp(v), "model")
        return spec(None, None)
    if name in _COL and nd - stacked == 2:
        if _div(eff[1], mesh, "model"):
            return spec(maybe_fsdp(eff[0]), "model")
        return spec(None, None)
    if name in _ROW and nd - stacked == 2:
        if _div(eff[0], mesh, "model"):
            return spec("model", maybe_fsdp(eff[1]))
        return spec(None, None)
    return P(*((None,) * nd))


def param_specs(params_or_shapes, mesh, cfg: ModelConfig, fsdp: bool = True):
    """Pytree of PartitionSpec mirroring the params tree.

    ``fsdp=True`` (default) additionally shards weights over the "data"
    axis (ZeRO-3): at 123B params, TP-16 alone leaves ~30 GiB fp32 of
    replicated master weights per device — FSDP brings it to ~1.9 GiB.
    The "pod" axis stays pure-DP (params replicated across pods; FSDP
    all-gathers stay inside a pod's ICI domain).
    """
    return jax.tree_util.tree_map_with_path(
        lambda path, leaf: _param_rule(path, leaf.shape, mesh, cfg, fsdp),
        params_or_shapes,
    )


# ---------------------------------------------------------------------------
# Batch / cache / optimizer specs
# ---------------------------------------------------------------------------

def batch_pspec(shape, mesh) -> P:
    """Shard dim0 (global batch) over DP axes when divisible, else replicate;
    shard the trailing (feature) dim over model when large & divisible."""
    dp = data_axes(mesh)
    first = dp if shape[0] % _axis_size(mesh, dp) == 0 else None
    rest = [None] * (len(shape) - 1)
    if len(shape) >= 3 and shape[-1] % _axis_size(mesh, "model") == 0 and shape[-1] >= 1024:
        rest[-1] = "model"
    return P(first, *rest)


def batch_specs_tree(batch_shapes, mesh):
    return jax.tree_util.tree_map(lambda s: batch_pspec(s.shape, mesh), batch_shapes)


def cache_specs(cache_shapes_tree, mesh):
    """KV/state caches: batch over DP if divisible, last dim over model."""
    dp = data_axes(mesh)
    dp_size = _axis_size(mesh, dp)
    model_size = _axis_size(mesh, "model")

    def rule(path, leaf):
        shape = leaf.shape
        name = _leaf_name(path)
        names = _path_names(path)
        stacked = 1 if ("blocks" in names or "cross_kv" in names) else 0
        if name == "pos":
            return P(None)
        axes = [None] * len(shape)
        bdim = stacked  # batch dim after the layer-stack dim
        if len(shape) > bdim and shape[bdim] % dp_size == 0 and shape[bdim] > 1:
            axes[bdim] = dp
        if len(shape) - stacked >= 2 and shape[-1] % model_size == 0:
            axes[-1] = "model"
        return P(*axes)

    return jax.tree_util.tree_map_with_path(rule, cache_shapes_tree)


def opt_state_specs(opt_shapes, p_specs, mesh, zero1: bool = True):
    """Adam moments inherit the param spec; ZeRO-1 adds "data" sharding on
    the largest still-replicated dim."""
    dp_size = mesh.shape.get("data", 1)

    def moment_spec(pspec, leaf):
        spec = list(pspec) + [None] * (len(leaf.shape) - len(pspec))
        if zero1 and "data" not in spec:  # FSDP may already consume "data"
            best, best_dim = -1, -1
            for i, (ax, d) in enumerate(zip(spec, leaf.shape)):
                if ax is None and d % dp_size == 0 and d > best:
                    best, best_dim = d, i
            if best_dim >= 0 and best >= dp_size:
                spec[best_dim] = "data"
        return P(*spec)

    out = {
        "step": P(),
        "m": jax.tree_util.tree_map(moment_spec, p_specs, opt_shapes["m"]),
        "v": jax.tree_util.tree_map(moment_spec, p_specs, opt_shapes["v"]),
    }
    if "master" in opt_shapes:
        out["master"] = jax.tree_util.tree_map(
            moment_spec, p_specs, opt_shapes["master"]
        )
    return out


# ---------------------------------------------------------------------------
# Paged-pool specs (serving: repro/paging/cache.py page pools)
# ---------------------------------------------------------------------------

# pool leaves whose lane axis holds the KV heads side by side: kp/vp rows
# (H_kv * head_dim) and the int8 scale rows (H_kv * page_size), head-major
_POOL_HEAD_LEAVES = ("kp", "vp", "kvp_scale")


def pool_specs(cache_shapes_tree, mesh, n_kv_heads: int):
    """PartitionSpecs for a paged serving cache (``paged_cache_shapes``).

    Page pools shard their KV heads over "model" — the axis the attention
    shards its heads over, so each device's pool slice feeds its own head
    shard with no gather traffic.  The heads lie side by side along a
    pool's lane axis, so that axis is split into whole heads, where the
    mesh divides ``n_kv_heads``.  MLA latent pools (``ckvp``) shard the
    latent rank; the shared rope pool (``krp``) is replicated (every head
    shard reads all rope dims).  ``pos`` and ``block_tables`` replicate:
    the host-side ``PageManager`` stays the single source of truth and
    every device sees the same table.  Per-lane leaves (recurrent state,
    local-attention rings) shard their trailing width.  Any non-divisible
    dim falls back to replication — never an error.
    """
    model = _axis_size(mesh, "model")

    def rule(path, leaf):
        shape = leaf.shape
        name = _leaf_name(path)
        nd = len(shape)
        axes = [None] * nd
        if name in ("pos", "block_tables") or nd < 2 or name == "krp":
            return P(*axes)
        if name in _POOL_HEAD_LEAVES:
            if n_kv_heads % model == 0:
                axes[-1] = "model"
            return P(*axes)
        # ckvp latent pools and per-lane leaves (recurrent h/conv/C/n/c,
        # local-attn rings): trailing width over "model" when divisible
        if shape[-1] % model == 0:
            axes[-1] = "model"
        return P(*axes)

    return jax.tree_util.tree_map_with_path(rule, cache_shapes_tree)


def ambient_mesh():
    """The mesh of the enclosing ``with mesh:`` block, or None.

    The serving engine traces its jits inside ``with mesh:`` (see
    ``serving/engine._with_mesh``); this is how trace-time code (the
    constraints below, the kernel wrappers in ``shard/kernels.py``) finds
    it without threading a mesh argument through every model function.
    """
    from jax._src import mesh as mesh_lib

    m = mesh_lib.thread_resources.env.physical_mesh
    return None if m.empty else m


def dp_axis_for(m, dim: int):
    """The mesh's DP axes if they divide ``dim``, else None (replicate)."""
    dp = data_axes(m)
    return dp if (dp and dim % _axis_size(m, dp) == 0) else None


def model_axis_for(m, dim: int):
    """"model" if the mesh has that axis and it divides ``dim``, else None."""
    return ("model" if "model" in m.axis_names and dim % m.shape["model"] == 0
            else None)


def _sp_constrain(x, seq_axis):
    """Internal: pin (B, S, d) to batch-over-DP with the given seq sharding.
    Identity outside a mesh; a dim that does not divide is replicated."""
    m = ambient_mesh()
    if m is None or x.ndim != 3:
        return x
    second = seq_axis and model_axis_for(m, x.shape[1])
    return jax.lax.with_sharding_constraint(
        x, P(dp_axis_for(m, x.shape[0]), second, None))


def sp_enter(x):
    """Megatron-SP boundary INTO attention/MLP: all-gather the sequence dim.

    Activations stay seq-sharded over "model" between layers (smallest
    resident form); entering a TP region each rank needs the full sequence
    for its head/column shard.  Without this explicit constraint XLA's
    SPMD partitioner prefers to UN-shard the TP weights instead —
    measured 87 GiB/device/layer-step of f32 weight all-gathers at 123B
    vs ~1.6 GiB of activation gathers (EXPERIMENTS.md Perf A-log)."""
    return _sp_constrain(x, None)


def sp_exit(x):
    """Megatron-SP boundary OUT of attention/MLP: reduce-scatter the row-
    parallel output back to seq-sharded."""
    return _sp_constrain(x, "model")


def constrain_activations(x):
    """Megatron-SP: pin (B, S, d) activations at layer boundaries to
    batch-over-DP x sequence-over-"model" sharding.  The scan-over-layers
    carry (saved for backward) is what dominates HBM at 100B scale; without
    this it is replicated over the model axis (16x larger).

    No-op outside a mesh context (CPU unit tests); dims that don't divide
    are replicated.  XLA SPMD re-gathers inside attention/MLP as needed.
    """
    return _sp_constrain(x, "model")


def constrain_decode_carry(x):
    """Serving decode/verify activations (B, 1..k, d): batch over DP,
    sequence and features replicated.  One decode row per lane is too
    narrow to seq-shard; pinning the carry keeps XLA's SPMD partitioner
    from round-tripping it through "model"-sharded layouts between the
    row-parallel reduce of one layer and the column-parallel matmul of the
    next.  No-op outside a mesh context (the unsharded engine)."""
    return _sp_constrain(x, None)


def named(tree_of_specs, mesh):
    return jax.tree_util.tree_map(
        lambda s: NamedSharding(mesh, s),
        tree_of_specs,
        is_leaf=lambda x: isinstance(x, P),
    )
