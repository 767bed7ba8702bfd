"""Mixture-of-Experts FFN: top-k routing with sort-based capacity dispatch.

Covers granite-moe (40 routed, top-8) and deepseek-moe (2 shared + 64
routed, top-6, first layer dense).  Dispatch is MegaBlocks-style: tokens
are argsorted by expert id, packed into an (E, C, d) buffer (capacity
C = ceil(T * k / E * capacity_factor); overflow tokens drop to a trash
row), run through grouped GEMMs (sharded over the "model" mesh axis =
expert parallelism), then combined with router weights.  Expert GEMMs run
under the same SPOGA quantization modes as dense layers.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from repro.backends import dynamic_quant, parse_quant_mode
from repro.backends.pipeline import effective_bits
from repro.configs.base import ModelConfig
from repro.core import spoga as spoga_ops
from repro.core.slicing import slice_planes
from repro.models.layers import (
    COMPUTE_DTYPE,
    _act,
    glu_mlp,
    init_glu_mlp,
    truncated_normal_init,
)


# HLO scope of the routed experts (router, dispatch, the held experts'
# grouped GEMMs with their re-quantization, combine; not the shared
# experts), named for the device trace
EXPERTS_SCOPE = "moe_experts"


def init_moe(key, cfg: ModelConfig):
    """The router over every routed expert; weights of the held ones."""
    m = cfg.moe
    d, e, de = cfg.d_model, m.held, m.d_expert
    ks = jax.random.split(key, 5)

    p = {
        # router stays fp32: routing logits are precision-sensitive
        "router": truncated_normal_init(ks[0], (d, m.num_experts), scale=0.02,
                                        dtype=jnp.float32),
        "experts_gate": truncated_normal_init(ks[1], (e, d, de), scale=0.02),
        "experts_up": truncated_normal_init(ks[2], (e, d, de), scale=0.02),
        "experts_down": truncated_normal_init(ks[3], (e, de, d), scale=0.02),
    }
    if m.num_shared_experts:
        p["shared"] = init_glu_mlp(ks[4], d, m.num_shared_experts * de)
    return p


def _grouped_matmul(x, w, quant_mode, backend=None):
    """x: (..., E, C, K), w: (E, K, N) -> (..., E, C, N).

    The expert dim stays aligned with the weights' leading dim (sharded
    over "model" = expert parallelism); any leading dims (the batch rows
    of the local-capacity dispatch) stay sharded over "data".
    Integer paths bit-slice per the mode's QuantSpec and reuse the generic
    radix accumulation from :mod:`repro.core.spoga` with this expert-batched
    contraction — the Pallas kernels are strictly 2-D, so the grouped GEMM
    keeps the jnp dataflow (sharded by pjit) for every mode family; an
    explicit ``backend`` override still picks the dataflow family.
    """
    if quant_mode == "bf16":
        return jnp.einsum("...eck,ekn->...ecn",
                          x.astype(COMPUTE_DTYPE), w.astype(COMPUTE_DTYPE))
    spec, family = parse_quant_mode(quant_mode)
    if backend is not None:
        from repro.backends import get_backend

        family = get_backend(backend).family
    a_bits, w_bits = effective_bits(spec, x.shape[-1])
    xq, xs = dynamic_quant(x.astype(jnp.float32), axis=-1, bits=a_bits)
    # the experts' per-call re-quantization, named for the device trace
    with jax.named_scope("weight_quant"):
        wq, ws = dynamic_quant(w.astype(jnp.float32), axis=1, bits=w_bits)

    e_axis = x.ndim - 3

    def dot(a, b):
        # contract K; batch over E; leading dims of `a` ride along.
        out = jax.lax.dot_general(
            a, b,
            (((a.ndim - 1,), (1,)), ((e_axis,), (0,))),
            preferred_element_type=jnp.int32,
        )  # -> (E, ..., C, N)
        return jnp.moveaxis(out, 0, e_axis)

    if family == "direct":
        acc = dot(xq, wq)
    else:
        acc = spoga_ops.sliced_dot_planes(
            slice_planes(xq, spec.n_a_slices, spec.slice_bits),
            slice_planes(wq, spec.n_w_slices, spec.slice_bits),
            spec.slice_bits,
            dot_fn=dot,
            materialize=(family == "deas"),
        )
    out = acc.astype(jnp.float32) * xs * ws
    return out.astype(COMPUTE_DTYPE)


def _grouped_glu(x, p, act, quant_mode, backend=None):
    g = _act(act)(_grouped_matmul(x, p["experts_gate"], quant_mode, backend))
    u = _grouped_matmul(x, p["experts_up"], quant_mode, backend)
    return _grouped_matmul(g * u, p["experts_down"], quant_mode, backend)


def _route(x, p, m):
    """Softmax router over all ``m.num_experts``: (probs (B, S, E), top-k
    weights and ids (B, S, k)), the weights renormalised over the chosen
    experts only where ``m.norm_topk_prob`` says so."""
    logits = jnp.einsum(
        "bsd,de->bse", x.astype(jnp.float32), p["router"].astype(jnp.float32)
    )
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, m.top_k)              # (B, S, k)
    if m.norm_topk_prob:
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    return probs, topw, topi


def _held_ids(topi, m):
    """Routed expert ids as this device's expert index: held experts
    ``[first_held, first_held + held)`` to ``[0, held)``, every other to
    ``held``, a group no buffer keeps (its part belongs to another
    device)."""
    if m.held == m.num_experts:
        return topi
    local = topi - m.first_held
    return jnp.where((local >= 0) & (local < m.held), local, m.held)


def moe_ffn(x, p, cfg: ModelConfig):
    """x: (B, S, d) -> (out (B, S, d), aux_loss scalar).

    With ``moe.n_held`` the layer computes its held experts' part only:
    the router still spans every expert and picks the top-k of all of
    them; tokens routed elsewhere add nothing here.

    Capacity is enforced PER BATCH ROW (local capacity): the sort-based
    dispatch is vmapped over B, so every tensor keeps its leading batch
    dim sharded over "data" while the expert dim aligns with the "model"
    axis (EP).  A global (B*S)-token sort would force XLA SPMD to gather
    the full (E, C, d) dispatch buffer onto every device — at 1M tokens
    that alone is tens of GiB/device (this was measured, see EXPERIMENTS
    Perf log), whereas the local form keeps it at tokens_per_device * k
    * capacity_factor.
    """
    m = cfg.moe
    s, k = x.shape[1], m.top_k
    cap = max(1, math.ceil(s * k / m.num_experts * m.capacity_factor))
    if cap > 128:
        # Round capacity up to a 128 multiple: when the expert count does
        # not divide the "model" axis (granite: 40 experts, TP-16), the
        # dispatch buffer is sharded along CAPACITY instead — it must
        # divide any model-axis size up to 128 (<=8% padding).
        cap = -(-cap // 128) * 128

    with jax.named_scope(EXPERTS_SCOPE):
        probs, topi, out = _routed_experts(x, p, cfg, cap)
    if m.num_shared_experts:
        out = out + glu_mlp(x, p["shared"], cfg.act, cfg.quant_mode, backend=cfg.gemm_backend)

    # Switch-style load-balance aux loss (global over B*S tokens).
    dispatch_frac = jnp.mean(
        jax.nn.one_hot(topi, m.num_experts, dtype=jnp.float32).sum(2), axis=(0, 1)
    )
    mean_prob = jnp.mean(probs, axis=(0, 1))
    aux = m.num_experts * jnp.sum(dispatch_frac * mean_prob) / k
    return out, aux


def _routed_experts(x, p, cfg: ModelConfig, cap: int):
    """Route, dispatch to the held experts' (E_held, C, d) buffers, run
    their grouped GLU and combine: (probs, top-k ids, out (B, S, d))."""
    m = cfg.moe
    b, s, d = x.shape
    k, e = m.top_k, m.held
    probs, topw, topi = _route(x, p, m)

    def route_row(xrow, topi_row):
        """xrow (S, d), topi_row (S, k) -> (buf (E, C, d), dest, sort_idx)."""
        flat_e = topi_row.reshape(-1)                       # (S*k,)
        sort_idx = jnp.argsort(flat_e, stable=True)
        sorted_e = flat_e[sort_idx]
        group_start = jnp.searchsorted(sorted_e, sorted_e, side="left")
        pos_in_e = jnp.arange(s * k) - group_start          # rank within expert
        keep = (pos_in_e < cap) & (sorted_e < e)            # held, in capacity
        dest = jnp.where(keep, sorted_e * cap + pos_in_e, e * cap)  # trash row
        x_sorted = jnp.take(xrow, sort_idx // k, axis=0)    # (S*k, d)
        buf = jnp.zeros((e * cap + 1, d), xrow.dtype).at[dest].set(x_sorted)
        return buf[: e * cap].reshape(e, cap, d), dest, sort_idx

    bufs, dest, sort_idx = jax.vmap(route_row)(x, _held_ids(topi, m))  # (B, E, C, d), ...
    bufs = _constrain_ep(bufs)                              # B->data, E->model

    y = _grouped_glu(bufs, p, cfg.act, cfg.quant_mode, backend=cfg.gemm_backend)      # (B, E, C, d)
    y = _constrain_ep(y)  # keep expert outputs EP-sharded until combine

    def combine_row(y_row, dest_row, sort_idx_row, topw_row):
        y_flat = jnp.concatenate(
            [y_row.reshape(e * cap, d), jnp.zeros((1, d), y_row.dtype)], axis=0)
        out_sorted = jnp.take(y_flat, dest_row, axis=0)     # dropped -> zeros
        out_flat = jnp.zeros((s * k, d), y_row.dtype).at[sort_idx_row].set(out_sorted)
        return jnp.einsum("skd,sk->sd", out_flat.reshape(s, k, d).astype(jnp.float32),
                          topw_row)

    out = jax.vmap(combine_row)(y, dest, sort_idx, topw).astype(x.dtype)
    return probs, topi, out


def _constrain_ep(bufs):
    """Pin the dispatch buffer (B, E, C, d) to batch-over-data x
    expert-over-model sharding (EP+DP).  No-op outside a mesh / on
    non-divisible dims."""
    from jax.sharding import PartitionSpec as P

    from repro.runtime.sharding import ambient_mesh, dp_axis_for, model_axis_for

    mesh = ambient_mesh()
    if mesh is None:
        return bufs
    # EP when the expert dim divides the model axis; otherwise shard the
    # (128-padded) capacity dim so the buffer still never replicates
    # across "model".
    second = model_axis_for(mesh, bufs.shape[1])
    third = None if second else model_axis_for(mesh, bufs.shape[2])
    return jax.lax.with_sharding_constraint(
        bufs, P(dp_axis_for(mesh, bufs.shape[0]), second, third, None))


def moe_ffn_reference(x, p, cfg: ModelConfig):
    """Dense (every-expert) reference for tests: no capacity, no drops."""
    m = cfg.moe
    b, s, d = x.shape
    xf = x.reshape(-1, d)
    logits = jnp.einsum("td,de->te", xf.astype(jnp.float32), p["router"].astype(jnp.float32))
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, m.top_k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    gate = jnp.zeros_like(probs).at[jnp.arange(xf.shape[0])[:, None], topi].set(topw)
    ys = _grouped_glu(
        jnp.broadcast_to(xf, (m.num_experts,) + xf.shape), p, cfg.act,
        cfg.quant_mode, backend=cfg.gemm_backend,
    )  # (E, T, d)
    out = jnp.einsum("etd,te->td", ys.astype(jnp.float32), gate).astype(x.dtype)
    if m.num_shared_experts:
        out = out + glu_mlp(xf, p["shared"], cfg.act, cfg.quant_mode, backend=cfg.gemm_backend)
    return out.reshape(b, s, d)
