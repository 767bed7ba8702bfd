"""Pallas kernels under a tensor-parallel mesh.

Mosaic kernels cannot be partitioned automatically: a ``pallas_call`` whose
operands are sharded over a mesh fails to compile.  Under the engine's
``with mesh:`` every kernel call therefore goes through ``shard_map`` with
the layout its operands were committed in by the Megatron rules of
``runtime/sharding.py``:

* column-parallel GEMM (``wq/wk/wv``, ``w_gate/w_up``, ...): each device
  holds whole output columns, so the fused dequant kernel runs locally on
  the replicated activations and writes its column shard;
* row-parallel GEMM (``wo``, ``w_down``, ...): each device contracts its
  K-slice to int32 partials, which ``psum`` exactly; the dequant epilogue
  runs once on the sum.  Activation and weight scales were computed in the
  global view (the per-row / per-column ``amax`` over a sharded K lowers
  to a cross-device max), so the result equals the unsharded GEMM's;
* paged decode attention: each device attends over its own KV heads of
  its own pool shard; block tables and lengths are replicated.

A layout that the mesh does not divide (or no layout hint) runs the kernel
on replicated operands: correct everywhere, at the cost of gathering them.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.sharding import PartitionSpec as P

from repro.runtime.sharding import ambient_mesh, model_axis_for


def _dequant(backend, x, w, x_scale, w_scale, spec):
    if backend.gemm_dequant is not None:
        return backend.gemm_dequant(x, w, x_scale, w_scale, spec)
    return backend.gemm(x, w, spec).astype(jnp.float32) * x_scale * w_scale


def sharded_gemm_dequant(backend, x, w, x_scale, w_scale, spec, mesh,
                         layout=None):
    """(M, K) int @ (K, N) int * (M, 1) * (1, N) -> (M, N) f32 on ``mesh``.

    ``layout`` is "col", "row" or None (see the module docstring).
    """
    k, n = w.shape
    if layout == "col" and model_axis_for(mesh, n):
        return jax.shard_map(
            lambda *a: _dequant(backend, *a, spec), mesh=mesh,
            in_specs=(P(), P(None, "model"), P(), P(None, "model")),
            out_specs=P(None, "model"), check_vma=False,
        )(x, w, x_scale, w_scale)
    if layout == "row" and model_axis_for(mesh, k):
        acc = jax.shard_map(
            lambda x, w: jax.lax.psum(backend.gemm(x, w, spec), "model"),
            mesh=mesh, in_specs=(P(None, "model"), P("model", None)),
            out_specs=P(), check_vma=False,
        )(x, w)
        return acc.astype(jnp.float32) * x_scale * w_scale
    return jax.shard_map(
        lambda *a: _dequant(backend, *a, spec), mesh=mesh,
        in_specs=(P(),) * 4, out_specs=P(), check_vma=False,
    )(x, w, x_scale, w_scale)


def sharded_paged_attention(q, kp, vp, tables, lengths, layer, *, scales=None,
                            interpret=False):
    """``kernels.paged_attention`` under the ambient mesh (if any): KV heads
    over "model" when the mesh divides them, as ``pool_specs`` shards the
    stacked pools' head-major lane axis; the kernel itself when there is no
    mesh."""
    from repro.kernels.paged_attention import paged_attention

    mesh = ambient_mesh()

    def attend(q, kp, vp, tables, lengths, layer, *scales):
        return paged_attention(q, kp, vp, tables, lengths, layer,
                               scales=scales[0] if scales else None,
                               interpret=interpret)

    scales = () if scales is None else (scales,)
    if mesh is None:
        return attend(q, kp, vp, tables, lengths, layer, *scales)
    heads = model_axis_for(mesh, q.shape[1])
    qs = P(None, heads, None, None)
    pool = P(None, None, None, heads)
    return jax.shard_map(
        attend, mesh=mesh,
        in_specs=(qs, pool, pool, P(), P(), P()) + (pool,) * len(scales),
        out_specs=qs, check_vma=False,
    )(q, kp, vp, tables, lengths, layer, *scales)
