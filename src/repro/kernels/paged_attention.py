"""Paged decode attention over block tables — Pallas TPU kernel + jnp twin.

The serving engine's paged KV cache (repro/paging/) stores K/V in a global
page pool ``(n_pages, page_size, H_kv, D)`` shared by every lane; a lane's
logical sequence is the concatenation of the physical pages its block
table names.  The kernel streams those pages straight from the pool —
``PrefetchScalarGridSpec`` hands the block table to the BlockSpec index
maps, so page ``j`` of lane ``b`` is DMA'd from ``tables[b, j]`` without
ever materializing the gathered (B, S, H, D) view that the jnp twin
builds.  A flash-style running softmax (per-lane max / denominator / value
accumulator in VMEM scratch) folds the pages into the output in one pass.

The grid is (lane, page): each step takes one whole page for all KV
heads, so a block's last two dims are the pool's own (Mosaic refuses a
size-1 block on ``H_kv``), and the heads are unrolled in the body.

The int8 byte-size variant fuses page dequantization: int8 payloads are
read from the pool and the per-(position, head) scales multiply the K / V
rows in VMEM before the dot products — the paper's byte-size operand
stream applied to decode's dominant HBM traffic, in the same shape as
``spoga_gemm_dequant`` fuses the epilogue.

Layouts (G = query heads per KV head):

    q        (B, H_kv, G, D)        bf16/f32
    kp, vp   (n_pages, page_size, H_kv, D)   bf16 | int8
    k_scale, v_scale  (n_pages, page_size, H_kv) f32 (int8 variant)
    tables   (B, P) int32 physical page ids
    lengths  (B,)   int32 valid rows per lane (pos + 1 at decode)
    out      (B, H_kv, G, D) f32

Off-TPU the kernel runs only through the Pallas interpreter
(``interpret=True``, ``paged_attn_impl="pallas_interpret"``), mirroring the
``pallas_interpret`` GEMM backend; under a mesh it runs per device inside
``shard_map`` (``shard/kernels.py``).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spoga_gemm import CompilerParams

NEG_INF = -1e30


def _kernel(tables_ref, lengths_ref, q_ref, kp_ref, vp_ref, *rest,
            page_size: int, n_tbl: int, int8: bool):
    """One grid step = one page of one lane, for all of its KV heads.

    The K/V block is a whole page ``(1, page_size, H_kv, D)``: its last two
    dims equal the pool's, which is what Mosaic requires of a block (a
    size-1 block on ``H_kv`` is refused).  The heads are unrolled in the
    body; each keeps its own flash state in the scratch rows ``[h]``.
    """
    if int8:
        ks_ref, vs_ref, o_ref, m_ref, l_ref, acc_ref = rest
    else:
        o_ref, m_ref, l_ref, acc_ref = rest
    b, p = pl.program_id(0), pl.program_id(1)
    n_heads, _, d = q_ref.shape[1:]

    @pl.when(p == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    for h in range(n_heads):
        q = q_ref[0, h].astype(jnp.float32)                # (G, D)
        k = kp_ref[0, :, h, :].astype(jnp.float32)         # (page_size, D)
        v = vp_ref[0, :, h, :].astype(jnp.float32)
        if int8:
            # fused dequant: per-row scales ride the page rows, so
            # q.(k*ks) == (q.k)*ks and p@(v*vs) == (p*vs)@v
            k = k * ks_ref[0, :, h:h + 1]
            v = v * vs_ref[0, :, h:h + 1]
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * (d ** -0.5)                                    # (G, page_size)
        kpos = p * page_size + jax.lax.broadcasted_iota(jnp.int32, s.shape, 1)
        s = jnp.where(kpos < lengths_ref[b], s, NEG_INF)

        # flash update: m/l scratches are (G, 128) lane-replicated scalars
        m_prev, l_prev = m_ref[h], l_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new[:, :1])                   # (G, page_size)
        l_ref[h] = alpha * l_prev + jnp.sum(pexp, axis=1, keepdims=True)
        m_ref[h] = m_new
        pv = jax.lax.dot_general(
            pexp, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[h] = acc_ref[h] * alpha[:, :1] + pv

    @pl.when(p == n_tbl - 1)
    def _emit():
        for h in range(n_heads):
            o_ref[0, h] = acc_ref[h] / l_ref[h][:, :1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, kp, vp, tables, lengths, *, k_scale=None,
                    v_scale=None, interpret: bool = False):
    """Flash decode attention over paged KV. See module docstring for
    layouts. ``k_scale``/``v_scale`` select the fused-int8-dequant variant;
    both or neither must be given."""
    if (k_scale is None) != (v_scale is None):
        raise ValueError("int8 paged attention needs both k_scale and v_scale")
    b, hkv, g, d = q.shape
    page_size = kp.shape[1]
    n_tbl = tables.shape[1]
    int8 = k_scale is not None

    def q_idx(bi, pi, tbl, ln):
        return (bi, 0, 0, 0)

    def kv_idx(bi, pi, tbl, ln):
        return (tbl[bi, pi], 0, 0, 0)

    def scale_idx(bi, pi, tbl, ln):
        return (tbl[bi, pi], 0, 0)

    in_specs = [
        pl.BlockSpec((1, hkv, g, d), q_idx),
        pl.BlockSpec((1, page_size, hkv, d), kv_idx),
        pl.BlockSpec((1, page_size, hkv, d), kv_idx),
    ]
    operands = [q, kp, vp]
    if int8:
        in_specs += [
            pl.BlockSpec((1, page_size, hkv), scale_idx),
            pl.BlockSpec((1, page_size, hkv), scale_idx),
        ]
        operands += [k_scale, v_scale]

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(b, n_tbl),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, g, d), q_idx),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 128), jnp.float32),   # running max
            pltpu.VMEM((hkv, g, 128), jnp.float32),   # running denominator
            pltpu.VMEM((hkv, g, d), jnp.float32),     # value accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, n_tbl=n_tbl, int8=int8),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(tables.astype(jnp.int32), lengths.astype(jnp.int32), *operands)


def paged_attention_ref(q, kp, vp, tables, lengths, *, k_scale=None,
                        v_scale=None):
    """jnp gather twin (exact softmax) — the reference the kernel is tested
    against, and the lowering the engine uses off-TPU."""
    b, hkv, g, d = q.shape
    page_size = kp.shape[1]
    smax = tables.shape[1] * page_size

    def gather(pool):
        return pool[tables].reshape((b, smax) + pool.shape[2:])

    k_all, v_all = gather(kp), gather(vp)
    qf = q.astype(jnp.float32)
    scores = jnp.einsum("bhgd,bshd->bhgs", qf, k_all.astype(jnp.float32))
    scores = scores * (d ** -0.5)
    if k_scale is not None:
        scores = scores * gather(k_scale).transpose(0, 2, 1)[:, :, None, :]
    valid = jnp.arange(smax)[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if v_scale is not None:
        probs = probs * gather(v_scale).transpose(0, 2, 1)[:, :, None, :]
    return jnp.einsum("bhgs,bshd->bhgd", probs, v_all.astype(jnp.float32))
