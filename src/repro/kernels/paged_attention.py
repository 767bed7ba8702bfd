"""Paged decode attention over block tables — Pallas TPU kernel + jnp twin.

The serving engine's paged KV cache (repro/paging/) stores K/V in global
page pools shared by every lane, one pool per layer stacked along a
leading layer axis; a lane's logical sequence is the concatenation of the
physical pages its block table names.  The kernel reads layer ``layer``'s
pages straight from the stacked pools into VMEM, never materializing the
layer's pool or the gathered (B, S, H, D) view that the jnp twin builds,
so the decode layer scan can carry the stacked pools in place.  A
flash-style running softmax (per-lane max / denominator / value
accumulator in VMEM scratch) folds the pages into the output in one pass.

The grid walks only live blocks.  A block is ``pages_per_block`` whole
pages of all KV heads (``ppb * page_size`` rows); lane ``b`` needs
``cdiv(lengths[b], block rows)`` of them, at least one, so an inactive
lane (length 1) costs one block and blocks past a lane's length are never
visited.  The grid is one step per live (lane, block) pair, its length
a traced value (the sum of the lanes' live blocks), and each step's pages
arrive as ``ppb`` page-sized BlockSpecs per pool array whose index maps
read ``(layer, tables[b, j])`` from the prefetched scalars: the pipeline
fetches the next step's pages, the next lane's first block included, while
the current block is computed.  A ragged last block of the table clamps
its page indices; every block masks rows by ``kpos < length``.  The block
size is derived from the shapes (:func:`pages_per_block`), not passed in.
The heads are unrolled in the body, each with its own flash state, which
is set at a lane's first block and emitted at its last.

Storage is lane-dense, so that the device's default layout of every pool
is row-major and neither the kernel nor the layer scan pays a relayout: a
page holds its rows with all heads side by side (``H_kv * D`` lanes), and
the int8 variant's scale pool holds a page's K scales and V scales as two
rows, each head-major (``H_kv * page_size`` lanes).  With ``D`` or ``H_kv``
narrower than a lane row, the natural ``(..., H_kv, D)`` and
``(..., page_size, H_kv)`` shapes get a pages-minor default layout
instead.  The kernel takes each head's rows from the lane axis.

(Manual async copies from a ``pl.ANY`` pool cannot express this: Mosaic
refuses a page slice of a pool whose minor dims are not 128-aligned, while
BlockSpec windows of the same pages compile.)

The int8 byte-size variant fuses page dequantization: int8 payloads are
read from the pool and the per-(position, head) scales are applied on the
score and probability side, ``(q.k) * ks`` and ``(p * vs) @ v`` — the
paper's byte-size operand stream applied to decode's dominant HBM
traffic, in the same shape as ``spoga_gemm_dequant`` fuses the epilogue.

Layouts (G = query heads per KV head, L = stacked layers):

    q        (B, H_kv, G, D)        bf16/f32
    kp, vp   (L, n_pages, page_size, H_kv * D)       bf16 | int8
    scales   (L, n_pages, 2, H_kv * page_size) f32 (int8 variant: K, V)
    tables   (B, P) int32 physical page ids
    lengths  (B,)   int32 valid rows per lane (pos + 1 at decode), >= 1
    layer    ()     int32 the layer of the stacked pools to attend
    out      (B, H_kv, G, D) f32

Off-TPU the kernel runs only through the Pallas interpreter
(``interpret=True``, ``paged_attn_impl="pallas_interpret"``), mirroring the
``pallas_interpret`` GEMM backend; under a mesh it runs per device inside
``shard_map`` (``shard/kernels.py``).
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels.spoga_gemm import CompilerParams

NEG_INF = -1e30
# rows a block aims at: of 64-512, the fastest at chat lengths on a v5e
# (PERF.md); smaller blocks pay the per-step overhead more often, larger
# ones attend more masked rows past the lanes' lengths
BLOCK_ROWS = 128
# logical bytes the double-buffered K/V page blocks (and scales) may take
BLOCK_VMEM_BYTES = 8 << 20


def page_bytes(pools) -> int:
    """Bytes of one page across ``pools`` (K, V and, int8, the scales):
    a page is the two minor dims of every pool layout above."""
    return sum(math.prod(p.shape[-2:]) * jnp.dtype(p.dtype).itemsize
               for p in pools)


def pages_per_block(page_size: int, nbytes: int, n_tbl: int) -> int:
    """Pages per block for pools of ``nbytes`` a page (:func:`page_bytes`):
    ``BLOCK_ROWS`` rows of whole pages, fewer where two buffers of a block
    would pass ``BLOCK_VMEM_BYTES``, never more than the table holds."""
    fit = BLOCK_VMEM_BYTES // (2 * nbytes)
    return max(1, min(BLOCK_ROWS // page_size, fit, n_tbl))


def _live_blocks(lengths, rows: int, n_blocks: int):
    return jnp.clip(pl.cdiv(lengths, rows), 1, n_blocks)


def _kernel(layer_ref, lane_ref, blk_ref, tables_ref, lengths_ref, q_ref,
            *refs, page_size: int, ppb: int, n_blocks: int, int8: bool):
    """One grid step = one live block of one lane, for all its KV heads.

    ``refs`` holds ``ppb`` page blocks ``(page_size, H_kv * D)`` of K and V
    (and in the int8 variant ``(2, H_kv * page_size)`` of their scales),
    then the output and the flash scratch.  A block's last two dims are the
    pool's own.
    """
    del layer_ref  # read by the index maps only
    n_in = 3 if int8 else 2
    pages = [refs[a * ppb:(a + 1) * ppb] for a in range(n_in)]
    o_ref, m_ref, l_ref, acc_ref = refs[n_in * ppb:]
    step = pl.program_id(0)
    lane, blk = lane_ref[step], blk_ref[step]
    length = lengths_ref[lane]
    n_heads, g, d = q_ref.shape[1:]
    rows = ppb * page_size

    @pl.when(blk == 0)
    def _init():
        m_ref[...] = jnp.full_like(m_ref, NEG_INF)
        l_ref[...] = jnp.zeros_like(l_ref)
        acc_ref[...] = jnp.zeros_like(acc_ref)

    def head_rows(a, h):
        """Rows of head ``h`` of pool ``a`` across the block's pages."""
        return jnp.concatenate(
            [pages[a][p][:, h * d:(h + 1) * d].astype(jnp.float32)
             for p in range(ppb)], axis=0)                  # (rows, D)

    def head_scales(a, h):
        """Scales of head ``h`` of K (``a`` 0) or V (1) across the block's
        rows."""
        return jnp.concatenate(
            [pages[2][p][a:a + 1, h * page_size:(h + 1) * page_size]
             for p in range(ppb)], axis=1)                  # (1, rows)

    kpos = blk * rows + jax.lax.broadcasted_iota(jnp.int32, (g, rows), 1)
    for h in range(n_heads):
        q = q_ref[0, h].astype(jnp.float32)                # (G, D)
        k, v = head_rows(0, h), head_rows(1, h)
        s = jax.lax.dot_general(
            q, k, (((1,), (1,)), ((), ())), preferred_element_type=jnp.float32,
        ) * (d ** -0.5)                                    # (G, rows)
        if int8:
            # fused dequant: per-row scales factor out of the D-contraction
            s = s * head_scales(0, h)
        s = jnp.where(kpos < length, s, NEG_INF)

        # flash update: m/l scratches are (G, 128) lane-replicated scalars
        m_prev, l_prev = m_ref[h], l_ref[h]
        m_new = jnp.maximum(m_prev, jnp.max(s, axis=1, keepdims=True))
        alpha = jnp.exp(m_prev - m_new)
        pexp = jnp.exp(s - m_new[:, :1])                   # (G, rows)
        l_ref[h] = alpha * l_prev + jnp.sum(pexp, axis=1, keepdims=True)
        m_ref[h] = m_new
        pw = pexp * head_scales(1, h) if int8 else pexp
        pv = jax.lax.dot_general(
            pw, v, (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
        acc_ref[h] = acc_ref[h] * alpha[:, :1] + pv

    @pl.when(blk == _live_blocks(length, rows, n_blocks) - 1)
    def _emit():
        for h in range(n_heads):
            o_ref[0, h] = acc_ref[h] / l_ref[h][:, :1]


@functools.partial(jax.jit, static_argnames=("interpret",))
def paged_attention(q, kp, vp, tables, lengths, layer, *, scales=None,
                    interpret: bool = False):
    """Flash decode attention over layer ``layer`` of stacked paged KV. See
    module docstring for layouts. ``scales`` selects the fused-int8-dequant
    variant."""
    b, hkv, g, d = q.shape
    page_size = kp.shape[2]
    n_tbl = tables.shape[1]
    int8 = scales is not None
    pools = [kp, vp] + ([scales] if int8 else [])
    ppb = pages_per_block(page_size, page_bytes(pools), n_tbl)
    n_blocks = pl.cdiv(n_tbl, ppb)
    lengths = lengths.astype(jnp.int32)

    # the work list: step i attends block blk[i] of lane lane[i]; lanes in
    # order, each over its live blocks only
    live = _live_blocks(lengths, ppb * page_size, n_blocks)
    ends = jnp.cumsum(live)
    steps = jnp.arange(b * n_blocks, dtype=jnp.int32)
    lane = jnp.minimum(jnp.sum(steps[:, None] >= ends[None, :], axis=1,
                               dtype=jnp.int32), b - 1)
    blk = steps - (ends - live)[lane]

    def q_idx(i, layer, lane, blk, tbl, ln):
        return (lane[i], 0, 0, 0)

    def page_spec(p, block):
        def idx(i, layer, lane, blk, tbl, ln):
            j = jnp.minimum(blk[i] * ppb + p, n_tbl - 1)
            return (layer[0], tbl[lane[i] * n_tbl + j], 0, 0)
        return pl.BlockSpec((None, None) + block, idx)

    in_specs = [pl.BlockSpec((1, hkv, g, d), q_idx)]
    for pool in pools:
        in_specs += [page_spec(p, pool.shape[2:]) for p in range(ppb)]
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=5,
        grid=(ends[-1],),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, hkv, g, d), q_idx),
        scratch_shapes=[
            pltpu.VMEM((hkv, g, 128), jnp.float32),   # running max
            pltpu.VMEM((hkv, g, 128), jnp.float32),   # running denominator
            pltpu.VMEM((hkv, g, d), jnp.float32),     # value accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, page_size=page_size, ppb=ppb,
                          n_blocks=n_blocks, int8=int8),
        grid_spec=grid_spec,
        out_shape=jax.ShapeDtypeStruct((b, hkv, g, d), jnp.float32),
        # a lane's blocks run in order: its flash state carries across them
        compiler_params=CompilerParams(dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(jnp.reshape(layer, (1,)).astype(jnp.int32), lane, blk,
      tables.astype(jnp.int32).reshape(-1), lengths, q,
      *[pool for pool in pools for _ in range(ppb)])


def _gather_rows(pool, layer, tables, head_dim):
    """(L, n_pages, page_size, H * D) pool -> (B, P * page_size, H, D): the
    logically-contiguous per-lane rows of ``layer`` in one gather."""
    b, n_tbl = tables.shape
    g = pool[layer, tables]                        # (B, P, page_size, H * D)
    return g.reshape(b, n_tbl * pool.shape[2], -1, head_dim)


def _gather_scales(pool, layer, tables, page_size):
    """(L, n_pages, 2, H * page_size) scales -> K and V scales, each
    (B, H, P * page_size)."""
    b, n_tbl = tables.shape
    g = pool[layer, tables].reshape(b, n_tbl, 2, -1, page_size)  # (B,P,2,H,ps)
    g = g.transpose(2, 0, 3, 1, 4).reshape(2, b, g.shape[3], n_tbl * page_size)
    return g[0], g[1]


def paged_attention_ref(q, kp, vp, tables, lengths, layer, *, scales=None):
    """jnp gather twin (exact softmax) — the reference the kernel is tested
    against."""
    b, hkv, g, d = q.shape
    page_size = kp.shape[2]
    smax = tables.shape[1] * page_size

    k_all = _gather_rows(kp, layer, tables, d)
    v_all = _gather_rows(vp, layer, tables, d)
    qf = q.astype(jnp.float32)
    scores = jnp.einsum("bhgd,bshd->bhgs", qf, k_all.astype(jnp.float32))
    scores = scores * (d ** -0.5)
    if scales is not None:
        ks, vs = _gather_scales(scales, layer, tables, page_size)
        scores = scores * ks[:, :, None, :]
    valid = jnp.arange(smax)[None, :] < lengths[:, None]
    scores = jnp.where(valid[:, None, None, :], scores, NEG_INF)
    probs = jax.nn.softmax(scores, axis=-1)
    if scales is not None:
        probs = probs * vs[:, :, None, :]
    return jnp.einsum("bhgs,bshd->bhgd", probs, v_all.astype(jnp.float32))
