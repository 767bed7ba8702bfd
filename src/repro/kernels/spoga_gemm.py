"""SPOGA fused bit-sliced INT8 GEMM — Pallas TPU kernel.

TPU-native adaptation of the SPOGA DPU (paper Fig. 3):

* an (bm x bk) x (bk x bn) tile pair plays the role of a bank of OAMEs:
  both int8 tiles are nibble-sliced *in VMEM* and the four INT4 partial
  products are computed back-to-back on the MXU
  (``dot_general(..., preferred_element_type=int32)``);
* the radix-position weighting happens **inside the accumulator update**
  (``<< 8``, ``<< 4``, ``<< 0``) — the in-transduction capacitor trick —
  so no per-slice intermediate matrix ever exists outside VMEM;
* the K-grid loop accumulating into the VMEM ``acc_ref`` scratch is the
  homodyne charge accumulation over up-to-249 OAMEs;
* exactly one output write per (bm x bn) tile = the single ADC per dot
  product.

Tile defaults are MXU-aligned (multiples of 128 on the lane dim) and sized
so the working set (x, w tiles int8 + int32 accumulator + 4 partial tiles)
stays well under ~16 MB of VMEM.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

RADIX_BITS = 4

CompilerParams = pltpu.CompilerParams

DEFAULT_BLOCK_M = 256
DEFAULT_BLOCK_N = 256
DEFAULT_BLOCK_K = 512


def _slice_tc(t):
    # Two's-complement nibble slicing, elementwise on the VMEM tile (VPU).
    msn = jnp.right_shift(t, RADIX_BITS)      # signed high nibble in [-8, 7]
    lsn = jnp.bitwise_and(t, (1 << RADIX_BITS) - 1)  # unsigned low nibble
    return msn, lsn


def _slice_planes_tile(t, n_slices: int, slice_bits: int):
    """In-VMEM generalization of ``_slice_tc``: n planes, LSB first.

    The shifts run on the tile widened to int32 (Mosaic has no arithmetic
    right shift for 8/16-bit vectors; sign extension makes the widened
    shift bitwise equal to the narrow one).  Planes are then cast to int8
    (they fit for slice_bits <= 7 when the operand honors its
    n_slices * slice_bits budget) so every partial product runs on the
    MXU's byte path regardless of the source operand width.
    """
    t = t.astype(jnp.int32)
    mask = (1 << slice_bits) - 1
    planes = [
        jnp.bitwise_and(jnp.right_shift(t, j * slice_bits), mask).astype(jnp.int8)
        for j in range(n_slices - 1)
    ]
    planes.append(jnp.right_shift(t, (n_slices - 1) * slice_bits).astype(jnp.int8))
    return planes


def _dot_i32(a, b):
    return jax.lax.dot_general(
        a, b, (((1,), (0,)), ((), ())), preferred_element_type=jnp.int32
    )


def _radix_accumulate(x_planes, w_planes, slice_bits: int):
    """All plane-pair MXU partials, grouped into i+j radix lanes (PWAB)."""
    lanes: dict[int, jnp.ndarray] = {}
    for i, xp in enumerate(x_planes):
        for j, wp in enumerate(w_planes):
            d = _dot_i32(xp, wp)
            lanes[i + j] = lanes[i + j] + d if (i + j) in lanes else d
    acc = None
    for lane, group in sorted(lanes.items()):
        term = group << (lane * slice_bits) if lane else group
        acc = term if acc is None else acc + term
    return acc


def spoga_gemm_kernel(
    x_ref, w_ref, o_ref, acc_ref, *,
    n_k_tiles: int, n_x_slices: int = 2, n_w_slices: int = 2,
    slice_bits: int = RADIX_BITS,
):
    """One grid step: slice tiles, plane-pair MXU partials, fused radix
    accumulate.  The default (2, 2, 4) configuration is the paper's four
    "wavelengths" with the 16^1 cross terms sharing one radix lane."""

    @pl.when(pl.program_id(2) == 0)
    def _zero_acc():
        acc_ref[...] = jnp.zeros_like(acc_ref)

    xp = _slice_planes_tile(x_ref[...], n_x_slices, slice_bits)
    wp = _slice_planes_tile(w_ref[...], n_w_slices, slice_bits)

    # PWAB: positional weighting fused into the charge accumulation.
    acc_ref[...] += _radix_accumulate(xp, wp, slice_bits)

    @pl.when(pl.program_id(2) == n_k_tiles - 1)
    def _emit():
        o_ref[...] = acc_ref[...]  # the single "ADC" per output tile


@functools.partial(
    jax.jit,
    static_argnames=(
        "block_m", "block_n", "block_k", "interpret",
        "n_x_slices", "n_w_slices", "slice_bits",
    ),
)
def spoga_gemm(
    x: jnp.ndarray,
    w: jnp.ndarray,
    *,
    block_m: int = DEFAULT_BLOCK_M,
    block_n: int = DEFAULT_BLOCK_N,
    block_k: int = DEFAULT_BLOCK_K,
    n_x_slices: int = 2,
    n_w_slices: int = 2,
    slice_bits: int = RADIX_BITS,
    interpret: bool = False,
) -> jnp.ndarray:
    """(M, K) @ (K, N) signed-int -> (M, N) int32, SPOGA fused dataflow.

    Slice counts are per operand: ``(2, 2, 4)`` is the paper's W8A8 kernel,
    ``(2, 1, 4)`` runs int4 weights in one plane (half the MXU partials),
    ``(4, 4, 4)`` carries int16 operands on the same 4-bit hardware model.
    """
    if x.dtype not in (jnp.int8, jnp.int16) or w.dtype not in (jnp.int8, jnp.int16):
        raise TypeError(f"spoga_gemm expects int8/int16 operands, got {x.dtype}, {w.dtype}")
    m, k = x.shape
    k2, n = w.shape
    assert k == k2, f"contraction mismatch {k} vs {k2}"

    bm, bn, bk = min(block_m, m), min(block_n, n), min(block_k, k)
    # Pad to tile multiples; zero padding is exact for integer GEMM.
    pm, pn, pk = (-m) % bm, (-n) % bn, (-k) % bk
    xp = jnp.pad(x, ((0, pm), (0, pk))) if (pm or pk) else x
    wp = jnp.pad(w, ((0, pk), (0, pn))) if (pk or pn) else w
    gm, gn, gk = xp.shape[0] // bm, wp.shape[1] // bn, xp.shape[1] // bk

    out = pl.pallas_call(
        functools.partial(
            spoga_gemm_kernel, n_k_tiles=gk, n_x_slices=n_x_slices,
            n_w_slices=n_w_slices, slice_bits=slice_bits,
        ),
        grid=(gm, gn, gk),
        in_specs=[
            pl.BlockSpec((bm, bk), lambda i, j, kk: (i, kk)),
            pl.BlockSpec((bk, bn), lambda i, j, kk: (kk, j)),
        ],
        out_specs=pl.BlockSpec((bm, bn), lambda i, j, kk: (i, j)),
        out_shape=jax.ShapeDtypeStruct((xp.shape[0], wp.shape[1]), jnp.int32),
        scratch_shapes=[pltpu.VMEM((bm, bn), jnp.int32)],
        compiler_params=CompilerParams(
            dimension_semantics=("parallel", "parallel", "arbitrary"),
        ),
        interpret=interpret,
    )(xp, wp)
    return out[:m, :n] if (pm or pn) else out
