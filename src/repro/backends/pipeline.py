"""The quantized-linear pipeline: quantize -> GEMM -> dequant, one place.

Every quantized matmul in the model hot path lands here.  The pipeline

1. dynamically quantizes activations per row and weights per output channel
   to the spec's bit widths (int8 storage up to 8 bits, int16 above),
2. flattens leading batch dims ONCE into the (M, K) layout the kernels
   expect,
3. runs the resolved backend — preferring its fused ``gemm_dequant`` (the
   paper's single-ADC-per-output semantics: no (M, N) int32 intermediate
   ever reaches HBM) and composing ``gemm`` + jnp epilogue otherwise,
4. restores the leading dims.

The old per-layer re-implementations (``models/layers._int8_forward``,
the dict dispatch in ``core/spoga.quantized_matmul`` and ``kernels/ops``)
are gone; they all route through here / the registry now.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp

from repro.backends import impls  # noqa: F401  (populates the registry)
from repro.backends.registry import resolve_backend
from repro.backends.spec import parse_quant_mode
from repro.obs import watchdog as _watchdog
from repro.runtime.sharding import ambient_mesh

__all__ = ["dynamic_quant", "effective_bits", "quantized_linear", "gemm_int"]

ACC_BITS = 32  # the kernels accumulate in int32 (paper: >=16-bit accumulation)


def dynamic_quant(x: jnp.ndarray, axis, bits: int = 8):
    """Symmetric dynamic quantization to ``bits`` (int8/int16 storage).

    Returns ``(q, scale)`` with ``x ~= q * scale``; clips to ±(2^(bits-1)-1)
    so every value honors the slicing budget (e.g. int4 weights stay in
    [-7, 7] and pass through a single 4-bit plane unchanged).  Thin wrapper
    over :func:`repro.quant.qtensor.quantize` — the quantization arithmetic
    lives in exactly one place.

    Tensor-parallel note: under pjit the per-row ``amax`` reduction over a
    "model"-sharded K axis lowers to a cross-device collective (pjit's
    global-view semantics), so per-row scales are GLOBALLY exact — every
    device quantizes its K-slice against the same scale, and the partial
    int32 accumulators psum into exactly what an unsharded quantized GEMM
    would produce.  Column-parallel (N-sharded) weights are even simpler:
    each device owns whole output columns, so weight scales never cross
    devices.  No sharding-specific code is needed here; this is why the
    tp=1 engine is bitwise and tp>1 differs only by float reduction order
    in the row-parallel psums.
    """
    from repro.quant.qtensor import quantize  # lazy: keeps layering one-way

    q = quantize(x, axis=axis, bits=bits)
    return q.data, q.scale


def effective_bits(spec, k: int) -> tuple[int, int]:
    """Accumulator-aware operand widths for a K-length contraction.

    A product of a ``a``-bit and a ``w``-bit operand spans ``a + w - 2``
    magnitude bits; summing K of them adds ``ceil(log2 K)`` more.  To keep
    the int32 accumulator exact (no mod-2^32 wrap) the effective widths are
    shrunk — largest first — until ``a + w + ceil(log2 K) <= 33``.  W8A8
    is untouched for every realistic K (it would take K > 2^17 to bind);
    ``w16a16`` lands at e.g. 14+13 bits for K = 64 — still far finer than
    int8, which is the point of the wide mode.  Storage dtype and the
    slicing plan keep following the *nominal* spec (values simply occupy
    fewer of the planes' bits).
    """
    headroom = (k - 1).bit_length() if k > 1 else 0  # ceil(log2 k)
    budget = ACC_BITS + 1 - headroom                 # a + w <= 33 - log2(K)
    a, w = spec.a_bits, spec.w_bits
    while a + w > budget and (a > 2 or w > 2):
        if a >= w and a > 2:
            a -= 1
        else:
            w -= 1
    return a, w


def _stage_watchdog_stats(label: str, quant_mode: str, xf, wf, xq, xs, wq,
                          a_bits: int, w_bits: int, nominal: int) -> None:
    """Stage this GEMM's numerics stats out of the jit via debug.callback.

    Everything is computed in-graph (no host sync; the callback is an
    effectful side output that does not feed the computation, so enabling
    it cannot change results):

    - at-rail occupancy of both quantized operands (``rail_hits``),
    - activation ``amax`` and mean relative quantization error,
    - an accumulator-magnitude bound in bits: ``max_row sum_k |xq|``
      times ``max |wq|`` is the largest int32 any output element can
      reach, so ``log2`` of it against the 31 usable magnitude bits is
      the live headroom the ``effective_bits`` clamp guarantees
      statically (the fused gemm_dequant path never materializes the
      accumulator, so this bound is the only runtime view of it).
    """
    from repro.quant.qtensor import rail_hits

    xqf = xq.astype(jnp.float32)
    deq = xqf * xs
    abs_mean = jnp.mean(jnp.abs(xf))
    stats = jnp.stack([
        rail_hits(xq, a_bits).astype(jnp.float32),
        rail_hits(wq, w_bits).astype(jnp.float32),
        jnp.float32(xq.size),
        jnp.float32(wq.size),
        jnp.max(jnp.abs(xf)),
        jnp.mean(jnp.abs(xf - deq)) / (abs_mean + 1e-12),
        jnp.log2(1.0 + jnp.max(jnp.sum(jnp.abs(xqf), axis=-1))
                 * jnp.max(jnp.abs(wq.astype(jnp.float32)))),
        jnp.float32(nominal - (a_bits + w_bits)),
    ])
    jax.debug.callback(
        functools.partial(_watchdog.record, label, quant_mode), stats)


def quantized_linear(
    x: jnp.ndarray,
    w: jnp.ndarray,
    quant_mode: str,
    *,
    backend: Optional[str] = None,
    out_dtype=None,
    watch: Optional[bool] = None,
    layer: Optional[str] = None,
    tp: Optional[str] = None,
) -> jnp.ndarray:
    """x (..., K) fp @ w (K, N) fp -> (..., N) fp via the quantized pipeline.

    ``tp`` ("col" | "row" | None) names the weight's tensor-parallel layout;
    it only matters for a Pallas backend traced under a mesh, where the
    kernel runs inside ``shard_map`` (``shard/kernels.py``).

    ``watch``/``layer`` drive the numerics watchdog explicitly; by
    default the ambient trace-time context set by the model entry points
    (``watchdog.watching``, keyed off ``ModelConfig.numerics_watchdog``)
    decides, so the ~60 model call sites need no extra plumbing.
    """
    b, spec = resolve_backend(quant_mode, backend)
    a_bits, w_bits = effective_bits(spec, x.shape[-1])
    xf = x.astype(jnp.float32)
    xq, xs = dynamic_quant(xf, axis=-1, bits=a_bits)
    # the weight's per-call re-quantization, named for the device trace
    with jax.named_scope("weight_quant"):
        wf = w.astype(jnp.float32)
        wq, ws = dynamic_quant(wf, axis=0, bits=w_bits)

    ctx = _watchdog.trace_ctx()
    if watch if watch is not None else ctx is not None:
        label = layer or _watchdog.next_label(
            ctx, x.shape[-1], w.shape[-1])
        _stage_watchdog_stats(label, quant_mode, xf, wf, xq, xs, wq,
                              a_bits, w_bits, spec.a_bits + spec.w_bits)

    xq = xq.astype(spec.a_dtype)
    wq = wq.astype(spec.w_dtype)

    lead = xq.shape[:-1]
    k = xq.shape[-1]
    n = wq.shape[-1]
    x2 = xq.reshape(-1, k)
    xs2 = xs.reshape(-1, 1)
    ws2 = ws.reshape(1, n)
    mesh = ambient_mesh() if b.pallas else None
    if mesh is not None:
        from repro.shard.kernels import sharded_gemm_dequant  # lazy: layering

        out = sharded_gemm_dequant(b, x2, wq, xs2, ws2, spec, mesh, tp)
    elif b.gemm_dequant is not None:
        out = b.gemm_dequant(x2, wq, xs2, ws2, spec)
    else:
        out = b.gemm(x2, wq, spec).astype(jnp.float32) * xs2 * ws2
    out = out.reshape(*lead, n)
    return out.astype(out_dtype if out_dtype is not None else x.dtype)


def gemm_int(
    x_q: jnp.ndarray,
    w_q: jnp.ndarray,
    *,
    quant_mode: str = "int8_spoga",
    backend: Optional[str] = None,
) -> jnp.ndarray:
    """Already-quantized (..., K) @ (K, N) -> (..., N) int32 accumulator.

    Leading batch dims are flattened around the backend call (the Pallas
    kernels are strictly 2-D); the jnp backends would broadcast natively but
    take the same path for uniformity.
    """
    b, spec = resolve_backend(quant_mode, backend)
    lead = x_q.shape[:-1]
    k = x_q.shape[-1]
    acc = b.gemm(x_q.reshape(-1, k), w_q, spec)
    return acc.reshape(*lead, w_q.shape[-1])


def quant_mode_summary(quant_mode: str) -> str:
    """Human-readable one-liner for logs/benchmarks: 'w4a8: 2x1 4b planes'."""
    spec, family = parse_quant_mode(quant_mode)
    return (f"{quant_mode}: {family}, a{spec.a_bits}/w{spec.w_bits}, "
            f"{spec.n_a_slices}x{spec.n_w_slices} planes of {spec.slice_bits}b")
