"""The built-in GEMM backends.

Eight strategies over the same integer arithmetic (all bit-exact vs
``direct_matmul`` — property-tested in tests/test_backends.py):

=========================  =================================================
``jnp_spoga``              fused radix accumulation, pure jnp (CPU/GPU default)
``jnp_deas``               prior-work baseline: materialized slice partials
``direct``                 native int dot_general (the MXU byte path endpoint)
``pallas_spoga``           fused Pallas kernel, int32 out (TPU only)
``pallas_spoga_dequant``   fused Pallas kernel + dequant epilogue (TPU default)
``pallas_deas``            materialized-slice Pallas baseline (TPU only; W8A8)
``pallas_interpret``       the fused kernels run by the Pallas interpreter —
                           how the kernel bodies are tested on CPU
``pallas_deas_interpret``  the DEAS kernels run by the Pallas interpreter
=========================  =================================================

The compiled names raise off-TPU: the interpreter runs only where it is
named, so a run that was meant for the chip never quietly lands on the CPU.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.backends.registry import GemmBackend, register_backend
from repro.backends.spec import DEFAULT_SPEC, QuantSpec
from repro.core import spoga as _spoga
from repro.kernels.deas_gemm import deas_gemm
from repro.kernels.spoga_gemm import spoga_gemm
from repro.kernels.spoga_gemm_dequant import spoga_gemm_dequant


def _require_tpu(name: str, twin: str) -> None:
    platform = jax.default_backend()
    if platform != "tpu":
        raise RuntimeError(
            f"GEMM backend {name!r} compiles a Pallas TPU kernel, but JAX's "
            f"default backend is {platform!r}; use {twin!r} to run the "
            f"kernel body in the Pallas interpreter, or a jnp backend")


# -- pure-jnp dataflows -----------------------------------------------------

def _jnp_sliced(materialize):
    def gemm(x_q, w_q, spec: QuantSpec):
        return _spoga.sliced_matmul(
            x_q, w_q,
            n_x_slices=spec.n_a_slices, n_w_slices=spec.n_w_slices,
            slice_bits=spec.slice_bits, materialize=materialize,
        )
    return gemm


def _direct_gemm(x_q, w_q, spec: QuantSpec):
    return _spoga.direct_matmul(x_q, w_q)


# -- Pallas kernels ---------------------------------------------------------

def _pallas_gemm(name, interpret=False):
    def gemm(x_q, w_q, spec: QuantSpec):
        if not interpret:
            _require_tpu(name, "pallas_interpret")
        return spoga_gemm(
            x_q, w_q,
            n_x_slices=spec.n_a_slices, n_w_slices=spec.n_w_slices,
            slice_bits=spec.slice_bits, interpret=interpret,
        )
    return gemm


def _pallas_gemm_dequant(name, interpret=False):
    def gemm_dequant(x_q, w_q, x_scale, w_scale, spec: QuantSpec):
        if not interpret:
            _require_tpu(name, "pallas_interpret")
        return spoga_gemm_dequant(
            x_q, w_q, x_scale, w_scale,
            n_x_slices=spec.n_a_slices, n_w_slices=spec.n_w_slices,
            slice_bits=spec.slice_bits, interpret=interpret,
        )
    return gemm_dequant


def _pallas_deas_gemm(name, interpret=False):
    def gemm(x_q, w_q, spec: QuantSpec):
        if not interpret:
            _require_tpu(name, "pallas_deas_interpret")
        return deas_gemm(x_q, w_q, interpret=interpret)
    return gemm


def _supports_nibble_planes(spec: QuantSpec) -> bool:
    # The Pallas kernels cast planes to int8 for the MXU byte path.
    return spec.slice_bits <= 7


register_backend(GemmBackend(
    name="jnp_spoga", family="spoga", gemm=_jnp_sliced(materialize=False),
    description="fused radix accumulation, algebraic jnp twin of the kernel",
))
register_backend(GemmBackend(
    name="jnp_deas", family="deas", gemm=_jnp_sliced(materialize=True),
    description="prior-work DEAS: materialized per-slice partial matrices",
))
register_backend(GemmBackend(
    name="direct", family="direct", gemm=_direct_gemm,
    description="native integer dot_general (no slicing; beyond-paper endpoint)",
))
register_backend(GemmBackend(
    name="pallas_spoga", family="spoga", gemm=_pallas_gemm("pallas_spoga"),
    supports=_supports_nibble_planes, pallas=True,
    description="fused SPOGA Pallas kernel, int32 out (TPU only)",
))
register_backend(GemmBackend(
    name="pallas_spoga_dequant", family="spoga",
    gemm=_pallas_gemm("pallas_spoga_dequant"),
    gemm_dequant=_pallas_gemm_dequant("pallas_spoga_dequant"),
    supports=_supports_nibble_planes, pallas=True,
    description="fused SPOGA Pallas kernel with in-kernel dequant epilogue",
))
register_backend(GemmBackend(
    name="pallas_deas", family="deas", gemm=_pallas_deas_gemm("pallas_deas"),
    supports=lambda spec: spec == DEFAULT_SPEC, pallas=True,
    description="materialized-slice Pallas baseline (paper Fig. 2a; W8A8 only)",
))
register_backend(GemmBackend(
    name="pallas_deas_interpret", family="deas",
    gemm=_pallas_deas_gemm("pallas_deas_interpret", interpret=True),
    supports=lambda spec: spec == DEFAULT_SPEC, pallas=True,
    description="the DEAS baseline kernels run by the Pallas interpreter",
))
register_backend(GemmBackend(
    name="pallas_interpret", family="spoga",
    gemm=_pallas_gemm("pallas_interpret", interpret=True),
    gemm_dequant=_pallas_gemm_dequant("pallas_interpret", interpret=True),
    supports=_supports_nibble_planes, pallas=True,
    description="fused dequant kernel run by the Pallas interpreter",
))
