"""GemmBackend registry: one pluggable home for quantize -> GEMM -> dequant.

A backend owns the integer GEMM (and optionally the fused dequantizing
epilogue) for a :class:`~repro.backends.spec.QuantSpec`.  Registration is
global and name-keyed; resolution order for a quantized linear is

1. an explicit ``backend=`` override (threaded from ``ModelConfig
   .gemm_backend`` / the launch ``--gemm-backend`` flag),
2. the process-wide default set via :func:`set_default_backend`,
3. auto-selection by dataflow family and ``jax.default_backend()``:
   TPU runs the fused Pallas kernels, everything else the algebraic jnp
   twins (the Pallas interpreter stays available as ``pallas_interpret``).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Optional

import jax

from repro.backends.spec import QuantSpec, parse_quant_mode

__all__ = [
    "GemmBackend",
    "register_backend",
    "get_backend",
    "list_backends",
    "resolve_backend",
    "set_default_backend",
    "get_default_backend",
]


@dataclasses.dataclass(frozen=True)
class GemmBackend:
    """One GEMM execution strategy.

    ``gemm(x_q, w_q, spec) -> int32 (M, N)`` is mandatory and operates on
    already-quantized 2-D operands.  ``gemm_dequant(x_q, w_q, x_scale,
    w_scale, spec) -> f32 (M, N)`` is the fused epilogue; when absent the
    pipeline composes ``gemm`` with a jnp epilogue (same math, one extra
    (M, N) int32 round trip — exactly what the fused kernels avoid).
    ``supports(spec)`` gates specs the strategy cannot express (e.g. the
    materialized DEAS Pallas baseline is pinned to the paper's 2x4b W8A8).
    ``pallas`` marks a Mosaic kernel: under a mesh it runs inside
    ``shard_map`` (automatic partitioning cannot split it).
    """

    name: str
    family: str                      # "spoga" | "deas" | "direct"
    gemm: Callable
    gemm_dequant: Optional[Callable] = None
    supports: Callable[[QuantSpec], bool] = lambda spec: True
    description: str = ""
    pallas: bool = False


_REGISTRY: dict[str, GemmBackend] = {}
_DEFAULT_BACKEND: Optional[str] = None


def register_backend(backend: GemmBackend, *, override: bool = False) -> GemmBackend:
    if backend.name in _REGISTRY and not override:
        raise ValueError(f"backend {backend.name!r} already registered")
    _REGISTRY[backend.name] = backend
    return backend


def get_backend(name: str) -> GemmBackend:
    try:
        return _REGISTRY[name]
    except KeyError:
        raise KeyError(
            f"unknown GEMM backend {name!r}; registered: {sorted(_REGISTRY)}"
        ) from None


def list_backends() -> tuple[str, ...]:
    return tuple(sorted(_REGISTRY))


def set_default_backend(name: Optional[str]) -> None:
    """Process-wide override (launch scripts call this from --gemm-backend).

    ``None`` restores family/platform auto-selection.  Set this before
    building jitted step functions: the choice is baked in at trace time.
    """
    global _DEFAULT_BACKEND
    if name is not None:
        get_backend(name)  # validate eagerly
    _DEFAULT_BACKEND = name


def get_default_backend() -> Optional[str]:
    return _DEFAULT_BACKEND


def _auto_name(family: str) -> str:
    on_tpu = jax.default_backend() == "tpu"
    if family == "direct":
        return "direct"
    if family == "deas":
        return "pallas_deas" if on_tpu else "jnp_deas"
    if family == "spoga":
        return "pallas_spoga_dequant" if on_tpu else "jnp_spoga"
    raise ValueError(f"unknown dataflow family {family!r}")


def resolve_backend(
    quant_mode: str, backend: Optional[str] = None
) -> tuple[GemmBackend, QuantSpec]:
    """(mode string, optional override) -> (backend, spec), validated."""
    spec, family = parse_quant_mode(quant_mode)
    name = backend or _DEFAULT_BACKEND or _auto_name(family)
    b = get_backend(name)
    if not b.supports(spec):
        raise ValueError(
            f"backend {b.name!r} does not support quant mode {quant_mode!r} "
            f"(spec {spec}); pick one of {list_backends()}"
        )
    return b, spec
