"""The quantized GEMM kernel's share of its roofline over the traced
slice: the least time of the GEMMs the slice's steps dispatched (2 M K N
at the int8 peak, or their int8 operands, scales and f32 output at the HBM
rate, whichever is larger; the GEMM's own work, not its bit-sliced
passes), over the device time of ``spoga_gemm_dequant``'s events."""

from chipbench import accounting
from chipbench.trace_reduce import kernel_seconds


def read(rec):
    if rec["trace"] is None:
        return None
    spent, n = kernel_seconds(rec["trace"], "spoga_gemm_dequant")
    if n == 0:
        return None
    return accounting.share(accounting.gemm_least_seconds(rec, *rec["slice"]), spent)
