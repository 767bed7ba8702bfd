"""The paged-attention kernel's share of its roofline over the traced
slice: the int8 K/V rows and f32 scales of every served lane's context
(the keys each decoded token attends, every layer) at the HBM rate, over
the device time of ``paged_attention``'s events."""

from chipbench import accounting
from chipbench.trace_reduce import kernel_seconds


def read(rec):
    if rec["trace"] is None:
        return None
    spent, n = kernel_seconds(rec["trace"], "paged_attention")
    if n == 0:
        return None
    return accounting.share(accounting.paged_attn_least_seconds(rec, *rec["slice"]), spent)
