"""The model step's share of the chip's peak: the window's logical model
work (2 x active matmul parameters per prompt row and decoded token, the
head once per output token, causal attention) over the wall time of the
engine steps in the window, at the peak of the configuration's GEMM
precision (int8: 393 TOP/s)."""

from chipbench import accounting


def read(rec):
    return accounting.mfu(rec, rec["model"], rec["gemm_peak_ops"])
