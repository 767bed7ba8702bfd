"""Median of every gap between consecutive streamed tokens of every
request due in the window, in milliseconds: the pace at which an answer
streams, which the decode step sets."""

from chipbench import accounting


def read(rec):
    gaps = accounting.itl_values(rec)
    return 1e3 * accounting.percentile(gaps, 50) if gaps else None
