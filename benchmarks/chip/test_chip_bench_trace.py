"""The trace reduction: busy time, per-op time, kernel time and labelled
idle gaps, on hand-made events and on a small trace recorded on a TPU v5e
(``testdata/slice.xplane.pb``, made by ``record_testdata.py``).  CPU only."""

from pathlib import Path

import pytest

from chipbench import trace_reduce as tr

CHIP = Path(__file__).resolve().parent
MS = 1_000_000  # ns


def _host():
    return [("measured_slice", 10 * MS, 20 * MS),
            ("step", 10 * MS, 14 * MS),
            ("admit", 14 * MS, 15 * MS),
            ("step", 15 * MS, 19 * MS),
            ("token_cb", 18 * MS, 19 * MS)]


def _device():
    # an op that starts before the slice, overlapping ops, a kernel, and an
    # op after the slice
    return {0: [("fusion.1", 9 * MS, 11 * MS, ""),
                ("fusion.2", 11 * MS, 12 * MS, "quantize"),
                ("custom-call.7", 11 * MS + MS // 2, 13 * MS,
                 "jit(step)/spoga_gemm_dequant/pallas_call"),
                ("fusion.1", 16 * MS, 17 * MS, ""),
                ("fusion.3", 21 * MS, 22 * MS, "")]}


def test_busy_is_the_union_of_op_intervals_inside_the_slice():
    r = tr.reduce_events(_host(), _device(), chips=1)
    assert r["window_s"] == pytest.approx(0.010)
    # [10, 13) and [16, 17): 4 ms busy of 10
    assert r["busy_s"] == pytest.approx(0.004)
    assert r["ops_s"]["fusion"] == pytest.approx(0.002 + 0.001)
    assert r["ops_s"]["custom-call"] == pytest.approx(0.0015)
    assert tr.kernel_seconds(r, "spoga_gemm_dequant") == (pytest.approx(0.0015), 1)
    assert tr.kernel_seconds(r, "paged_attention") == (0.0, 0)


def test_idle_gaps_carry_the_harness_annotation_around_them():
    r = tr.reduce_events(_host(), _device(), chips=1)
    # gaps: [13, 16) mid 14.5 -> admit; [17, 20) mid 18.5 -> token_cb
    assert sorted(r["idle_gaps"]) == sorted([["admit", pytest.approx(0.003)],
                                             ["token_cb", pytest.approx(0.003)]])
    assert tr.label_at(_host(), 19.5 * MS) == "between_steps"


def test_a_trace_without_the_slice_is_refused():
    with pytest.raises(ValueError):
        tr.reduce_events(_host()[1:], _device(), chips=1)


def test_op_names_group_by_hlo_name_and_output_type():
    assert tr._op_key("fusion.12") == "fusion"
    assert tr._op_key("%fusion.148 = s8[14336,5120]{1,0:T(8,128)} fusion(bf16[10]"
                      " %p), kind=kLoop") == "fusion s8[14336,5120]"
    assert tr._op_key("%spoga_gemm_dequant.54 = f32[4096,14336]{1,0} custom-call("
                      ")") == "spoga_gemm_dequant f32[4096,14336]"


def test_per_op_time_is_self_time_of_nested_ops():
    # a loop op holding two kernels: only its gaps (3 ms) are its own
    evs = [("while.3", 0, 10 * MS, ""), ("custom-call.1", 1 * MS, 4 * MS, ""),
           ("fusion.2", 5 * MS, 9 * MS, ""), ("copy.4", 11 * MS, 12 * MS, "")]
    assert tr.self_times(evs) == [3 * MS, 3 * MS, 4 * MS, 1 * MS]
    host = [("measured_slice", 0, 20 * MS)]
    r = tr.reduce_events(host, {0: evs}, chips=1)
    assert r["busy_s"] == pytest.approx(0.011)
    assert sum(r["ops_s"].values()) == pytest.approx(r["busy_s"])
    assert r["top_ops"][0] == ["fusion", pytest.approx(0.004)]


def test_union_merges_overlaps():
    assert tr.union([(5, 7), (1, 3), (2, 4), (7, 8)]) == [[1, 4], [5, 8]]


def test_the_recorded_v5e_trace():
    """Three ``step``s of the int8 GEMM kernel, each followed by a 20-ms
    ``await_arrival`` sleep, inside the ``measured_slice``."""
    r = tr.reduce_trace(str(CHIP / "testdata"), chips=1)
    assert 0.06 < r["window_s"] < 0.2
    assert 0 < r["busy_s"] < 0.01 * r["window_s"]
    assert r["top_ops"][0][0] == "spoga_gemm_dequant f32[32,2048]"
    assert sum(r["ops_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
    spent, n = tr.kernel_seconds(r, "spoga_gemm_dequant")
    assert n >= 2 and spent == pytest.approx(r["ops_s"]["spoga_gemm_dequant f32[32,2048]"])
    longest = r["idle_gaps"][:3]
    assert [label for label, _ in longest] == ["await_arrival"] * 3
    assert all(0.019 < s < 0.03 for _, s in longest)
