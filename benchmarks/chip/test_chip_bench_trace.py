"""The trace reduction: busy time, per-op time, kernel time, labelled
idle gaps, op scopes and engine spans, on hand-made events, on a small
trace recorded on a TPU v5e (``testdata/slice.xplane.pb``, made by
``record_testdata.py``) and on the engine's (``enginedata/``), and the
per-layer readers over them.  CPU only."""

import hashlib
import importlib
import json
from pathlib import Path

import pytest

from chipbench import accounting as acc
from chipbench import spec
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


# What the reduction gave on the two recorded traces before it kept scopes
# and engine spans: sha256 of the JSON of its fields (``_digest``), and
# what ``spans.py``'s side reduction read from them then
RECORDED = {
    "testdata": {"digest": "a7dbb53576c1579f1112f45111ec12c9e7544d883fe1708d22bf90d4a51b015c",
                 "n_events": 6, "busy_s": 2.2946e-05,
                 "requant_share.chat": None, "host_ms_per_step.chat": None},
    "enginedata": {"digest": "46596f5fc9375d3a97cc22e552bed8fe4fa941ea8834d557465254d95e0c080a",
                   "n_events": 266, "busy_s": 8.6305e-05,
                   "requant_share.chat": 1.5676959619952495, "host_ms_per_step.chat": 0.64596}}
FIELDS = ("window_s", "busy_s", "ops_s", "events", "top_ops", "idle_gaps")


def _digest(r):
    blob = json.dumps({k: r[k] for k in FIELDS}, sort_keys=True)
    return hashlib.sha256(blob.encode()).hexdigest()


@pytest.mark.parametrize("trace", sorted(RECORDED))
def test_the_reduction_keeps_scopes_and_spans_beside_what_it_read_before(trace):
    r = tr.reduce_trace(str(CHIP / trace), chips=1)
    want = RECORDED[trace]
    assert _digest(r) == want["digest"]
    assert len(r["events"]) == want["n_events"] and r["busy_s"] == want["busy_s"]
    assert set(r) == set(FIELDS) | {"scopes", "host_spans"}
    # every device op of the slice has its scope; a kernel is still found
    # by its name or HLO text alone
    assert {name for name, _, _ in r["events"]} <= set(r["scopes"])
    assert tr.kernel_seconds(r, "weight_quant")[1] == 0
    if trace == "enginedata":
        assert {name for name, _, _ in r["host_spans"]["spans"]} >= {
            "engine.step", "engine.decode", "engine.token_pull"}
    else:
        assert r["host_spans"]["spans"] == []


@pytest.mark.parametrize("trace", sorted(RECORDED))
@pytest.mark.parametrize("metric", ["requant_share.chat", "host_ms_per_step.chat"])
def test_the_span_and_scope_readers_read_what_the_side_reduction_read(trace, metric):
    rec = {"trace": tr.reduce_trace(str(CHIP / trace), chips=1)}
    assert spec.load_reader(metric)(rec) == RECORDED[trace][metric]
    assert spec.load_reader(metric)({"trace": None}) is None


NEMO = json.loads((CHIP / "configs" / "nemo12b-l10-int8.json").read_text())["model"]
GRANITE = json.loads((CHIP / "configs" / "granite3b-moe-int8.json").read_text())["model"]
PEAKS = json.loads((CHIP / "peaks.json").read_text())["devices"]["TPU v5 lite"]


def _synthetic(trace, fam, m):
    """A run's record around a recorded trace: 78 decode steps and a
    prefill step in the slice, 30 requests in the window.  The steps are
    not those of the trace, so a share read from it is arithmetic only."""
    steps = [{"t0": 146.0 + 0.05 * i, "t1": 146.048 + 0.05 * i, "decode": 1,
              "oneshot_prompts": [], "chunks": 0,
              "decode_keys": [600 + 7 * j + i for j in range(21)]} for i in range(78)]
    steps.append({"t0": 149.90, "t1": 149.99, "decode": 0, "oneshot_prompts": [600, 40],
                  "chunks": 0, "decode_keys": []})
    reqs = [{"due": 101.0 + j, "prompt_len": 600 + j,
             "prefill_events": [[101.5 + j, 0, 600 + j]],
             "token_times": [101.6 + j + 0.05 * t for t in range(40)]} for j in range(30)]
    return {"model": m, "counts": acc.layer_counts(fam, m), "peaks": PEAKS,
            "gemm_peak_ops": PEAKS["int8_ops_per_s"], "window": [100.0, 150.0],
            "serving": {"n_slots": 32, "prefill_buckets": [64, 1024, 4096],
                        "prefill_chunk": None},
            "requests": reqs, "steps": steps, "slice": [146.0, 150.0], "trace": trace}


# the readers on those records before the counts were kept layer by layer
METRICS = {
    ("testdata", "gqa_dense"): (99.9669691524186, 7.271226641924594, 1478003.654570069, None),
    ("testdata", "gqa_moe"): (99.9669691524186, 2.1964957763043365, 118886.4054587134, None),
    ("enginedata", "gqa_dense"): (99.60378435181177, 7.271226641924594, 15684936.742376242,
                                  66676.41030211211),
    ("enginedata", "gqa_moe"): (99.60378435181177, 2.1964957763043365, 1261651.649765938,
                                109915.05213439086)}
READERS = ("idle_share.chat", "mfu.chat", "spoga_gemm_roofline.chat", "paged_attn_roofline.chat")


@pytest.mark.parametrize("trace, family", sorted(METRICS))
def test_the_metrics_of_the_recorded_traces_read_as_before(trace, family):
    fam = importlib.import_module(f"reference.{family}")
    m = NEMO if family == "gqa_dense" else GRANITE
    rec = _synthetic(tr.reduce_trace(str(CHIP / trace), chips=1), fam, m)
    for name, want in zip(READERS, METRICS[trace, family]):
        got = spec.load_reader(name)(rec)
        assert got == (None if want is None else pytest.approx(want, rel=1e-12)), name
