"""The engine's spans read from a profiler trace (``chipbench/host_spans.py``)
and the device ops' HLO scopes (``chipbench/op_scopes.py``), on hand-made
events, on the harness's recorded v5e trace (``testdata/slice.xplane.pb``)
and on a small trace of the program's engine recorded on a TPU v5e
(``enginedata/engine_slice.xplane.pb``, made by
``record_engine_testdata.py``).  CPU only."""

from pathlib import Path

import pytest

from chipbench import host_spans as hs
from chipbench import op_scopes
from chipbench import trace_reduce as tr

CHIP = Path(__file__).resolve().parent
ENGINE_TRACE = CHIP / "enginedata" / "engine_slice.xplane.pb"
MS = 1_000_000  # ns


def _host():
    # the harness's step around an engine step: schedule, page tables,
    # the decode's enqueue, the token pull and the delivery (with the
    # harness's streaming callback inside it)
    return [("measured_slice", 0, 40 * MS),
            ("step", 1 * MS, 20 * MS),
            ("engine.step", 1 * MS, 19 * MS),
            ("engine.schedule", 1 * MS, 2 * MS),
            ("engine.page_tables", 2 * MS, 6 * MS),
            ("engine.decode", 6 * MS, 7 * MS),
            ("engine.token_pull", 7 * MS, 15 * MS),
            ("engine.token_deliver", 15 * MS, 19 * MS),
            ("token_cb", 16 * MS, 17 * MS),
            ("step", 21 * MS, 30 * MS),
            ("engine.step", 21 * MS, 29 * MS),       # no decode: an admission
            ("engine.first_token_pull", 22 * MS, 28 * MS),
            ("await_arrival", 30 * MS, 40 * MS)]


def _device():
    # busy while the decode runs behind the token pull; idle in the page
    # tables, the delivery and the sleep
    return {0: [("fusion.1", 0, 2 * MS, ""),
                ("custom-call.2", 7 * MS, 14 * MS, "jit(_decode)/weight_quant/round"),
                ("fusion.3", 22 * MS, 27 * MS, "")]}


def test_idle_gaps_name_the_innermost_engine_phase():
    host = _host()
    assert hs.label_at(host, 4 * MS) == "engine.page_tables"
    assert hs.label_at(host, 16.5 * MS) == "token_cb"
    assert hs.label_at(host, 18 * MS) == "engine.token_deliver"
    assert hs.label_at(host, 20.5 * MS) == "between_steps"
    assert hs.label_at(host, 19.5 * MS) == "step"
    assert hs.label_at(host, 35 * MS) == "await_arrival"
    gaps = [[hs.label_at(host, (a + b) / 2), (b - a) / 1e9]
            for a, b in hs.idle_intervals(host, _device())]
    # [2, 7) page tables + decode enqueue (mid 4.5), [14, 22) delivery and
    # the harness (mid 18), [27, 40) the sleep (mid 33.5)
    assert gaps == [["engine.page_tables", pytest.approx(0.005)],
                    ["engine.token_deliver", pytest.approx(0.008)],
                    ["await_arrival", pytest.approx(0.013)]]
    # every idle ms, split where an annotation starts or ends
    by_label = hs.idle_by_label(host, _device())
    assert by_label == {
        "await_arrival": pytest.approx(0.010), "engine.page_tables": pytest.approx(0.004),
        "engine.token_deliver": pytest.approx(0.003), "step": pytest.approx(0.002),
        "engine.step": pytest.approx(0.002), "engine.decode": pytest.approx(0.001),
        "engine.token_pull": pytest.approx(0.001), "token_cb": pytest.approx(0.001),
        "between_steps": pytest.approx(0.001), "engine.first_token_pull": pytest.approx(0.001)}
    assert list(by_label)[:2] == ["await_arrival", "engine.page_tables"]
    # the harness's own labelling of the same gaps is unchanged
    r = tr.reduce_events([h for h in host if not h[0].startswith("engine.")],
                         _device(), chips=1)
    assert sorted(label for label, _ in r["idle_gaps"]) == ["await_arrival", "step", "step"]


def test_host_time_per_decode_step_leaves_out_the_blocking_waits():
    spans = hs.host_spans(_host())
    assert spans["slice"] == [0, 40 * MS]
    assert {s[0] for s in spans["spans"]} == {
        "engine.step", "engine.schedule", "engine.page_tables", "engine.decode",
        "engine.token_pull", "engine.token_deliver", "engine.first_token_pull"}
    # one decode step: 18 ms less its 8-ms token pull; the admission step
    # holds no decode
    assert hs.host_ms_per_step(spans) == pytest.approx(10.0)
    phases = hs.phase_seconds(spans)
    assert phases["engine.step"] == {"s": pytest.approx(0.026), "n": 2}
    # a step that the slice cuts is not priced, nor a program without spans
    cut = hs.host_spans([("measured_slice", 5 * MS, 40 * MS)] + _host()[1:])
    assert hs.host_ms_per_step(cut) is None
    bare = hs.host_spans([h for h in _host() if not h[0].startswith("engine.")])
    assert bare["spans"] == [] and hs.host_ms_per_step(bare) is None


def test_scope_share_prices_the_ops_under_a_named_scope():
    r = tr.reduce_events(_host(), _device(), chips=1)
    scopes = {"custom-call.2": "jit(_decode)/weight_quant/round:", "fusion.1": "jit(_decode)/add:"}
    # 7 of the 14 busy ms
    assert op_scopes.scope_share(r, scopes, "weight_quant") == pytest.approx(50.0)
    assert op_scopes.scope_share(r, {}, "weight_quant") is None


def test_op_scopes_read_the_recorded_v5e_trace(tmp_path):
    """The harness's trace: each op's ``tf_op`` sits in its event
    metadata, which ``ProfileData`` does not give."""
    path = CHIP / "testdata" / "slice.xplane.pb"
    scopes = op_scopes.read_scopes(str(path))
    gemm = [op for name, op in scopes.items() if name.startswith("%spoga_gemm_dequant")]
    assert gemm == ["jit(spoga_gemm_dequant)/jit(spoga_gemm_dequant)/pallas_call:"]
    r = tr.reduce_trace(str(CHIP / "testdata"), chips=1)
    share = op_scopes.scope_share(r, scopes, "pallas_call")
    assert share == pytest.approx(100 * tr.kernel_seconds(r, "spoga_gemm_dequant")[0] / r["busy_s"])
    # pruned to the device's ops, the host's events and each op's tf_op,
    # the trace reduces the same
    raw = path.read_bytes()
    assert b"".join(op_scopes._encode(*f) for f in op_scopes._wire_fields(raw)) == raw
    small = tmp_path / "small.xplane.pb"
    small.write_bytes(op_scopes.prune(raw, lambda name: name in ("/device:TPU:0", "/host:CPU"),
                                      lambda line: line not in ("XLA Modules", "Async XLA Ops"),
                                      {op_scopes.TF_OP}))
    assert small.stat().st_size < len(raw) // 2
    r_small = tr.reduce_trace(str(tmp_path), chips=1)
    for key in ("window_s", "busy_s", "ops_s", "top_ops", "idle_gaps"):
        assert r_small[key] == r[key], key
    assert sorted(op_scopes.read_scopes(str(small)).values()) == sorted(
        op for name, op in scopes.items() if name.startswith("%"))


def test_the_recorded_engine_trace_holds_the_engine_phases():
    """Three engine steps of two decoding lanes inside the slice, each
    followed by a 5-ms sleep."""
    host, devices = tr.read_events(str(ENGINE_TRACE))
    spans = hs.host_spans(host)
    steps = [(a, b) for name, a, b in spans["spans"] if name == hs.STEP]
    assert len(steps) == 3
    phases = {"engine.schedule", "engine.page_tables", "engine.decode",
              "engine.token_pull", "engine.token_deliver"}
    for a, b in steps:
        assert phases <= {n for n, x, y in spans["spans"] if a <= x and y <= b}
    ms = hs.host_ms_per_step(spans)
    assert 0 < ms < min(b - a for a, b in steps) / 1e6
    gaps = sorted(((b - a) / 1e9, hs.label_at(host, (a + b) / 2))
                  for a, b in hs.idle_intervals(host, devices))[::-1]
    assert [label for _, label in gaps[:3]] == ["await_arrival"] * 3
    assert all(0.005 < s < 0.01 for s, _ in gaps[:3])
    assert list(hs.idle_by_label(host, devices))[0] == "await_arrival"
    # the harness's reduction keeps the same spans
    assert tr.reduce_trace(str(ENGINE_TRACE.parent), 1)["host_spans"] == spans


def test_the_recorded_engine_trace_names_the_requantization():
    host, devices = tr.read_events(str(ENGINE_TRACE))
    r = tr.reduce_events(host, devices, chips=1)
    scopes = op_scopes.read_scopes(str(ENGINE_TRACE))
    quant = {tr._op_key(name) for name, op in scopes.items() if "weight_quant" in op}
    # the weight's amax, its scale and its int8 codes
    assert any(key.startswith("fusion s8[") for key in quant), quant
    assert 0 < op_scopes.scope_share(r, scopes, "weight_quant") < 100
    # the harness's own reduction reads the same trace as before
    assert tr.kernel_seconds(r, "spoga_gemm_dequant")[1] > 0
    assert tr.kernel_seconds(r, "paged_attention")[1] > 0
    assert sum(r["ops_s"].values()) == pytest.approx(r["busy_s"], rel=1e-6)
