"""``BENCHMARK.json`` and the files it names agree: every cell finds its
configuration, reference family, traffic mix and metric readers by name,
and every per-layer metric moves an end-to-end metric its cells report.
CPU only."""

import json
import re
from pathlib import Path

import pytest

from chipbench import spec, traffic

CHIP = Path(__file__).resolve().parent
BENCH = json.loads((CHIP.parents[1] / "BENCHMARK.json").read_text())
CELLS = [w["name"] for w in BENCH["workloads"]]
NAME = re.compile(r"[A-Za-z0-9_][A-Za-z0-9_.-]{0,63}")


@pytest.mark.parametrize("name", CELLS)
def test_cell_finds_its_pieces_by_name(name):
    cell = spec.load_cell(name)
    assert cell.traffic["kind"] in ("open_loop", "backlog")
    assert "check" in cell.config and cell.config["check"]["max_logit_gap"] > 0
    for fn in ("layer_specs", "layer", "layer_params", "kernel_gemms"):
        assert callable(getattr(cell.reference, fn)), fn
    for fn in ("model_config", "to_program"):
        assert callable(getattr(cell.layout, fn)), fn
    assert callable(cell.program_params)
    # the deployment's lanes hold the mix's longest request
    serving = cell.serving
    assert serving["n_slots"] >= 1
    assert (traffic.max_prompt_len(cell.traffic) + traffic.max_output_len(cell.traffic)
            <= serving["cache_len"])
    if cell.traffic["kind"] == "open_loop" and serving["prefill_buckets"]:
        assert max(serving["prefill_buckets"]) >= traffic.max_prompt_len(cell.traffic)
    for trace in (False, True):
        entries = cell.metric_entries(trace)
        assert entries, (name, trace)
        for entry in entries:
            assert callable(spec.load_reader(entry["name"]))
    reported = {m["name"] for m in cell.metric_entries(False)}
    assert "setup_s" in reported and len(reported) >= 2
    for m in cell.metric_entries(True):
        assert m["moves"] in reported, (name, m["name"])


def test_names_and_reduced_keys():
    for section in ("configs", "workloads", "end_to_end", "per_layer"):
        for entry in BENCH[section]:
            assert NAME.fullmatch(entry["name"]), entry["name"]
    for c in BENCH["configs"]:
        conf = json.loads((CHIP.parents[1] / c["file"]).read_text())
        assert sorted(conf["reduced"]) == sorted(c["reduced"])
        for key, cut in conf["reduced"].items():
            assert conf["model"][key] == cut["run"] != cut["published"]


def test_unknown_device_kind_is_an_error():
    assert spec.peaks_for("TPU v5 lite")["int8_ops_per_s"] == 393e12
    with pytest.raises(SystemExit):
        spec.peaks_for("cpu")


def test_benchmark_file_keeps_to_its_schema():
    assert set(BENCH) == {"command", "paths", "run_seconds", "configs", "workloads",
                          "end_to_end", "per_layer"}
    assert 1 <= len(BENCH["paths"]) <= 16 and len(BENCH["command"]) <= 32
    assert isinstance(BENCH["run_seconds"], int) and 1 <= BENCH["run_seconds"] <= 51
    # a full check of 24 cells fits its 12 hours
    runs = 2 + 14 * 24
    assert runs * (BENCH["run_seconds"] + 60) + 24 * 2 * 90 + 1200 <= 43200
    unit = re.compile(r"[A-Za-z0-9_/%.-]{1,16}")
    for c in BENCH["configs"]:
        assert set(c) == {"name", "source", "file", "reduced", "why"}
        assert c["file"].startswith(tuple(p + "/" for p in BENCH["paths"]))
        assert len(c["reduced"]) <= 16 and all(NAME.fullmatch(k) for k in c["reduced"])
    pairs = [(w["config"], w["traffic"]) for w in BENCH["workloads"]]
    assert len(set(pairs)) == len(pairs)
    for w in BENCH["workloads"]:
        assert set(w) == {"name", "config", "traffic", "chips", "why"}
        assert w["chips"] in (1, 4) and 1 <= len(w["why"]) <= 200
        assert NAME.fullmatch(w["config"]) and NAME.fullmatch(w["traffic"])
    for m in BENCH["end_to_end"]:
        assert set(m) <= {"name", "unit", "better", "bound", "source", "workloads"}
        assert m["source"] in ("host_clock", "device_trace")
        assert 0.01 <= m["bound"] <= 0.25 and unit.fullmatch(m["unit"])
        assert m["better"] in ("lower", "higher")
    for m in BENCH["per_layer"]:
        assert set(m) <= {"name", "unit", "better", "source", "layer", "moves",
                          "workloads"}
        assert m["source"] in ("device_trace", "program_span", "program_counter",
                               "host_clock")
        assert unit.fullmatch(m["unit"]) and m["better"] in ("lower", "higher")
    names = [m["name"] for s in ("end_to_end", "per_layer") for m in BENCH[s]]
    assert len(set(names)) == len(names) and "setup_s" in names
