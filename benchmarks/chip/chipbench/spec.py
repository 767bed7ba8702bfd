"""Find a cell's pieces by name: ``BENCHMARK.json`` at the checkout root
names the cell; its configuration and traffic mix are data files under
``configs/`` and ``traffic/``; the configuration's ``family`` names its
plain reference (``reference/<family>.py``) and the program's side of it
(``layouts/<family>.py``); each metric is a reader under ``metrics/``.
Adding a configuration, a family, a mix or a metric adds files and
entries; nothing here changes.
"""

from __future__ import annotations

import dataclasses
import importlib
import importlib.util
import json
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parents[1]
ROOT = CHIP_DIR.parents[1]


@dataclasses.dataclass(frozen=True)
class Cell:
    name: str
    config_name: str
    traffic_name: str
    chips: int
    config: dict        # configs/<config>.json
    traffic: dict       # traffic/<mix>.json
    bench: dict         # the whole BENCHMARK.json

    @property
    def model(self) -> dict:
        return self.config["model"]

    @property
    def family(self) -> str:
        return self.config["family"]

    @property
    def reference(self):
        """The plain reference family module, ``reference/<family>.py``."""
        return importlib.import_module(f"reference.{self.family}")

    @property
    def layout(self):
        """The program's side of the family, ``layouts/<family>.py``."""
        return importlib.import_module(f"layouts.{self.family}")

    def program_params(self, seed: int, shapes):
        """The program's parameter tree (``shapes``) filled from the seed:
        every layer with its kind's weights, converted by the layout's
        ``to_program``."""
        from chipbench import weights
        from reference import layer_kinds

        m, ref = self.model, self.reference
        return weights.program_params(m, seed, shapes, layer_kinds(ref, m),
                                      lambda kind: ref.layer_specs(m, kind),
                                      self.layout.to_program)

    @property
    def serving(self) -> dict:
        """The deployment's serving geometry, from the configuration's
        runtime: lanes, prefill buckets or chunk, rows per lane, pages."""
        rt = self.config["program"]["runtime"]
        sched, kv = rt.get("scheduler", {}), rt.get("kv", {})
        return {"n_slots": sched["n_slots"],
                "prefill_buckets": sched.get("prefill_buckets"),
                "prefill_chunk": sched.get("prefill_chunk"),
                "cache_len": kv["cache_len"], "n_pages": kv.get("n_pages")}

    def metric_entries(self, trace: bool) -> list[dict]:
        """The metrics this cell reports: its end-to-end ones untraced,
        its per-layer ones traced (an entry with ``workloads`` names the
        cells it applies to; one without applies to every cell)."""
        key = "per_layer" if trace else "end_to_end"
        return [m for m in self.bench[key]
                if self.name in m.get("workloads", [self.name])]


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_mix(name: str) -> dict:
    """``traffic/<name>.json``; a mix that ``extends`` another takes that
    one's keys and replaces those it gives."""
    mix = load_json(CHIP_DIR / "traffic" / f"{name}.json")
    if "extends" in mix:
        mix = {**load_mix(mix["extends"]), **{k: v for k, v in mix.items() if k != "extends"}}
    return mix


def load_cell(name: str, root: Path = ROOT) -> Cell:
    bench = load_json(root / "BENCHMARK.json")
    cells = {w["name"]: w for w in bench["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}; known: {sorted(cells)}")
    w = cells[name]
    configs = {c["name"]: c for c in bench["configs"]}
    cfg_entry = configs[w["config"]]
    return Cell(
        name=name, config_name=w["config"], traffic_name=w["traffic"],
        chips=int(w["chips"]),
        config=load_json(root / cfg_entry["file"]),
        traffic=load_mix(w["traffic"]),
        bench=bench)


def load_reader(metric_name: str):
    """``metrics/<name>.py``'s ``read(record)`` (file names carry dots, so
    they are loaded by path, not imported)."""
    path = CHIP_DIR / "metrics" / f"{metric_name}.py"
    spec = importlib.util.spec_from_file_location(
        "chipbench_metric_" + metric_name.replace(".", "_"), path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod.read


def peaks_for(device_kind: str) -> dict:
    table = load_json(CHIP_DIR / "peaks.json")
    if device_kind not in table["devices"]:
        raise SystemExit(f"device_kind {device_kind!r} is not in peaks.json "
                         f"({sorted(table['devices'])}); add its published "
                         "peaks before measuring on it")
    return table["devices"][device_kind]


# ---------------------------------------------------------------------------
# The program's side: ModelConfig and RuntimeConfig from the data files
# ---------------------------------------------------------------------------

def model_config(cell):
    """The program's ``ModelConfig``: the family's layout builds it from the
    configuration file, so the file is what runs."""
    return cell.layout.model_config(cell.config)


def runtime_config(cell: Cell):
    """The program's ``RuntimeConfig``, as the configuration states it:
    quantization, KV storage and the serving geometry."""
    from repro.api.config import RuntimeConfig

    return RuntimeConfig.from_dict(cell.config["program"]["runtime"])
