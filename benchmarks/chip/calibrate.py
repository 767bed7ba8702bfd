"""Readings that a cell's correctness limit is set from, on the chip.

    python3 benchmarks/chip/calibrate.py --workload <cell> --seeds 12 \\
        --first-seed <n> --seconds 50 [--control]

In one process (one compile), for each seed: the weights are drawn, the
program serves a window of the cell's own traffic at its own load and
serves on until the harness's sample of finished requests is full, as a
run does; the program is freed, and the plain float32 reference reads
the widest gap of the served tokens (the program's reading).  With
``--control`` the same positions are also read for the control, the
reference one precision step lower (int4 weights over int8
activations), which has to read above the limit, so that a run with
the control in the program's place is not ``correct``.  One JSON line per seed;
``PERF.md`` records the readings and the limit set between them.
"""

from __future__ import annotations

import argparse
import gc
import json
import sys
import time
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))
sys.path.insert(0, str(CHIP_DIR.parents[1] / "src"))

import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=50.0)
    ap.add_argument("--control", action="store_true")
    args = ap.parse_args(argv)

    from chipbench import check, spec

    cell = spec.load_cell(args.workload)
    devs = harness.find_devices(cell.chips)
    if devs is None:
        return 2
    harness.enable_compile_cache()
    for i in range(args.seeds):
        seed = args.first_seed + 7919 * i
        t0 = time.perf_counter()
        llm, engine, (tracked, window, _, _) = harness.serve(cell, seed, args.seconds)
        missing = check.missing(tracked, window)
        pairs = harness.check_pairs(cell, engine, tracked, seed)
        del llm, engine, tracked
        gc.collect()
        g = check.gaps(cell.family, cell.model, seed, pairs,
                       quant_ctrl="w4a8" if args.control else None)
        limit = cell.config["check"]["max_logit_gap"]
        out = {"cell": cell.name, "seed": seed, "missing": missing,
               "requests": len(pairs), "tokens": int(len(g["served"])),
               "program_max_gap": float(g["served"].max()),
               "program_p99_gap": float(sorted(g["served"])[int(0.99 * (len(g["served"]) - 1))]),
               "seconds": time.perf_counter() - t0}
        if args.control:
            # the control in the program's place, judged as a run judges
            # the program
            out["control_max_gap"] = float(g["control"].max())
            out["control_correct"] = check.verdict(
                {"max_logit_gap": {"value": out["control_max_gap"], "limit": limit}})
        print(json.dumps(out), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
