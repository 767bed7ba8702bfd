"""Run one cell as ``run.py`` does and also print the diagnostics of its
traced slice that the benchmark does not report.

    python3 benchmarks/chip/spans.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> [--trace 1] [--obs 1]

Prints ``run.py``'s result line with one more key, ``host_spans`` (with
``--trace 1``): all of the device's idle time split by the innermost
engine phase or harness annotation around it (``idle_by_label``), the
time and count of each ``engine.*`` span in the slice (``phases``), and
the slice's heaviest device ops with their HLO op names (``ops``).
``--obs 1`` serves with the program's span recording on
(``ObsConfig(trace=...)``), to price it.  The per-layer metrics that read
the same spans and scopes (``host_ms_per_step.chat``,
``requant_share.chat``) are in the result line's ``metrics``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys
import tempfile
from collections import defaultdict

import run
from chipbench import host_spans, spec, trace_reduce

TOP_OPS = 16
EXTRA: dict = {}


def reduce_trace(trace_dir: str, chips: int, _reduce=trace_reduce.reduce_trace) -> dict:
    """``trace_reduce.reduce_trace``, keeping the diagnostics in ``EXTRA``."""
    out = _reduce(trace_dir, chips)
    host, devices = trace_reduce.read_events(trace_reduce.find_xplane(trace_dir))
    ops = defaultdict(lambda: [0.0, set()])
    for name, seconds, _ in out["events"]:
        op = ops[trace_reduce._op_key(name)]
        op[0] += seconds
        op[1].add(out["scopes"].get(name, ""))
    EXTRA.update({
        "idle_by_label": host_spans.idle_by_label(host, devices),
        "phases": host_spans.phase_seconds(out["host_spans"]),
        "ops": sorted(([k, s, sorted(names)[:3]] for k, (s, names) in ops.items()),
                      key=lambda o: -o[1])[:TOP_OPS]})
    return out


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=1)
    ap.add_argument("--obs", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)

    cell = spec.load_cell(args.workload)
    devs = run.find_devices(cell.chips)
    if devs is None:
        return 2
    trace_reduce.reduce_trace = reduce_trace
    if args.obs:
        from repro.api import ObsConfig

        base = spec.runtime_config
        path = os.path.join(tempfile.mkdtemp(prefix="chipbench-obs-"), "engine.json")
        spec.runtime_config = lambda c: dataclasses.replace(
            base(c), obs=ObsConfig(trace=path))
    run.enable_compile_cache()
    result = run.run_cell(cell, args.seed, args.seconds, bool(args.trace), devs)
    if EXTRA:
        result["host_spans"] = EXTRA
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
