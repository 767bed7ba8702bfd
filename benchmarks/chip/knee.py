"""Find an open-loop cell's capacity: the highest request rate its engine
sustains, from which the cell's fixed rate is set.

    python3 benchmarks/chip/knee.py --workload <cell> --seed <n> \\
        --seconds 60 --settle 25 --rate 3

One process, one set-up: the cell's traffic is offered at ``--rate``,
more than the engine can serve, so that once the first ``--settle``
seconds have filled every lane the engine works at its limit, admitting a
waiting request whenever a lane frees.  The output tokens received per
second after the settle are its capacity in tokens; over the mix's mean
answer length (the mean of the generator's stratified quantiles) they are
its capacity in requests per second: the knee, since below it the queue
stays bounded and above it the queue grows without end.  Printed as one
JSON line, with the median gap between streamed tokens (the decode step
without a prefill in it), the requests still waiting at the close (the
engine was saturated only if some were), and the rate four fifths of the
knee.
"""

from __future__ import annotations

import argparse
import json
import sys
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))
sys.path.insert(0, str(CHIP_DIR.parents[1] / "src"))

import run as harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, default=60.0)
    ap.add_argument("--settle", type=float, default=25.0)
    ap.add_argument("--rate", type=float, required=True)
    args = ap.parse_args(argv)

    from chipbench import accounting, drive, spec, traffic

    cell = spec.load_cell(args.workload)
    mix, m = cell.traffic, cell.model
    if mix["kind"] != "open_loop":
        raise SystemExit("the knee is found for open-loop cells")
    devs = harness.find_devices(cell.chips)
    if devs is None:
        return 2
    harness.enable_compile_cache()
    llm, engine = harness.build(cell, args.seed)
    harness.warm_up(engine, cell)
    reqs = traffic.generate(dict(mix, fill_s=0.0), args.seed, args.seconds,
                            m["vocab_size"], rate_rps=args.rate)
    tracked, (w0, w1), _, _ = drive.drive_open_loop(
        engine, reqs, args.seconds, drain_limit_s=0.0)
    lo = w0 + args.settle
    received = sum(1 for t in tracked for s in t.token_times if lo <= s < w1)
    tok_s = received / (w1 - lo)
    cls = traffic.classes(mix)
    mean_out = float(sum(c["share"] * traffic.quantiles(c["output"], 1000).mean()
                         for c in cls) / sum(c["share"] for c in cls))
    gaps = accounting.itl_values({"window": [w0, w1], "requests":
                                  [t.record() for t in tracked]})
    knee = tok_s / mean_out
    print(json.dumps({
        "cell": cell.name, "offered_rps": args.rate, "settle_s": args.settle,
        "output_tok_s": tok_s, "mean_output_tokens": mean_out,
        "knee_rps": knee, "rate_rps_at_0.8": 0.8 * knee,
        "itl_p50_ms": 1e3 * accounting.percentile(gaps, 50),
        "waiting_at_close": len(engine.scheduler.waiting),
        "lanes": cell.serving["n_slots"],
    }), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
