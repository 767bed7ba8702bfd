"""Run one cell of the chip benchmark once and print its result line.

    python3 benchmarks/chip/run.py --workload <cell> --seed <n> \\
        --seconds <run_seconds> --trace <0|1>

The cell (``BENCHMARK.json``'s ``workloads``) names a configuration
(``configs/<name>.json``: sizes, family, serving geometry) and a traffic
mix (``traffic/<name>.json``).  The run makes the weights on the device
from the seed, builds the program's ``repro.api.LLM`` and engine, warms
every shape the deployment uses, serves the mix's fill (if any), drives
the engine for ``--seconds`` (open loop, or a backlog), drains,
reads the device's peak memory, serves on untimed until enough requests
have finished for the check, frees the program, and compares a sample
of what it served with the plain float32 reference.  With ``--trace 1``
it also profiles a steady slice at the end of the window and reports the
cell's per-layer metrics from it; otherwise its end-to-end metrics.

The last line of standard output is one JSON object (``correct``,
``attempted``, ``failed``, ``metrics``, ``device``, with ``--trace 1``
``breakdown``, and last ``checks``: each number compared beside its
limit).  The run fails, printing no result, where JAX finds no TPU or
fewer chips than the cell asks for.
"""

from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402
from pathlib import Path  # noqa: E402

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))
sys.path.insert(0, str(CHIP_DIR.parents[1] / "src"))

DRAIN_LIMIT_S = 20.0
FINISH_LIMIT_S = 60.0    # untimed serving after the drain, for the check's sample
TRACE_SLICE_S = 4.0      # traced slice: the last seconds of the window
WARM_NEW_TOKENS = 2


def log(*a) -> None:
    print(*a, file=sys.stderr, flush=True)


def parse_args(argv=None):
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    return ap.parse_args(argv)


def find_devices(chips: int):
    """The TPU devices, or None (with the reason on stderr)."""
    import jax

    try:
        devs = jax.devices()
    except RuntimeError as e:
        log(f"no accelerator: {e}")
        return None
    if devs[0].platform != "tpu":
        log(f"no TPU: JAX's devices are {devs[0].platform}")
        return None
    if len(devs) < chips:
        log(f"the cell asks for {chips} chips, JAX finds {len(devs)}")
        return None
    return devs


BUILDS: list[float] = []     # host times at which JAX built a program


def _on_jax_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/compile_requests_use_cache":
        BUILDS.append(time.perf_counter())


def enable_compile_cache() -> None:
    """JAX's persistent cache in the checkout (or where
    ``JAX_COMPILATION_CACHE_DIR`` says), every program cached; and a count
    of the programs built (compiled or read from that cache), so a run can
    show that none was built inside its window."""
    import jax
    from repro.launch.compile_cache import enable_compile_cache as enable

    log(f"compile cache: {enable()}")
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.monitoring.register_event_listener(_on_jax_event)


def build(cell, seed: int):
    """Weights from the seed, the program's LLM and its engine."""
    import jax
    from chipbench import spec, traffic
    from repro.api import LLM
    from repro.models.model import param_shapes

    mc = spec.model_config(cell)
    rt = spec.runtime_config(cell)
    params = cell.program_params(seed, param_shapes(mc))
    jax.block_until_ready(params)
    llm = LLM(config=mc, runtime=rt, params=params)
    rows = cell.serving["cache_len"]
    out_max = traffic.max_output_len(cell.traffic)
    if traffic.max_prompt_len(cell.traffic) + out_max > rows:
        raise SystemExit(f"{cell.traffic_name}'s longest request does not fit "
                         f"{cell.config_name}'s {rows} rows per lane")
    engine = llm.build_engine(rows - out_max, out_max)
    return llm, engine


def warm_up(engine, cell) -> int:
    """Run every shape the deployment uses once: one request per prefill
    bucket (or one long enough to take every chunk path), then decode.
    Returns the number of warm-up requests."""
    import numpy as np
    from chipbench import traffic

    serving, vocab = cell.serving, cell.model["vocab_size"]
    stream = any(c["stream"] for c in traffic.classes(cell.traffic))
    lens = list(serving.get("prefill_buckets") or [])
    if serving.get("prefill_chunk"):
        lens.append(serving["prefill_chunk"] * 2 + 1)
    rng = np.random.default_rng(0)
    for n in lens:
        engine.add_request(rng.integers(1, vocab, n).tolist(), WARM_NEW_TOKENS,
                           on_token=(lambda t: None) if stream else None)
    while engine.has_work:
        engine.step()
    return len(lens)


class TraceSlice:
    """``on_window`` hook of the drive loops: starts the profiler
    ``slice_s`` before the window closes and marks the slice with a
    ``measured_slice`` annotation; the trace itself is stopped after the
    drain, so writing it never stalls a measured request."""

    def __init__(self, window_s: float, slice_s: float, log_dir: str):
        import jax

        self.jax = jax
        self.window_s = window_s
        self.slice_s = min(slice_s, window_s)
        self.log_dir = log_dir
        self.t0 = None
        self.mark = None
        self.span = None      # host (start, end) of the slice

    def __call__(self, phase: str, now: float) -> None:
        if phase == "open":
            self.t0 = now
            return
        close = self.t0 + self.window_s
        if self.mark is None and now >= close - self.slice_s:
            opts = self.jax.profiler.ProfileOptions()
            opts.python_tracer_level = 0     # host: the harness's annotations
            opts.host_tracer_level = 1
            self.jax.profiler.start_trace(self.log_dir, profiler_options=opts)
            self.mark = self.jax.profiler.TraceAnnotation("measured_slice")
            self.mark.__enter__()
            self.span = [time.perf_counter(), None]
        elif self.span is not None and self.span[1] is None and now >= close:
            self.mark.__exit__(None, None, None)
            self.span[1] = time.perf_counter()

    def stop(self) -> str | None:
        if self.mark is None:
            return None
        if self.span[1] is None:
            self.mark.__exit__(None, None, None)
            self.span[1] = time.perf_counter()
        self.jax.profiler.stop_trace()
        return self.log_dir


def serve(cell, seed: int, seconds: float, on_window=None):
    """Set-up (weights, engine, warm-up), then the cell's traffic driven
    through a window of ``seconds`` and drained.  Returns the LLM, its
    engine, and the drive loop's (tracked requests, window, end, steps)."""
    from chipbench import drive, traffic

    mix, m = cell.traffic, cell.model
    t_build = time.perf_counter()
    llm, engine = build(cell, seed)
    t_warm = time.perf_counter()
    n_warm = warm_up(engine, cell)
    log(f"set-up: imports {t_build - T_START:.3f} s, weights and engine "
        f"{t_warm - t_build:.3f} s, warm-up of {n_warm} requests "
        f"{time.perf_counter() - t_warm:.3f} s")
    if mix["kind"] == "open_loop":
        reqs = traffic.generate(mix, seed, seconds, m["vocab_size"],
                                after_s=DRAIN_LIMIT_S)
        return llm, engine, drive.drive_open_loop(
            engine, reqs, seconds, fill_s=float(mix.get("fill_s", 0.0)),
            drain_limit_s=DRAIN_LIMIT_S, on_window=on_window)
    backlog = drive.Backlog(engine, traffic.generate(mix, seed, seconds, m["vocab_size"]))
    backlog.fill(cell.serving["n_slots"])
    return llm, engine, backlog.drive(seconds, drain_limit_s=DRAIN_LIMIT_S,
                                      on_window=on_window)


def check_pairs(cell, engine, tracked, seed: int) -> list:
    """(prompt ids, served ids) of the requests the check compares, once
    the engine has served on, untimed, until enough of them finished."""
    from chipbench import check, drive

    tokens = cell.traffic["check_tokens"]
    drive.finish_for_check(engine, tracked, tokens, FINISH_LIMIT_S)
    return [(list(map(int, t.tr.prompt)), list(t.req.output_tokens))
            for t in check.sample(tracked, seed, tokens)]


def run_cell(cell, seed: int, seconds: float, trace: bool, devs) -> dict:
    from chipbench import accounting, check, spec

    mix = cell.traffic
    m = cell.model
    tdir = tempfile.mkdtemp(prefix="chipbench-trace-") if trace else None
    hook = TraceSlice(seconds, TRACE_SLICE_S, tdir) if trace else None
    llm, engine, (tracked, window, end, steps) = serve(cell, seed, seconds, hook)
    trace_dir = hook.stop() if hook else None
    stats = devs[0].memory_stats() or {}
    peak = max((d.memory_stats() or {}).get("peak_bytes_in_use", 0)
               for d in devs[:cell.chips])
    lateness = [t.submit - t.due for t in tracked if t.submit is not None]
    built = sum(1 for t in BUILDS if window[0] <= t < window[1])
    log(f"programs built: {len(BUILDS)}, inside the window: {built}")
    longest = sorted(accounting.steps_in({"steps": steps}, *window),
                     key=lambda s: s["t0"] - s["t1"])[:3]
    log("longest steps in the window: " + "; ".join(
        f"{s['t1'] - s['t0']:.3f} s (decode {s['decode']}, prefill "
        f"{s['oneshot_prompts']}, chunks {s['chunks']}, defrags {s['defrags']})"
        for s in longest))
    log(f"submitted {len(tracked)}; generator late "
        f"p50 {sorted(lateness)[len(lateness) // 2] if lateness else 0:.6f} s, "
        f"max {max(lateness, default=0):.6f} s; bytes in use {stats.get('bytes_in_use')}")

    peaks = spec.peaks_for(devs[0].device_kind)
    int8 = cell.config["program"]["runtime"]["quant"]["mode"] != "bf16"
    record = {
        "cell": cell.name, "model": m, "traffic": mix, "peaks": peaks,
        "gemm_peak_ops": peaks["int8_ops_per_s" if int8 else "bf16_flops_per_s"],
        "process_start": T_START, "window": list(window), "end": end,
        "requests": [t.record() for t in tracked],
        "steps": steps,
        "serving": cell.serving,
        "counts": accounting.layer_counts(cell.reference, m),
        "slice": None, "trace": None,
    }
    if trace_dir is not None:
        from chipbench.trace_reduce import reduce_trace

        record["slice"] = hook.span
        record["trace"] = reduce_trace(trace_dir, cell.chips)
        shutil.rmtree(trace_dir, ignore_errors=True)

    n_missing = check.missing(tracked, window)
    ttft, itl = accounting.ttft_values(record), accounting.itl_values(record)
    log(f"latency over {len(ttft)} requests due in the window: ttft p50 "
        f"{accounting.percentile(ttft, 50):.4f} p90 {accounting.percentile(ttft, 90):.4f} s; "
        + (" ".join(f"itl p{q} {1e3 * accounting.percentile(itl, q):.2f}"
                    for q in (50, 90, 95, 99)) + f" ms over {len(itl)} gaps" if itl else "no gaps"))
    attempted = sum(1 for t in tracked if window[0] <= t.due < window[1])
    pairs = check_pairs(cell, engine, tracked, seed)

    # the program's state goes before the reference runs
    del llm, engine, tracked, steps
    gc.collect()
    t_ref = time.perf_counter()
    # no finished request to compare reads as no number: not correct
    gap = float(check.gaps(cell.family, m, seed, pairs)["served"].max()) if pairs else None
    log(f"reference over {len(pairs)} requests, "
        f"{sum(len(s) for _, s in pairs)} served tokens: "
        f"{time.perf_counter() - t_ref:.3f} s")
    limit = cell.config["check"]["max_logit_gap"]
    checks = {
        "missing_requests": {"value": n_missing, "limit": 0},
        "max_logit_gap": {"value": gap, "limit": limit},
    }
    correct = check.verdict(checks)

    metrics = {}
    for entry in cell.metric_entries(trace):
        value = spec.load_reader(entry["name"])(record)
        if value is not None:
            metrics[entry["name"]] = {"value": value, "unit": entry["unit"]}
    device = {"platform": devs[0].platform, "kind": devs[0].device_kind,
              "count": cell.chips, "memory_peak_bytes": int(peak)}
    result = {"correct": bool(correct), "attempted": attempted,
              "failed": n_missing, "metrics": metrics, "device": device}
    if trace_dir is not None:
        tr = record["trace"]
        device["busy_s"] = tr["busy_s"]
        device["window_s"] = tr["window_s"]
        result["breakdown"] = {"device_ops": tr["top_ops"],
                               "idle_gaps": tr["idle_gaps"]}
    for name, c in checks.items():
        log(f"check {name}: {c['value']} (limit {c['limit']})")
    result["checks"] = checks
    return result


def main(argv=None) -> int:
    args = parse_args(argv)
    from chipbench import spec

    cell = spec.load_cell(args.workload)
    devs = find_devices(cell.chips)
    if devs is None:
        return 2
    enable_compile_cache()
    spec.peaks_for(devs[0].device_kind)
    result = run_cell(cell, args.seed, args.seconds, bool(args.trace), devs)
    print(json.dumps(result), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
