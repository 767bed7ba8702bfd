"""Benchmark: the INT8 GEMM dataflows across every registered backend.

Three views (Sec. III-B / Fig. 2):

1. **Analytic TPU HBM traffic** per dataflow, derived from the Pallas
   kernels' BlockSpecs — the architectural quantity SPOGA improves.
   ``deas`` pays an extra 4 int32 intermediate-matrix writes + 4 reads
   (the "ADC + memory + DEAS" pipeline of prior work); ``spoga`` keeps
   partials in VMEM and writes each output tile once.
2. **Host XLA wall-clock** of every registry backend that compiles on this
   platform (the Pallas interpreter is skipped on CPU above tiny shapes —
   it runs the kernel body in Python and would swamp the table).
3. A machine-readable ``BENCH_kernels.json`` next to this file (override
   with ``--out``): per-backend, per-shape timings + analytic bytes. Each
   invocation APPENDS a run stamped with git SHA + date (``bench_record``),
   so the file accumulates the perf trajectory across PRs.

    PYTHONPATH=src python benchmarks/kernel_bench.py [--out PATH] [--quick]
"""

import argparse
import pathlib
import sys
import time

import jax
import jax.numpy as jnp
import numpy as np

sys.path.insert(0, str(pathlib.Path(__file__).parent))
from bench_record import append_run  # noqa: E402

from repro.backends import list_backends, resolve_backend
from repro.kernels.spoga_gemm import DEFAULT_BLOCK_K, DEFAULT_BLOCK_M, DEFAULT_BLOCK_N

SHAPES = ((256, 512, 256), (512, 2048, 512), (1024, 4096, 1024))
QUICK_SHAPES = ((256, 512, 256),)

# The compiled Pallas names run only on a TPU (off it they raise); their
# interpret twins execute the kernel bodies in Python, so off-TPU those are
# timed on the smallest shape only.
_TPU_ONLY = ("pallas_spoga", "pallas_spoga_dequant", "pallas_deas")
_INTERPRETED = ("pallas_interpret", "pallas_deas_interpret")


def _ceil(a: int, b: int) -> int:
    return -(-a // b)


def analytic_hbm_bytes(m: int, k: int, n: int, family: str) -> int:
    """HBM bytes moved by the Pallas dataflow (BlockSpec-level model)."""
    bm = min(DEFAULT_BLOCK_M, m)
    bn = min(DEFAULT_BLOCK_N, n)
    bk = min(DEFAULT_BLOCK_K, k)
    gm, gn, gk = _ceil(m, bm), _ceil(n, bn), _ceil(k, bk)
    # per K-sweep of one (i, j) tile: x tile + w tile per k step (int8)
    gemm_reads = gm * gn * gk * (bm * bk + bk * bn)
    out_write = gm * gn * (bm * bn) * 4                      # int32
    if family in ("spoga", "direct"):
        # slicing happens in VMEM; 1 fused sweep, 1 output write
        return gemm_reads + out_write
    if family == "deas":
        # 4 slice GEMMs (each sweeps + writes an int32 intermediate) +
        # DEAS combine re-reading all four and writing the final matrix.
        slice_cost = 4 * (gemm_reads + out_write)
        combine = 4 * (m * n * 4) + m * n * 4
        return slice_cost + combine
    raise ValueError(family)


def _time(fn, *args, iters: int = 10) -> float:
    jax.block_until_ready(fn(*args))
    t0 = time.perf_counter()
    for _ in range(iters):
        out = fn(*args)
    jax.block_until_ready(out)
    return (time.perf_counter() - t0) / iters * 1e6


def run(shapes=SHAPES) -> tuple[list[str], list[dict]]:
    on_tpu = jax.default_backend() == "tpu"
    lines = ["", "=== kernel bench: INT8 GEMM dataflows x backend registry ==="]
    lines.append(f"{'shape':>18s} {'backend':>22s} {'us/call':>12s} "
                 f"{'TPU HBM bytes':>14s} {'vs spoga':>9s}")
    rng = np.random.default_rng(0)
    records = []
    for m, k, n in shapes:
        x = jnp.asarray(rng.integers(-128, 128, (m, k), dtype=np.int8))
        w = jnp.asarray(rng.integers(-128, 128, (k, n), dtype=np.int8))
        base = analytic_hbm_bytes(m, k, n, "spoga")
        for name in list_backends():
            backend, spec = resolve_backend("int8_spoga", name)
            nbytes = analytic_hbm_bytes(m, k, n, backend.family)
            rec = {
                "bench": "int8_gemm",
                "backend": name,
                "family": backend.family,
                "shape": [m, k, n],
                "analytic_hbm_bytes": nbytes,
                "hbm_vs_spoga": round(nbytes / base, 3),
                "platform": jax.default_backend(),
                "us_per_call": None,
            }
            skip = None
            if not on_tpu and name in _TPU_ONLY:
                skip = "(tpu only)"
            elif not on_tpu and name in _INTERPRETED and (m, k, n) != min(shapes):
                skip = "(skipped)"
            if skip is None:
                fn = jax.jit(lambda a, b, _b=backend, _s=spec: _b.gemm(a, b, _s))
                rec["us_per_call"] = round(_time(fn, x, w), 1)
            us = f"{rec['us_per_call']:12.1f}" if skip is None else f"{skip:>12s}"
            lines.append(f"{f'{m}x{k}x{n}':>18s} {name:>22s} {us} "
                         f"{nbytes:14.3e} {nbytes / base:9.2f}x")
            records.append(rec)
    lines.append("(deas/spoga HBM ratio == the intermediate-matrix round trips "
                 "the paper eliminates; Fig. 2a vs 2b. Off-TPU the compiled "
                 "Pallas names are skipped and their interpret twins are "
                 "timed only on the smallest shape.)")
    return lines, records


def main():
    ap = argparse.ArgumentParser()
    default_out = pathlib.Path(__file__).parent / "BENCH_kernels.json"
    ap.add_argument("--out", default=str(default_out),
                    help="machine-readable results path (JSON)")
    ap.add_argument("--quick", action="store_true",
                    help="smallest shape only (CI-friendly)")
    args = ap.parse_args()
    lines, records = run(QUICK_SHAPES if args.quick else SHAPES)
    print("\n".join(lines))
    stamped = append_run(args.out, "kernel_bench",
                         {"quick": args.quick, "records": records})
    print(f"appended {len(records)} records to {args.out} "
          f"(sha {stamped['git_sha']}, {stamped['date']})")


if __name__ == "__main__":
    main()
