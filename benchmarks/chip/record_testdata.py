"""Record the small TPU trace that ``test_chip_bench_trace.py`` reduces.

    python3 benchmarks/chip/record_testdata.py

On one TPU, inside a ``measured_slice`` annotation as the harness makes
it: three ``step`` annotations, each running the program's int8 GEMM
kernel (``spoga_gemm_dequant``) on a decode-sized block and one XLA
fusion, with an ``await_arrival`` sleep of ``GAP_S`` after each and one
``admit`` before the second.  Writes ``testdata/slice.xplane.pb`` and
prints its reduction.  Fails off-TPU.
"""

from __future__ import annotations

import glob
import json
import os
import shutil
import sys
import tempfile
import time
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))
sys.path.insert(0, str(CHIP_DIR.parents[1] / "src"))

GAP_S = 0.02
STEPS = 3
OUT = CHIP_DIR / "testdata" / "slice.xplane.pb"


def main() -> int:
    import jax
    import jax.numpy as jnp

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    from chipbench import trace_reduce
    from repro.kernels.spoga_gemm_dequant import spoga_gemm_dequant

    m, k, n = 32, 1024, 2048
    keys = jax.random.split(jax.random.key(0), 2)
    x = jax.random.randint(keys[0], (m, k), -127, 128, jnp.int8)
    w = jax.random.randint(keys[1], (k, n), -127, 128, jnp.int8)
    xs, ws = jnp.full((m, 1), 0.01, jnp.float32), jnp.full((1, n), 0.02, jnp.float32)
    gemm = jax.jit(spoga_gemm_dequant)
    epilogue = jax.jit(lambda y: jnp.tanh(y) * 2.0 + 1.0)
    jax.block_until_ready(epilogue(gemm(x, w, xs, ws)))      # compile first

    tmp = tempfile.mkdtemp(prefix="chipbench-testdata-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with jax.profiler.TraceAnnotation("measured_slice"):
        for i in range(STEPS):
            if i == 1:
                with jax.profiler.TraceAnnotation("admit"):
                    time.sleep(GAP_S / 4)
            with jax.profiler.TraceAnnotation("step"):
                jax.block_until_ready(epilogue(gemm(x, w, xs, ws)))
            with jax.profiler.TraceAnnotation("await_arrival"):
                time.sleep(GAP_S)
    jax.profiler.stop_trace()
    src = max(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True),
              key=os.path.getmtime)
    OUT.parent.mkdir(exist_ok=True)
    shutil.copyfile(src, OUT)
    shutil.rmtree(tmp, ignore_errors=True)
    red = trace_reduce.reduce_trace(str(OUT.parent), 1)
    print(json.dumps({"bytes": OUT.stat().st_size, "window_s": red["window_s"],
                      "busy_s": red["busy_s"], "top_ops": red["top_ops"],
                      "idle_gaps": red["idle_gaps"],
                      "gemm": trace_reduce.kernel_seconds(red, "spoga_gemm_dequant")}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
