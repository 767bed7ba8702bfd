"""Record the small TPU trace of the program's serving engine that
``test_chip_bench_spans.py`` reads.

    python3 benchmarks/chip/record_engine_testdata.py

On one TPU: a paged engine of one quantized decoder layer (``int8_spoga``
GEMMs, int8 pages; the reduced llama3.2-1b widths) with two requests
decoding, then, inside a ``measured_slice`` annotation as the harness
makes it, ``STEPS`` engine steps under ``step``, each streamed token
under ``token_cb``, with an ``await_arrival`` sleep of ``GAP_S`` after
each step.  The engine's own ``engine.*`` spans and the device's
``weight_quant`` ops land in the trace.  Writes
``enginedata/engine_slice.xplane.pb`` (kept apart from ``testdata/``,
whose reduction reads the newest trace there) pruned to what the
readers use (``op_scopes.prune``: the first TPU's ops and the host's
events, each op's ``tf_op``), and prints what the readers make of it.
Fails off-TPU or above ``MAX_BYTES``.
"""

from __future__ import annotations

import glob
import json
import os
import re
import shutil
import sys
import tempfile
import time
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))
sys.path.insert(0, str(CHIP_DIR.parents[1] / "src"))

GAP_S = 0.005
STEPS = 3
MAX_BYTES = 64 * 1024
OUT = CHIP_DIR / "enginedata" / "engine_slice.xplane.pb"
KEEP_PLANES = re.compile(r"^(/device:TPU:0|/host:CPU)$")
DROP_LINES = ("XLA Modules", "Async XLA Ops")


def build_engine():
    from repro.api import LLM, KVConfig, RuntimeConfig, SchedulerConfig
    from repro.api.config import QuantRuntime
    from repro.configs import get_config, reduced

    cfg = reduced(get_config("llama3.2-1b")).with_(n_layers=1)
    rt = RuntimeConfig(
        quant=QuantRuntime(mode="int8_spoga"),
        kv=KVConfig(mode="paged", dtype="int8", page_size=16, cache_len=128),
        scheduler=SchedulerConfig(n_slots=4, prefill_buckets=(32,)))
    llm = LLM(config=cfg, runtime=rt)
    return llm.build_engine(32, 16), cfg.vocab_size


def main() -> int:
    import jax
    import numpy as np

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 2
    from chipbench import host_spans, op_scopes, trace_reduce

    Annot = jax.profiler.TraceAnnotation
    engine, vocab = build_engine()
    rng = np.random.default_rng(0)

    def on_token(tok) -> None:
        with Annot("token_cb"):
            pass

    def add():
        engine.add_request(rng.integers(1, vocab, 20).tolist(), 16, on_token=on_token)

    add()
    while engine.has_work:                      # compile every program first
        engine.step()
    add()
    add()
    while engine.scheduler.waiting:             # both lanes decoding
        engine.step()

    tmp = tempfile.mkdtemp(prefix="chipbench-enginedata-")
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 1
    jax.profiler.start_trace(tmp, profiler_options=opts)
    with Annot("measured_slice"):
        for _ in range(STEPS):
            with Annot("step"):
                engine.step()
            with Annot("await_arrival"):
                time.sleep(GAP_S)
    jax.profiler.stop_trace()
    src = max(glob.glob(os.path.join(tmp, "**", "*.xplane.pb"), recursive=True),
              key=os.path.getmtime)
    with open(src, "rb") as f:
        raw = f.read()
    shutil.rmtree(tmp, ignore_errors=True)
    OUT.parent.mkdir(exist_ok=True)
    OUT.write_bytes(op_scopes.prune(raw, KEEP_PLANES.match,
                                    lambda line: line not in DROP_LINES, {op_scopes.TF_OP}))

    red = trace_reduce.reduce_trace(str(OUT.parent), 1)
    scopes, hs = red["scopes"], red["host_spans"]
    host, devices = trace_reduce.read_events(str(OUT))
    size = OUT.stat().st_size
    print(json.dumps({"bytes": size, "raw_bytes": len(raw), "window_s": red["window_s"],
                      "busy_s": red["busy_s"], "top_ops": red["top_ops"],
                      "requant_share": op_scopes.scope_share(red, scopes, "weight_quant"),
                      "weight_quant_ops": sorted({trace_reduce._op_key(n) for n, op in
                                                  scopes.items() if "weight_quant" in op}),
                      "phases": host_spans.phase_seconds(hs),
                      "host_ms_per_step": host_spans.host_ms_per_step(hs),
                      "idle_by_label": host_spans.idle_by_label(host, devices)}))
    if size > MAX_BYTES:
        print(f"{size} bytes: over {MAX_BYTES}", file=sys.stderr)
        return 1
    return 0


if __name__ == "__main__":
    sys.exit(main())
