"""Compile a cell's step programs for a described TPU v5e, without a chip.

    JAX_PLATFORMS=cpu python3 benchmarks/chip/rehearse.py --workload <cell>

Lowers the engine's decode step and its largest prefill (the top bucket,
or one chunk) at the cell's real shapes with the compiled kernels
selected, compiles them for one chip of a described ``v5e:2x2``, and
prints each program's ``memory_analysis`` with the bytes of the weights
and the page pool.  What the chip's compiler would refuse, and a program
that does not fit, fail here at no chip time.  Nothing runs.
"""

from __future__ import annotations

import argparse
import json
import os
import sys
from pathlib import Path

CHIP_DIR = Path(__file__).resolve().parent
sys.path.insert(0, str(CHIP_DIR))
sys.path.insert(0, str(CHIP_DIR.parents[1] / "src"))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    args = ap.parse_args(argv)
    os.environ.setdefault("TPU_LOG_DIR", "disabled")

    import jax
    import jax.numpy as jnp
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding

    from chipbench import spec
    from repro.configs.base import pages_for
    from repro.models import model as model_lib
    from repro.serving import engine as engine_mod

    jax.config.update("jax_enable_compilation_cache", False)
    topo = topologies.get_topology_desc(platform="tpu", topology_name="v5e:2x2")
    chip = SingleDeviceSharding(topo.devices[0])
    # the compiled kernels are chosen only where JAX's backend is a TPU;
    # here the TPU is described, not attached
    jax.default_backend = lambda: "tpu"

    cell = spec.load_cell(args.workload)
    serving = cell.serving
    rt = spec.runtime_config(cell)
    mc = rt.resolve_model(spec.model_config(cell)).with_(
        gemm_backend="pallas_spoga_dequant" if rt.quant.mode != "bf16" else None,
        paged_attn_impl="pallas")
    n, cache_len, ps = serving["n_slots"], serving["cache_len"], rt.kv.page_size
    n_pages = rt.kv.n_pages or n * pages_for(cache_len, ps) + 1

    def on_chip(tree):
        return jax.tree_util.tree_map(
            lambda s: jax.ShapeDtypeStruct(s.shape, s.dtype, sharding=chip), tree)

    params = on_chip(model_lib.param_shapes(mc))
    pool = on_chip(model_lib.paged_cache_shapes(mc, n, cache_len, ps, n_pages))
    nbytes = lambda t: sum(x.size * x.dtype.itemsize for x in jax.tree_util.tree_leaves(t))
    s = lambda shape, dt: jax.ShapeDtypeStruct(shape, dt, sharding=chip)
    out = {"cell": cell.name, "weights_bytes": nbytes(params),
           "page_pool_bytes": nbytes(pool), "n_pages": n_pages}

    decode = engine_mod._jitted_decode_sample(mc)
    lanes = lambda dt: s((n,), dt)
    programs = {"decode": decode.lower(
        params, lanes(jnp.int32), pool, lanes(jnp.bool_), lanes(jnp.float32),
        lanes(jnp.int32), lanes(jnp.bool_), s((n, 2), jnp.uint32), False)}
    if serving.get("prefill_chunk"):
        c = serving["prefill_chunk"]
        step = engine_mod._jitted_chunk_step(mc, c)
        programs[f"chunk_{c}"] = step.lower(
            params, pool, s((1, c), jnp.int32), s((), jnp.int32),
            s((1,), jnp.int32), s((1,), jnp.int32))
    else:
        top = max(serving["prefill_buckets"])
        single = pages_for(top, ps) * ps
        admit = engine_mod._jitted_admit_paged(mc, single)
        one = lambda dt: s((1,), dt)
        programs[f"prefill_{top}"] = admit.lower(
            pool, params, s((1, top), jnp.int32), one(jnp.int32), s((), jnp.int32),
            s((single // ps,), jnp.int32), s((pages_for(cache_len, ps),), jnp.int32),
            one(jnp.float32), one(jnp.int32), one(jnp.bool_), s((1, 2), jnp.uint32))
    for name, lowered in programs.items():
        ma = lowered.compile().memory_analysis()
        out[name] = {k: getattr(ma, k) for k in (
            "argument_size_in_bytes", "output_size_in_bytes",
            "temp_size_in_bytes", "alias_size_in_bytes",
            "generated_code_size_in_bytes")}
    print(json.dumps(out, indent=1))
    return 0


if __name__ == "__main__":
    sys.exit(main())
