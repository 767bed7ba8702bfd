"""Smoke run of the serving path on a TPU: the quickest proof it still starts.

    python chip_smoke.py            # one chip
    python chip_smoke.py --tp 4     # four chips: the tensor-parallel path only

One chip runs two phases in this one process:

1. kernels — ``spoga_gemm_dequant`` (W8A8 and w4a8) at llama3.2-1b's decode,
   prefill and LM-head shapes, and ``paged_attention`` (bf16 and int8 pages)
   at its GQA geometry and, int8, at the served geometries of
   mistral-nemo-12b and granite-3.0-3b-a800m with ragged lengths, each
   compiled and compared with its jnp reference;
2. engine — ``LLM(arch="llama3.2-1b")`` at full width with random weights,
   once with the serve CLI's defaults (bf16, slot cache) and once on the
   paper's path (int8 SPOGA GEMMs, int8 paged KV), serving synthetic
   requests made from ``--seed``.

``--tp 4`` runs only the tensor-parallel path: mistral-nemo-12b's widths
cut to 8 layers compare their prefill logits at tp=1 and tp=4, then the
model at full width (more than one chip holds) serves a few requests on a
4-way "model" mesh.

The times printed are one cold run of a smoke, not a benchmark.  The script
exits non-zero, without a result line, unless JAX's first device is a TPU;
any failed check or phase ends it non-zero.  The last line of standard
output is ``{"ok": true, "device": {"platform", "kind", "count"}}``.
"""

from __future__ import annotations

import argparse
import gc
import json
import os
import sys
import time

import numpy as np

LLAMA = "llama3.2-1b"
NEMO = "mistral-nemo-12b"
GEN = 32                       # tokens generated per request (one chip)
PROMPT_LENS = (32, 48, 96, 160, 200, 300, 400, 512)
TP_PROMPT_LENS = (64, 80, 100, 128)    # two prefill buckets: 64, 128
TP_GEN = 16
TP_CUT_LAYERS = 8
# Paged attention: the kernel's p @ V product takes its probabilities
# through the MXU at bf16 input precision (a relative error of at most
# 2^-8 per term), and each output row is a convex combination of V rows, so
# |kernel - reference| <= 2^-8 * max|V| ~= 3.9e-3 * max|V|.  The reference
# runs at full f32 precision.  1e-2 of the output's scale leaves 2.5x
# margin; a wrong page, mask or head gives errors of order the scale itself.
ATTN_TOL = 1e-2
# tp=4 vs tp=1 prefill logits.  Every integer GEMM is exact on both sides
# (row-parallel int32 partials psum exactly) and the quantization scales
# are global maxima, so only bf16 attention/unembedding matmuls can differ,
# by the reduction order the partitioned program picks: a few bf16 ulps
# (2^-8 relative) of the logits' scale, compounded over 8 layers.
TP_LOGIT_TOL = 2e-2
# what the paper path must resolve to on a TPU
PAPER_GEMM_BACKEND = "pallas_spoga_dequant"
PAPER_PAGED_IMPL = "pallas"


class compile_clock:
    """Seconds XLA spent compiling while the block ran (every backend
    compile reports its duration through ``jax.monitoring``)."""

    total = 0.0
    _registered = False

    def __enter__(self):
        import jax

        if not compile_clock._registered:
            def listen(event, duration, **_):
                if event == "/jax/core/compile/backend_compile_duration":
                    compile_clock.total += duration
            jax.monitoring.register_event_duration_secs_listener(listen)
            compile_clock._registered = True
        self.start = compile_clock.total
        self.wall0 = time.perf_counter()
        return self

    def __exit__(self, *exc):
        self.compile_s = compile_clock.total - self.start
        self.wall_s = time.perf_counter() - self.wall0


def _serve_runtime(*flags):
    """A RuntimeConfig exactly as ``repro.launch.serve`` builds it from
    these command-line flags (the rest at the CLI's defaults)."""
    from repro.launch.serve import _runtime_from_args, build_parser

    return _runtime_from_args(build_parser().parse_args(list(flags)))


def _assert_full_width(cfg, arch):
    from repro.configs import get_config

    full = get_config(arch)
    assert (cfg.d_model, cfg.d_ff, cfg.n_layers, cfg.n_heads) == (
        full.d_model, full.d_ff, full.n_layers, full.n_heads), cfg


def _prompts(rng, lens, vocab):
    return [rng.integers(0, vocab, n).tolist() for n in lens]


# -- phase 1: kernels -------------------------------------------------------

def kernel_phase(seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.configs import get_config
    from repro.core.spoga import direct_matmul
    from repro.kernels.paged_attention import page_bytes, pages_per_block
    from repro.kernels.spoga_gemm_dequant import spoga_gemm_dequant

    cfg = get_config(LLAMA)
    lanes = 8
    d, ff, vocab = cfg.d_model, cfg.d_ff, cfg.vocab_size
    rng = np.random.default_rng(seed)
    gemms = [("decode w_up", lanes, d, ff), ("prefill w_down", 512, ff, d),
             ("lm head", lanes, d, vocab)]
    for mode, w_max, n_w_slices in (("W8A8", 127, 2), ("w4a8", 7, 1)):
        for name, m, k, n in gemms:
            x = jnp.asarray(rng.integers(-127, 128, (m, k)), jnp.int8)
            w = jnp.asarray(rng.integers(-w_max, w_max + 1, (k, n)), jnp.int8)
            xs = jnp.asarray(rng.uniform(1e-3, 1e-1, (m, 1)), jnp.float32)
            ws = jnp.asarray(rng.uniform(1e-3, 1e-1, (1, n)), jnp.float32)
            with compile_clock() as c:
                got = spoga_gemm_dequant(x, w, xs, ws, n_w_slices=n_w_slices)
                got.block_until_ready()
            want = direct_matmul(x, w).astype(jnp.float32) * xs * ws
            # integer GEMM: exact, so only the f32 epilogue's last ulp may move
            np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                                       rtol=1e-6, atol=0)
            print(f"[kernels] spoga_gemm_dequant {mode} {name} "
                  f"{m}x{k}x{n}: matches the int32 reference "
                  f"(compile {c.compile_s:.2f}s)")

    hkv, g, hd, ps = cfg.n_kv_heads, cfg.n_heads // cfg.n_kv_heads, cfg.resolved_head_dim, 16
    n_tbl = 36                                  # 576 rows per lane
    lengths = np.concatenate([[1, n_tbl * ps], rng.integers(1, n_tbl * ps, lanes - 2)])
    for kind in ("bf16", "int8"):
        check_paged_attention(rng, kind, lanes, (hkv, g, hd), n_tbl, lengths)
    # the served geometry: 32 lanes of 257-page tables, lengths on and
    # either side of a block boundary, inactive and full lanes
    lanes, n_tbl = 32, 257
    for geometry in ((8, 4, 128), (8, 3, 64)):      # mistral-nemo, granite-3.0
        hkv, _, hd = geometry
        pools = [jax.ShapeDtypeStruct((1, ps, hkv * hd), jnp.int8)] * 2 + [
            jax.ShapeDtypeStruct((1, 2, hkv * ps), jnp.float32)]
        rows = ps * pages_per_block(ps, page_bytes(pools), n_tbl)
        edges = [1, rows - 1, rows, rows + 1, n_tbl * ps]
        lengths = np.concatenate([edges, rng.choice(
            [1, 1, 600, 1065, 2100], lanes - len(edges))])
        check_paged_attention(rng, "int8", lanes, geometry, n_tbl, lengths)


def check_paged_attention(rng, kind, lanes, geometry, n_tbl, lengths) -> None:
    """``paged_attention`` on random pages of the last of two stacked
    layers against its reference: tables of distinct random pages (page 0
    is the trash page), ``lengths`` rows."""
    import jax
    import jax.numpy as jnp

    from repro.kernels.paged_attention import paged_attention, paged_attention_ref

    (hkv, g, hd), ps = geometry, 16
    n_pages = lanes * n_tbl + 1
    tables = jnp.asarray(rng.permutation(np.arange(1, n_pages))
                         .reshape(lanes, n_tbl), jnp.int32)
    lengths = jnp.asarray(lengths, jnp.int32)
    q = jnp.asarray(rng.normal(size=(lanes, hkv, g, hd)), jnp.bfloat16)
    pool = (2, n_pages, ps, hkv * hd)
    if kind == "int8":
        kp = jnp.asarray(rng.integers(-127, 128, pool), jnp.int8)
        vp = jnp.asarray(rng.integers(-127, 128, pool), jnp.int8)
        scales = dict(scales=jnp.asarray(
            rng.uniform(5e-3, 2e-2, (2, n_pages, 2, hkv * ps)), jnp.float32))
    else:
        kp = jnp.asarray(rng.normal(size=pool), jnp.bfloat16)
        vp = jnp.asarray(rng.normal(size=pool), jnp.bfloat16)
        scales = {}
    with compile_clock() as c:
        got = paged_attention(q, kp, vp, tables, lengths, jnp.int32(1), **scales)
        got.block_until_ready()
    last = {k: v[1:] for k, v in scales.items()}
    with jax.default_matmul_precision("highest"):
        want = paged_attention_ref(q, kp[1:], vp[1:], tables, lengths, 0, **last)
    got, want = np.asarray(got), np.asarray(want)
    assert np.isfinite(got).all(), f"paged_attention {kind}: non-finite"
    err = float(np.max(np.abs(got - want)) / np.max(np.abs(want)))
    assert err <= ATTN_TOL, f"paged_attention {kind}: rel err {err} > {ATTN_TOL}"
    print(f"[kernels] paged_attention {kind} H_kv={hkv} G={g} D={hd} "
          f"page={ps} lanes={lanes} table={n_tbl}: max err {err:.3e} of scale "
          f"(tol {ATTN_TOL}) (compile {c.compile_s:.2f}s)")


# -- phase 2: the engine ----------------------------------------------------

def _check_finished(outs, prompts, gen, eos):
    assert len(outs) == len(prompts)
    for o, p in zip(outs, prompts):
        assert o.prompt_token_ids == list(p)
        n = len(o.token_ids)
        ok = n == gen or (eos is not None and n and o.token_ids[-1] == eos)
        assert ok, f"request {o.request_id}: {n} tokens, {o.finish_reason}"


def engine_run(label: str, flags, seed: int, *, paper_path: bool):
    import jax

    from repro.api import LLM
    from repro.backends import resolve_backend
    from repro.models.attention import _resolve_paged_impl

    runtime = _serve_runtime(*flags, "--gen", str(GEN),
                             "--prompt-len", str(max(PROMPT_LENS)))
    with compile_clock() as init:
        llm = LLM(arch=LLAMA, runtime=runtime, seed=seed)
        jax.block_until_ready(llm.params)
    cfg = llm.config
    _assert_full_width(cfg, LLAMA)
    prompts = _prompts(np.random.default_rng(seed), PROMPT_LENS, cfg.vocab_size)
    with compile_clock() as c:
        outs = llm.generate(prompts)
    _check_finished(outs, prompts, GEN, runtime.eos_token)
    tokens = sum(len(o.token_ids) for o in outs)
    print(f"[engine] {label}: {len(outs)} requests finished, {tokens} tokens; "
          f"smoke timings, not a benchmark: init {init.wall_s:.1f}s, "
          f"wall {c.wall_s:.1f}s of which compile {c.compile_s:.1f}s")
    if paper_path:
        backend = resolve_backend(cfg.quant_mode, cfg.gemm_backend)[0].name
        impl = _resolve_paged_impl(cfg)
        assert backend == PAPER_GEMM_BACKEND, backend
        assert impl == PAPER_PAGED_IMPL, impl
        eng = llm.engine
        active = np.ones((eng.engine_cfg.n_slots,), bool)
        hlo = eng._decode_sample.lower(
            llm.params, eng._tokens, eng.store.cache, active, eng._temps,
            eng._topk, eng._greedy, eng._keys, False).as_text()
        n_kernels = hlo.count("tpu_custom_call")
        assert n_kernels, "decode step holds no Pallas kernel"
        print(f"[engine] {label}: GEMM backend {backend}, paged attention "
              f"{impl}, {n_kernels} tpu_custom_call sites in the decode step")
    del llm, outs
    gc.collect()


def one_chip(seed: int) -> None:
    kernel_phase(seed)
    engine_run("cli defaults (bf16, slot cache)", [], seed, paper_path=False)
    engine_run("paper path (int8_spoga, paged int8 KV)",
               ["--quant-mode", "int8_spoga", "--cache-mode", "paged",
                "--kv-cache-dtype", "int8"], seed, paper_path=True)


# -- --tp: the tensor-parallel path -----------------------------------------

def _tp_runtime(tp: int):
    return _serve_runtime("--quant-mode", "int8_spoga", "--cache-mode", "paged",
                          "--kv-cache-dtype", "int8", "--tp", str(tp),
                          "--gen", str(TP_GEN),
                          "--prompt-len", str(max(TP_PROMPT_LENS)))


def _prefill_logits(llm, tokens):
    import contextlib

    import jax

    from repro.models.model import forward

    ctx = llm.mesh if llm.mesh is not None else contextlib.nullcontext()
    with ctx:
        fn = jax.jit(lambda p, t: forward(p, llm.config, {"tokens": t}))
        return np.asarray(fn(llm.params, tokens))


def tensor_parallel(tp: int, seed: int) -> None:
    import jax
    import jax.numpy as jnp

    from repro.api import LLM
    from repro.configs import get_config
    from repro.shard import describe_mesh

    # 1. the same widths cut to a depth one chip holds: tp=1 vs tp=N on the
    # same weights (built once on one chip, then resharded)
    cut = get_config(NEMO).with_(n_layers=TP_CUT_LAYERS)
    tokens = jnp.asarray(np.random.default_rng(seed + 1).integers(
        0, cut.vocab_size, (1, 128)), jnp.int32)
    llm = LLM(config=cut, runtime=_tp_runtime(1), seed=seed)
    ref = _prefill_logits(llm, tokens)
    params = llm.params
    del llm
    llm = LLM(config=cut, runtime=_tp_runtime(tp), params=params)
    del params
    got = _prefill_logits(llm, tokens)
    del llm
    gc.collect()
    assert np.isfinite(got).all() and got.shape == ref.shape
    err = float(np.max(np.abs(got - ref)) / np.max(np.abs(ref)))
    same = float(np.mean(got.argmax(-1) == ref.argmax(-1)))
    print(f"[tp] {TP_CUT_LAYERS}-layer cut, prefill logits tp={tp} vs tp=1: "
          f"max err {err:.3e} of scale (tol {TP_LOGIT_TOL}); argmax agrees "
          f"at {same:.4f} of positions")
    assert err <= TP_LOGIT_TOL, f"tp={tp} logits off by {err}"

    # 2. full width on the mesh: the model does not fit one chip
    runtime = _tp_runtime(tp)
    with compile_clock() as init:
        llm = LLM(arch=NEMO, runtime=runtime, seed=seed)
        jax.block_until_ready(llm.params)
    _assert_full_width(llm.config, NEMO)
    per_dev = sum(s.data.nbytes for leaf in jax.tree_util.tree_leaves(llm.params)
                  for s in leaf.addressable_shards if s.device == jax.devices()[0])
    print(f"[tp] {NEMO} full width ({llm.config.n_layers} layers) on "
          f"{describe_mesh(llm.mesh)}: {per_dev / 2**30:.2f} GiB of params on "
          f"device 0 (init {init.wall_s:.1f}s)")
    prompts = _prompts(np.random.default_rng(seed), TP_PROMPT_LENS,
                       llm.config.vocab_size)
    with compile_clock() as c:
        outs = llm.generate(prompts)
    _check_finished(outs, prompts, TP_GEN, runtime.eos_token)
    print(f"[tp] {NEMO} tp={tp}: {len(outs)} requests finished, "
          f"{sum(len(o.token_ids) for o in outs)} tokens; smoke timings, not "
          f"a benchmark: wall {c.wall_s:.1f}s of which compile {c.compile_s:.1f}s")


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--tp", type=int, default=None,
                    help="run only the tensor-parallel path on this many chips")
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    import jax

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"chip_smoke: JAX's first device is {devices[0].platform!r}, "
              "not a TPU; nothing was run", file=sys.stderr)
        return 1
    device = {"platform": devices[0].platform, "kind": devices[0].device_kind,
              "count": len(devices)}
    print(f"[smoke] {device['count']} x {device['kind']}")
    need = args.tp or 1
    if len(devices) < need:
        print(f"chip_smoke: --tp {need} needs {need} chips, found "
              f"{len(devices)}", file=sys.stderr)
        return 1

    sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)), "src"))
    from repro.launch.compile_cache import enable_compile_cache

    print(f"[smoke] compile cache: {enable_compile_cache()}")
    if args.tp:
        tensor_parallel(args.tp, args.seed)
    else:
        one_chip(args.seed)
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    sys.exit(main())
