"""Serving CLI — a thin consumer of the ``repro.api`` facade.

Every flag maps onto one field of the layered ``RuntimeConfig``; the CLI
builds it, hands it to ``LLM``, and drives the engine with a synthetic
staggered-arrival workload:

    PYTHONPATH=src python -m repro.launch.serve --arch llama3.2-1b \
        --reduced --requests 8 --slots 4 --prompt-len 32 --gen 16 --stagger 2

``--cache-mode paged`` serves from the global page pool (block tables,
optional ``--prefill-chunk`` chunked long-prompt admission, int8 byte-size
pages via ``--kv-cache-dtype int8``, ``--paged-attn pallas_interpret`` to
force the Pallas kernel through the interpreter off-TPU).
``--prefix-cache`` turns on the shared-prefix KV cache (``repro/prefix/``:
admissions alias cached prompt-prefix pages and prefill only the suffix —
pair it with ``--shared-prefix N`` to give the synthetic workload an
N-token common system prompt).  ``--batched-admission`` stacks same-bucket
prompts into one prefill dispatch (slot and paged modes);
``--admission priority`` ranks the queue by ``Request.priority`` with
starvation-free aging (``prefix-aware`` admits hot-prefix requests
back-to-back); ``--defrag-threshold`` tunes (or ``-1`` disables) the pool
compaction policy; ``--spec K`` turns on speculative decoding (K drafted
tokens per verify dispatch, ``--draft ngram|model``); ``--stream`` prints
every token the moment it reaches the host.

``--runtime SPEC`` sidesteps the per-knob flags entirely: SPEC is a JSON
file (``RuntimeConfig.from_dict``) or a registered preset name
(``repro.api.list_presets()``), and the quant/KV/scheduler flags are
ignored in its favour — only workload flags (``--requests``/
``--prompt-len``/``--gen``/...) still apply.

``--static`` (and enc-dec / frontend archs, which the engine does not
admit) falls back to the lockstep baseline ``repro.api.serve_batch`` —
kept both as the reference implementation the engine is tested against and
as the baseline ``benchmarks/serve_bench.py`` beats.

Observability (``repro/obs/``): ``--trace out.json`` writes the engine's
span timeline as Chrome trace-event JSON (load at https://ui.perfetto.dev);
``--events out.jsonl`` writes the scheduler decision log (one JSON object
per admit/reject/chunk/CoW/defrag/finish event); ``--profile DIR`` wraps
the first ``--profile-steps`` engine steps in a ``jax.profiler`` device
trace, where the engine's phases show as ``engine.*`` host spans beside
the device's ops; ``--debug-invariants`` checks the page
pool's bookkeeping after every step.  ``--metrics-port P`` serves a live
Prometheus ``/metrics`` endpoint (plus ``/healthz`` and a JSON
``/snapshot``) off the engine's registries; ``--watchdog`` arms the
numerics watchdog (per-layer saturation counters and amax/quant-error
histograms from inside the quantized GEMM pipeline, bitwise
output-invisible); ``--deadline SEC`` attaches an SLO deadline to every
synthetic request so the run reports goodput and hit/miss counts.  All
off by default — the disabled engine runs with null sinks and zero extra
host syncs.

``--tp N`` serves tensor-parallel (``repro/shard``): params, attention
heads, MoE experts and the paged KV pool shard over an N-way "model" mesh
axis while block tables stay host-side and replicated, so prefill /
decode / verify each remain one pjit dispatch per step.  Off-accelerator,
fake the devices with ``XLA_FLAGS=--xla_force_host_platform_device_count=N``.
"""

from __future__ import annotations

import argparse
import dataclasses

import jax
import numpy as np

from repro.api import (
    LLM,
    KVConfig,
    MeshConfig,
    ObsConfig,
    QuantRuntime,
    RuntimeConfig,
    SamplingDefaults,
    SchedulerConfig,
    SpecConfig,
    list_presets,
    load_runtime,
    serve_batch,
)
from repro.configs import default_cache_len
from repro.launch.compile_cache import enable_compile_cache
from repro.models.frontends import fake_audio_frames, fake_vision_embeds


def synthetic_workload(cfg, n_requests: int, prompt_len: int, gen: int,
                       stagger: int, seed: int = 0, shared_prefix: int = 0):
    """Mixed-length prompts/budgets around the nominal sizes, arriving every
    ``stagger`` engine steps — a deterministic stand-in for live traffic.
    ``shared_prefix`` prepends a common system prompt of that many tokens
    to every request (the workload the prefix cache accelerates)."""
    rng = np.random.default_rng(seed)
    prefix = rng.integers(0, cfg.vocab_size, shared_prefix).tolist()
    arrivals = []
    for i in range(n_requests):
        plen = int(rng.integers(max(1, prompt_len // 2), prompt_len + 1))
        budget = int(rng.integers(max(1, gen // 2), gen + 1))
        prompt = prefix + rng.integers(0, cfg.vocab_size, plen).tolist()
        arrivals.append((i * stagger, prompt, budget))
    return arrivals


def _static_main(llm: LLM, args) -> None:
    cfg, params = llm.config, llm.params
    key = jax.random.PRNGKey(0)
    kt, ke = jax.random.split(key)
    if cfg.is_encoder_decoder:
        batch = {
            "src_embeds": fake_audio_frames(ke, cfg, args.batch, args.prompt_len),
            "tgt_tokens": jax.random.randint(kt, (args.batch, 8), 0, cfg.vocab_size),
        }
    elif cfg.frontend is not None:
        batch = {"embeds": fake_vision_embeds(ke, cfg, args.batch, args.prompt_len)}
    else:
        batch = {"tokens": jax.random.randint(kt, (args.batch, args.prompt_len), 0,
                                              cfg.vocab_size)}
    cache_len = default_cache_len(args.prompt_len, args.gen)
    tokens, stats = serve_batch(cfg, params, batch, cache_len=cache_len,
                                gen_tokens=args.gen)
    tps = args.batch * args.gen / stats["decode_s"]
    print(f"[serve] generated {tokens.shape} tokens; prefill {stats['prefill_s']:.2f}s, "
          f"decode {stats['decode_s']:.2f}s ({tps:.1f} tok/s)")
    print("[serve] sample:", tokens[0][:12].tolist())


def _engine_main(llm: LLM, args) -> None:
    # workload hints anchor the 'auto' bucket ladder to the nominal prompt
    # length (auto_buckets(prompt_len), as the pre-facade CLI built it)
    engine = llm.build_engine(args.prompt_len + args.shared_prefix, args.gen)
    if llm.metrics_server is not None:
        print(f"[obs] metrics server at {llm.metrics_server.url}/metrics "
              f"(also /healthz, /snapshot)")
    sampling = llm.runtime.sampling.to_params()
    if args.deadline is not None:
        sampling = dataclasses.replace(sampling, deadline_s=args.deadline)
    arrivals = [(s, p, g, sampling)
                for s, p, g in synthetic_workload(llm.config, args.requests,
                                                  args.prompt_len, args.gen,
                                                  args.stagger, args.seed,
                                                  args.shared_prefix)]
    on_token = (lambda req, tok: print(f"[stream] req {req.req_id}: {tok}",
                                       flush=True)) if args.stream else None
    metrics = engine.run(arrivals, on_token=on_token)
    print(metrics.format_report())
    if engine.paged:
        m = metrics
        print(f"[engine] paged: peak {m.peak_running} concurrent lanes, "
              f"{m.peak_pages_used}/{m.pages_total} pages "
              f"(page_size {m.page_size}), {m.chunk_steps} prefill chunks, "
              f"{m.defrag_count} defrags")
    if engine.prefix is not None:
        m = metrics
        print(f"[engine] prefix cache: {m.prefix_hits} hits / "
              f"{m.prefix_misses} misses, {m.prefix_hit_tokens} prompt "
              f"tokens reused, {m.prefix_cow_forks} CoW forks, "
              f"{m.prefix_evicted_pages} pages evicted, "
              f"{m.prefix_tree_pages} pages cached")
    if metrics.verify_dispatches:
        r = metrics.report()
        print(f"[engine] spec decode: {metrics.spec_accepted}/"
              f"{metrics.spec_proposed} drafts accepted "
              f"(rate {r['acceptance_rate']:.2f}) across "
              f"{metrics.verify_dispatches} verify dispatches")
    if metrics.stacked_prefills:
        print(f"[engine] batched admission: {metrics.prefills} prefills in "
              f"{metrics.prefill_dispatches} dispatches "
              f"({metrics.stacked_prefills} stacked)")
    if metrics.deadline_hits or metrics.deadline_misses:
        r = metrics.report()
        print(f"[engine] SLO: {metrics.deadline_hits} hit / "
              f"{metrics.deadline_misses} missed deadlines "
              f"(hit rate {r['deadline_hit_rate']:.2f}, "
              f"{metrics.deadline_late_admissions} already late at "
              f"admission) | goodput {r['goodput_tokens_per_s']:.1f} tok/s "
              f"of {r['tokens_per_s']:.1f} total")
    if metrics.finished:
        first = min(metrics.finished, key=lambda r: r.req_id)
        print(f"[engine] sample (req {first.req_id}):", first.output_tokens[:12])
    if llm.obs.enabled:
        r = metrics.report()
        print(f"[obs] TTFT p50/p95/p99 {r['ttft_p50_s']*1e3:.1f}/"
              f"{r['ttft_p95_s']*1e3:.1f}/{r['ttft_p99_s']*1e3:.1f} ms | "
              f"per-token p50/p99 {r['per_token_p50_s']*1e3:.2f}/"
              f"{r['per_token_p99_s']*1e3:.2f} ms | "
              f"queue wait p99 {r['queue_wait_p99_s']*1e3:.1f} ms | "
              f"{len(llm.obs.events)} scheduler events, "
              f"{len(llm.obs.tracer.events)} spans")
    if llm.runtime.obs.watchdog:
        from repro.obs import watchdog as _watchdog

        sat = _watchdog.saturation_report()
        if sat:
            worst = sorted(sat.items(), key=lambda kv: -kv[1])[:3]
            rendered = ", ".join(f"{k} {v:.4f}" for k, v in worst)
            print(f"[obs] watchdog: worst at-rail occupancy {rendered}")
    for path in llm.obs.save():
        print(f"[obs] wrote {path}")
    llm.close()
    if llm.obs.recorder is not None:
        print(f"[obs] flight recorder: bundle at {llm.obs.recorder.path} "
              f"(replay with `python -m repro.launch.replay "
              f"{llm.obs.recorder.path}`)")


def _obs_from_args(args) -> ObsConfig:
    return ObsConfig(
        trace=args.trace,
        events=args.events,
        profile_dir=args.profile,
        profile_steps=args.profile_steps,
        debug_invariants=args.debug_invariants,
        metrics_port=args.metrics_port,
        events_max_mb=args.events_max_mb,
        watchdog=args.watchdog,
        record_path=args.record,
    )


def _runtime_from_args(args) -> RuntimeConfig:
    """Flags -> the layered RuntimeConfig (the whole point of the facade:
    this mapping is the CLI's only job)."""
    return RuntimeConfig(
        quant=QuantRuntime(mode=args.quant_mode, gemm_backend=args.gemm_backend),
        kv=KVConfig(
            mode=args.cache_mode,
            dtype=args.kv_cache_dtype,
            cache_len=default_cache_len(args.prompt_len + args.shared_prefix,
                                        args.gen),
            page_size=args.page_size,
            n_pages=args.pages,
            paged_attn_impl=args.paged_attn,
            prefix_cache=args.prefix_cache,
        ),
        scheduler=SchedulerConfig(
            n_slots=args.slots,
            max_prefills_per_step=args.max_prefills,
            prefill_buckets=None if args.no_buckets else "auto",
            prefill_chunk=args.prefill_chunk,
            batched_admission=args.batched_admission,
            admission=args.admission,
            eviction=args.eviction,
            defrag_threshold=(None if args.defrag_threshold < 0
                              else args.defrag_threshold),
        ),
        sampling=SamplingDefaults(
            greedy=args.temperature == 0.0,
            temperature=args.temperature or 1.0,
            top_k=args.top_k,
            seed=args.seed,
        ),
        spec=SpecConfig(
            enabled=args.spec > 0,
            k=args.spec or 4,
            drafter=args.draft,
            draft_arch=args.draft_arch,
        ),
        obs=_obs_from_args(args),
        mesh=MeshConfig(tp=args.tp),
        max_new_tokens=args.gen,
        reduced=args.reduced,
    )


def build_parser() -> argparse.ArgumentParser:
    """The CLI's flags; ``build_parser().parse_args([])`` are its defaults."""
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="llama3.2-1b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--static", action="store_true",
                    help="lockstep static-batch baseline instead of the engine")
    ap.add_argument("--batch", type=int, default=4, help="static path: batch size")
    ap.add_argument("--requests", type=int, default=8, help="engine: request count")
    ap.add_argument("--slots", type=int, default=4, help="engine: KV-cache lanes")
    ap.add_argument("--stagger", type=int, default=2,
                    help="engine: steps between request arrivals")
    ap.add_argument("--max-prefills", type=int, default=1,
                    help="engine: admission dispatches interleaved per step")
    ap.add_argument("--no-buckets", action="store_true",
                    help="engine: exact-length prefill (one trace per length)")
    ap.add_argument("--batched-admission", action="store_true",
                    help="engine: stack same-bucket prompts into one prefill "
                         "dispatch (slot and paged modes)")
    ap.add_argument("--admission", default="fifo",
                    choices=["fifo", "priority", "prefix-aware", "deadline"],
                    help="engine: admission ordering (priority = "
                         "Request.priority with starvation-free aging; "
                         "prefix-aware = requests sharing a hot cached "
                         "prefix admit back-to-back; deadline = FIFO that "
                         "also sheds already-late requests at ingress)")
    ap.add_argument("--eviction", default="budget",
                    choices=["budget", "deadline-preempt"],
                    help="engine: eviction policy (deadline-preempt = "
                         "budget/EOS plus SLO preemption of lanes whose "
                         "request already missed its deadline while queued "
                         "work can still hit)")
    ap.add_argument("--spec", type=int, default=0, metavar="K",
                    help="engine: speculative decoding with K drafted tokens "
                         "per verify dispatch (0 = off; greedy lanes only)")
    ap.add_argument("--draft", default="ngram", choices=["ngram", "model"],
                    help="spec drafter: model-free prompt-lookup n-grams or "
                         "a small draft model (repro/spec/)")
    ap.add_argument("--draft-arch", default=None,
                    help="spec: draft model architecture (default: a "
                         "truncated copy of the target)")
    ap.add_argument("--runtime", default=None,
                    help="RuntimeConfig source: a JSON file (from_dict) or a "
                         f"preset name {list_presets()}; overrides the "
                         "quant/KV/scheduler flags")
    ap.add_argument("--prompt-len", type=int, default=32)
    ap.add_argument("--gen", type=int, default=16)
    ap.add_argument("--temperature", type=float, default=0.0,
                    help="0 = greedy; > 0 samples")
    ap.add_argument("--top-k", type=int, default=0, help="0 = no truncation")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quant-mode", default="bf16")
    ap.add_argument("--gemm-backend", default=None,
                    help="GEMM backend registry name; default auto-selection")
    ap.add_argument("--kv-cache-dtype", default="bf16", choices=["bf16", "int8"],
                    help="int8: SPOGA-style byte-size KV cache (+scales)")
    ap.add_argument("--cache-mode", default="slot", choices=["slot", "paged"],
                    help="paged: global page pool + block tables (repro/paging)")
    ap.add_argument("--page-size", type=int, default=16,
                    help="paged: tokens per KV page")
    ap.add_argument("--pages", type=int, default=None,
                    help="paged: pool size in pages (default: slot-equivalent budget)")
    ap.add_argument("--prefill-chunk", type=int, default=None,
                    help="paged: admit long prompts in chunks of this many "
                         "tokens (multiple of page-size), interleaved with decode")
    ap.add_argument("--prefix-cache", action="store_true",
                    help="paged: shared-prefix KV cache (radix tree + "
                         "copy-on-write pages; repro/prefix/)")
    ap.add_argument("--shared-prefix", type=int, default=0,
                    help="workload: prepend a common system prompt of this "
                         "many tokens to every request")
    ap.add_argument("--defrag-threshold", type=float, default=0.5,
                    help="paged: compact the pool when fragmentation crosses "
                         "this ratio (-1 disables)")
    ap.add_argument("--paged-attn", default=None,
                    choices=["jnp", "pallas", "pallas_interpret"],
                    help="paged attention impl (default: auto by platform)")
    ap.add_argument("--stream", action="store_true",
                    help="engine: print every token as it reaches the host")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="obs: write the engine span timeline as Chrome "
                         "trace-event JSON (load in Perfetto)")
    ap.add_argument("--events", default=None, metavar="OUT.jsonl",
                    help="obs: write the scheduler decision log as JSONL")
    ap.add_argument("--profile", default=None, metavar="DIR",
                    help="obs: jax.profiler device trace over the first "
                         "--profile-steps engine steps, written under DIR")
    ap.add_argument("--profile-steps", type=int, default=20,
                    help="obs: engine steps the --profile window covers")
    ap.add_argument("--debug-invariants", action="store_true",
                    help="obs: check page-pool invariants after every step")
    ap.add_argument("--metrics-port", type=int, default=None, metavar="PORT",
                    help="obs: serve live /metrics (Prometheus text "
                         "exposition) + /healthz + /snapshot on this port "
                         "(0 = ephemeral; URL printed at startup)")
    ap.add_argument("--events-max-mb", type=float, default=64.0,
                    help="obs: rotate the --events JSONL stream past this size")
    ap.add_argument("--record", default=None, metavar="DIR",
                    help="obs: arm the flight recorder — capture the run "
                         "(config fingerprint, arrivals, decision journal, "
                         "outputs, decision-clock tape) into DIR; replay "
                         "with `python -m repro.launch.replay DIR`")
    ap.add_argument("--watchdog", action="store_true",
                    help="obs: numerics watchdog — per-layer saturation/"
                         "clip counters and amax/quant-error histograms "
                         "from inside the quantized GEMM pipeline "
                         "(output-invisible; retraces the jits)")
    ap.add_argument("--deadline", type=float, default=None, metavar="SEC",
                    help="SLO: per-request deadline in seconds from submit; "
                         "finished-late requests count as misses and drop "
                         "out of goodput")
    ap.add_argument("--tp", type=int, default=1,
                    help="tensor-parallel degree: shard params, attention "
                         "heads, experts and the paged KV pool over a "
                         "'model' mesh axis (repro/shard). Needs "
                         "jax.device_count() divisible by tp; use "
                         "XLA_FLAGS=--xla_force_host_platform_device_count=N "
                         "to fake a multi-device host mesh")
    return ap


def main():
    args = build_parser().parse_args()
    enable_compile_cache()

    runtime = (load_runtime(args.runtime) if args.runtime
               else _runtime_from_args(args))
    if args.runtime:
        # obs + reduced are session flags, not deployment profile state:
        # they apply on top of whatever --runtime loaded
        if args.reduced:
            runtime = dataclasses.replace(runtime, reduced=True)
        obs = _obs_from_args(args)
        if obs != ObsConfig():
            runtime = dataclasses.replace(runtime, obs=obs)
        if args.tp != 1:
            runtime = dataclasses.replace(runtime, mesh=MeshConfig(tp=args.tp))
    llm = LLM(arch=args.arch, runtime=runtime)
    cfg = llm.config
    engine_capable = not cfg.is_encoder_decoder and cfg.frontend is None
    if args.static or not engine_capable:
        if not engine_capable and not args.static:
            print(f"[serve] {cfg.name}: enc-dec/frontend arch — static path")
        _static_main(llm, args)
    else:
        _engine_main(llm, args)


if __name__ == "__main__":
    main()
