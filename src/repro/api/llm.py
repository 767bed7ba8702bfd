"""The ``LLM`` facade: one entrypoint over configs, params, checkpointing
and the continuous-batching engine.

    from repro.api import LLM, RuntimeConfig, KVConfig

    llm = LLM(arch="llama3.2-1b",
              runtime=RuntimeConfig(reduced=True, kv=KVConfig(mode="paged")))
    outs = llm.generate([[1, 2, 3], [4, 5]], max_new_tokens=8)
    for piece in llm.stream([1, 2, 3], detokenize=True):
        print(piece, end="")

``LLM`` owns parameter init (or checkpoint restore), resolves the layered
``RuntimeConfig`` into the legacy ``ModelConfig`` overrides + engine
config, builds the engine policies, and drives the engine for you.  The
engine is built lazily: when ``kv.cache_len`` is unset, the first
``generate``/``stream`` call sizes the cache from its own workload (the
shared ``default_cache_len`` policy) and later, larger workloads rebuild
the engine between calls (jit caches are keyed by (config, cache_len), so
rebuilds reuse compiled traces).
"""

from __future__ import annotations

import dataclasses
from typing import Callable, Iterator, Optional, Sequence, Union

import jax
import numpy as np

from repro.api.config import RuntimeConfig
from repro.api.outputs import RequestOutput
from repro.configs import get_config, reduced as reduce_config
from repro.serving.engine import ServingEngine
from repro.serving.policies import EnginePolicies
from repro.serving.request import RequestState, default_detokenizer
from repro.serving.sampling import SamplingParams
from repro.shard import build_mesh, init_sharded_params, shard_params

Prompt = Sequence[int]


class LLM:
    """One generation endpoint: ``LLM(arch=...)`` then ``.generate`` /
    ``.stream``.  Advanced callers reach the underlying ``ServingEngine``
    via ``.engine`` (e.g. for staggered-arrival workloads)."""

    def __init__(self, arch: Optional[str] = None, *,
                 runtime: Optional[RuntimeConfig] = None,
                 config=None, params=None,
                 tokenizer: Optional[Callable[[Sequence[int]], str]] = None,
                 checkpoint_dir: Optional[str] = None,
                 policies: Optional[EnginePolicies] = None,
                 seed: int = 0):
        if (arch is None) == (config is None):
            raise ValueError("pass exactly one of arch= (registry name) or "
                             "config= (a ModelConfig)")
        self.runtime = runtime if runtime is not None else RuntimeConfig()
        base = get_config(arch) if config is None else config
        if self.runtime.reduced:
            base = reduce_config(base)
        # the single resolution step (model side): RuntimeConfig owns the
        # runtime knobs; the result is the plain frozen ModelConfig jit keys on
        self.config = self.runtime.resolve_model(base)
        # sharded serving (repro/shard/): resolve the per-arch Megatron
        # PartitionSpecs into NamedShardings and commit the weights once,
        # here — every engine dispatch then sees the TP layout as a stable
        # input constraint.  mesh=None (the default config) changes nothing.
        self.mesh = build_mesh(self.runtime.mesh)
        if params is None:
            # built already sharded: no device ever holds the whole model
            params = init_sharded_params(self.config, jax.random.PRNGKey(seed),
                                         self.mesh)
        if checkpoint_dir is not None:
            from repro.checkpoint.checkpoint import restore_checkpoint

            params = restore_checkpoint(checkpoint_dir, None, params)
        if self.mesh is not None:
            params = shard_params(params, self.mesh, self.config)
        self.params = params
        self.tokenizer = tokenizer or default_detokenizer
        self._policies = (policies if policies is not None
                          else self.runtime.build_policies())
        # one observability bundle per LLM: it outlives engine rebuilds
        # (spans/events accumulate across them, like metrics), and
        # ``llm.obs.save()`` writes the configured trace/event sinks
        self.obs = self.runtime.obs.build()
        if self.obs.recorder is not None:
            # flight recorder armed: stamp everything replay needs to
            # rebuild this model (repro/obs/recorder.py bundle manifest)
            self.obs.recorder.set_run_info(
                arch=arch, runtime=self.runtime, seed=seed,
                checkpoint_dir=checkpoint_dir)
        self._engine: Optional[ServingEngine] = None
        # live telemetry frontend: a stdlib HTTP server polling the engine's
        # registry (plus the numerics watchdog's, when armed) on each
        # scrape.  Pure pull — nothing on the dispatch path knows about it.
        self.metrics_server = None
        if self.runtime.obs.metrics_port is not None:
            from repro.obs.server import MetricsServer

            self.metrics_server = MetricsServer(
                self._collect_metrics,
                port=self.runtime.obs.metrics_port,
                events=lambda: self.obs.events).start()

    def _collect_metrics(self):
        """Scrape-time collector: registries + cheap derived gauges.
        Derived values read host-side counters only (no ``report()``, no
        device sync), so a scrape never perturbs the run."""
        from repro.obs import watchdog as _watchdog

        regs, derived = [], {}
        m = self._engine.metrics if self._engine is not None else None
        if m is not None:
            regs.append(m.registry)
            wall = m.wall_s
            toks = m.generated_tokens
            derived["wall_seconds"] = wall
            derived["generated_tokens"] = float(toks)
            derived["tokens_per_second"] = toks / max(wall, 1e-9)
            derived["goodput_tokens_per_second"] = (
                m.goodput_tokens / max(wall, 1e-9))
            derived["requests_finished"] = float(len(m.finished))
        wreg = _watchdog.peek_registry()
        if wreg is not None:
            regs.append(wreg)
        return regs, derived

    def close(self) -> None:
        """Stop the metrics server (if any) and close event/trace sinks.
        Idempotent; the LLM stays usable for generate/stream afterwards
        minus the closed sinks."""
        if self.metrics_server is not None:
            self.metrics_server.close()
            self.metrics_server = None
        self.obs.close()

    @staticmethod
    def replay(bundle_path: str, runtime_transform=None,
               max_steps: int = 100_000):
        """Replay a flight-recorder bundle (``ObsConfig.record_path`` /
        ``serve --record DIR``): rebuild the recorded engine, re-feed the
        recorded arrivals on their step schedule, and compare token
        streams + decision journal bitwise.  Returns a
        ``repro.obs.replay.ReplayResult``; ``runtime_transform`` perturbs
        the rebuilt ``RuntimeConfig`` on purpose so the divergence differ
        can name the first decision that changes."""
        from repro.obs.replay import replay_bundle

        return replay_bundle(bundle_path, runtime_transform=runtime_transform,
                             max_steps=max_steps)

    # -- engine lifecycle --------------------------------------------------
    def _ensure_engine(self, prompt_len: int, gen_tokens: int) -> ServingEngine:
        need = prompt_len + gen_tokens
        if self._engine is not None:
            if (need <= self._engine.engine_cfg.cache_len + 1
                    or self.runtime.kv.cache_len is not None):
                # fits — or the user pinned cache_len, in which case
                # add_request raises its own sizing error
                return self._engine
            if self._engine.has_work:
                raise RuntimeError(
                    "cannot grow the KV cache while requests are in flight; "
                    "drain the engine first or set kv.cache_len up front")
        ecfg = self.runtime.resolve_engine(self.config, prompt_len, gen_tokens)
        old = self._engine
        if old is not None:
            # grow monotonically so earlier workloads keep fitting
            ecfg = dataclasses.replace(
                ecfg, cache_len=max(ecfg.cache_len, old.engine_cfg.cache_len))
        self._engine = ServingEngine(self.config, self.params, ecfg,
                                     policies=self._policies, obs=self.obs,
                                     mesh=self.mesh)
        if old is not None:
            # metrics accumulate across rebuilds: carry the old object over
            # (held references stay live) with the new pool geometry stamped
            carried = old.metrics
            carried.set_gauge("pages_total", self._engine.metrics.pages_total)
            carried.set_gauge("page_size", self._engine.metrics.page_size)
            self._engine.metrics = carried
        return self._engine

    def build_engine(self, prompt_len: int, gen_tokens: int) -> ServingEngine:
        """Build (or reuse) the engine for a nominal workload — the hints
        size the cache when ``kv.cache_len`` is unset and anchor the
        'auto' prefill-bucket ladder to real prompt lengths.  This is what
        ``generate``/``stream`` call internally; use it directly when
        driving ``engine.run`` / ``engine.step`` yourself."""
        return self._ensure_engine(prompt_len, gen_tokens)

    @property
    def engine(self) -> ServingEngine:
        """The underlying engine (built on demand; requires ``kv.cache_len``
        to be set when no generate/stream/build_engine call has sized it
        yet — and with 'auto' buckets, prefer ``build_engine`` so the
        ladder anchors to the workload's prompt length, not cache_len)."""
        if self._engine is None:
            if self.runtime.kv.cache_len is None:
                raise RuntimeError(
                    "engine not built yet: set RuntimeConfig.kv.cache_len, "
                    "call build_engine(prompt_len, gen_tokens), or issue a "
                    "generate()/stream() call to size it from the workload")
            self._ensure_engine(0, 1)
        return self._engine

    @property
    def metrics(self):
        return self._engine.metrics if self._engine is not None else None

    # -- sampling plumbing -------------------------------------------------
    def _sampling_for(self, n: int, sampling) -> list[SamplingParams]:
        if sampling is None:
            return [self.runtime.sampling.to_params()] * n
        if isinstance(sampling, SamplingParams):
            return [sampling] * n
        sampling = list(sampling)
        if len(sampling) != n:
            raise ValueError(f"got {len(sampling)} SamplingParams for {n} prompts")
        return sampling

    # -- the public calls --------------------------------------------------
    def generate(self, prompts: Union[Prompt, Sequence[Prompt]],
                 sampling: Union[SamplingParams, Sequence[SamplingParams], None] = None,
                 max_new_tokens: Optional[int] = None,
                 detokenize: bool = False) -> list[RequestOutput]:
        """Generate for one prompt (flat token-id list) or many.  Returns
        ``RequestOutput``s in prompt order; scheduling is output-invisible,
        so each entry's greedy tokens equal a solo decode of that prompt."""
        prompts = list(prompts)
        if prompts and isinstance(prompts[0], (int, np.integer)):
            prompts = [prompts]
        if not prompts:
            return []
        gen = max_new_tokens if max_new_tokens is not None else self.runtime.max_new_tokens
        per_req = self._sampling_for(len(prompts), sampling)
        engine = self._ensure_engine(max(len(p) for p in prompts), gen)
        reqs = [engine.add_request(p, gen, sampling=s,
                                   detokenizer=self.tokenizer)
                for p, s in zip(prompts, per_req)]
        while engine.has_work:
            engine.step()
        detok = self.tokenizer if detokenize else None
        # with observability on, each output carries its scheduler timeline
        # (queued -> admitted -> chunks -> first_token -> finished events)
        return [RequestOutput.from_request(
            r, detok, timeline=self.obs.events.timeline(r.req_id) or None)
            for r in reqs]

    def stream(self, prompt: Prompt,
               sampling: Optional[SamplingParams] = None,
               max_new_tokens: Optional[int] = None,
               eos_token: Optional[int] = None,
               detokenize: bool = False) -> Iterator[Union[int, str]]:
        """Submit one request and yield its output as the engine produces
        it — token ids by default, detokenized text fragments with
        ``detokenize=True`` (the ``Request.on_text`` hook; fragments
        concatenate to the full decode).  Other queued requests advance
        normally between yields."""
        gen = max_new_tokens if max_new_tokens is not None else self.runtime.max_new_tokens
        engine = self._ensure_engine(len(prompt), gen)
        emitted: list = []
        hook = ({"on_text": emitted.append, "detokenizer": self.tokenizer}
                if detokenize else {"on_token": emitted.append})
        req = engine.add_request(prompt, gen,
                                 sampling=self._sampling_for(1, sampling)[0],
                                 eos_token=eos_token, **hook)
        i = 0
        while True:
            while i < len(emitted):
                yield emitted[i]
                i += 1
            if req.state is RequestState.FINISHED or not engine.has_work:
                break
            engine.step()
        yield from emitted[i:]
