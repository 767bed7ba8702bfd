"""Continuous-batching serving engine.

One ``ServingEngine`` owns a fixed pool of ``n_slots`` KV-cache lanes and
runs an iteration-level loop: every ``step()``

1. **admits** up to ``max_prefills_per_step`` FIFO-queued requests into
   free lanes — each admission is a batch=1 prefill (optionally padded to a
   prefill bucket so jit traces stay bounded) whose cache is scattered into
   the lane, and whose last-position logits yield the request's *first*
   token (the TTFT token); in paged mode, long prompts instead stream in as
   page-sized **chunked prefills** interleaved with decode steps, so one
   big admission can no longer stall in-flight decodes;
2. **decodes** one token for every occupied lane in a single jitted
   ``decode_step`` over the whole pool — fixed shapes, zero retraces —
   sampling per-lane (greedy / temperature / top-k);
3. **evicts** finished lanes (length budget or EOS) immediately, so the
   next step can refill them instead of burning compute on dead lanes.

WHICH requests admit, WHEN a lane evicts, WHEN the paged pool compacts
and HOW cached prefixes are reused are pluggable
``policies.EnginePolicies`` (admission / eviction / defrag / prefix):
the defaults reproduce FIFO + budget-or-EOS and add threshold-triggered
defrag; ``BucketBatchedAdmission`` stacks same-bucket prompts into one
batched prefill dispatch (slot AND paged modes — paged groups scatter
per-lane pages); ``PriorityAdmission`` ranks by ``Request.priority`` with
starvation-free aging.  New scheduling scenarios are new policy classes,
not engine surgery.

With ``EngineConfig.prefix_cache`` (paged, chunkable stacks) admissions
consult the shared-prefix radix tree (``repro/prefix/``): the longest
page-aligned cached prefix is aliased into the lane's block table
(refcounted pages, copy-on-write on the boundary page for full-prompt
hits) and only the uncached suffix runs through the chunk step; completed
prefills publish their full pages back, and the tree LRU-evicts under
pool pressure inside the admission gate.  Scheduling stays
output-invisible: greedy tokens with the cache ON are bitwise the cache-
OFF (and solo ``serve_batch``) streams.

Two cache modes (``EngineConfig.cache_mode``):

* ``"slot"``  — ``slots.SlotCache``: every lane preallocates ``cache_len``
  rows.  Simple, but a pool serving mixed-length traffic wastes most of
  its KV HBM on short requests.
* ``"paged"`` — ``paging.PagedCache``: KV lives in a global page pool
  (int8 byte-size pages supported) indexed by per-lane block tables;
  admission *reserves* a request's worst case but pages materialize only
  as the sequence grows, and eviction returns them the same step.  Same
  budget, strictly more concurrent requests on mixed lengths.  Scheduling
  stays output-invisible: greedy tokens equal the solo ``serve_batch``
  stream in both modes.

This is what keeps a byte-size integer GEMM accelerator fed: the decode
GEMMs always run at the full pool batch, prefill is interleaved instead of
lock-stepped, and a long request never stalls the batch (the failure mode
of the static ``serve_batch`` baseline).

The model side is the ordinary ``launch/steps.py`` builders, so the whole
quantized ``gemm_backend`` pipeline (Pallas SPOGA kernels, int8 KV cache,
parametric quant modes) serves every engine step unchanged.

Supported: decoder-only token-input stacks (any cache kind, including MLA
and recurrent state).  Prefill buckets require attention-family caches —
recurrent state integrates right-padding — so bucketed padding is rejected
for rglru/mlstm/slstm patterns at construction time.
"""

from __future__ import annotations

import dataclasses
import functools
import time
from typing import Iterator, Optional, Sequence

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs.base import (
    DEFAULT_PAGE_SIZE,
    KV_CACHE_HEADROOM,
    ModelConfig,
    default_cache_len,
    pages_for,
)
from repro.kernels.paged_attention import page_bytes, pages_per_block
from repro.launch.steps import make_prefill_step, make_serve_step
from repro.obs import DISABLED, Observability
from repro.paging import (
    PagedCache,
    chunkable,
    chunkable_with_state,
    make_chunk_step,
    paged_insert,
    paged_insert_many,
    stack_kinds,
)
from repro.prefix import PrefixCache
from repro.serving.metrics import EngineMetrics
from repro.serving.policies import EnginePolicies
from repro.serving.request import Request, RequestState
from repro.serving.sampling import SamplingParams, request_key, sample_tokens
from repro.serving.scheduler import Scheduler
from repro.serving.slots import SlotCache
from repro.spec.config import SpecConfig
from repro.spec.verify import jitted_verify

RECURRENT_KINDS = frozenset({"rglru", "mlstm", "slstm"})
# effective kinds whose KV lands in page pools (models/kvcache.py); a
# window-bearing local_attn keeps its per-lane ring in both modes
PAGED_KINDS = frozenset({"attn", "mla", "moe", "dense_ffn_layer"})

_ZERO_KEY = np.zeros((2,), np.uint32)

_sample_jit = jax.jit(sample_tokens)


def _attn_block_geometry(store: PagedCache):
    """(rows per block, blocks per table) of the paged-attention kernel on
    this pool's K/V pages as one device holds them, or None for a stack
    with no K/V pool (MLA latents, recurrent state)."""
    cache = store.cache
    for blk in (*cache["head_blocks"], *cache["blocks"], *cache["tail_blocks"]):
        if "kp" in blk:
            pools = [jax.ShapeDtypeStruct(l.sharding.shard_shape(l.shape), l.dtype)
                     for l in blk.values()]
            ppb = pages_per_block(store.page_size, page_bytes(pools),
                                  store.max_pages)
            return ppb * store.page_size, -(-store.max_pages // ppb)
    return None


def _mla_gather_rows(store: PagedCache):
    """Latent rows one layer's paged MLA decode gathers a step (every
    lane's whole block table), or None for a stack with no latent pool."""
    cache = store.cache
    if any("ckvp" in blk for blk in (*cache["head_blocks"], *cache["blocks"],
                                       *cache["tail_blocks"])):
        return store.n_lanes * store.max_pages * store.page_size
    return None


def _roundup(n: int, m: int) -> int:
    return pages_for(n, m) * m


def _with_mesh(mesh, fn):
    """Dispatch ``fn`` inside ``with mesh:`` so the trace-time sharding
    constraints (``runtime/sharding.sp_enter`` and friends) activate and
    pjit partitions the step across the mesh.  Identity when ``mesh`` is
    None — the unsharded engine pays nothing.  The mesh context is part of
    pjit's cache key, so meshed and unmeshed engines sharing one lru-cached
    jit object still get distinct compiled programs."""
    if mesh is None:
        return fn

    @functools.wraps(fn)
    def run(*args, **kwargs):
        with mesh:
            return fn(*args, **kwargs)

    return run


# jit wrappers are cached per (cfg, cache_len[, mesh]) so spinning up a new
# engine (benchmark sweeps, tests) reuses compiled traces instead of
# re-jitting — ``make_*_step`` returns a fresh closure per call, which
# defeats jax's own cache if wrapped naively per instance.  ``mesh`` (a
# hashable jax.sharding.Mesh, or None) keys the cache too so sharded and
# unsharded engines never swap wrappers.
@functools.lru_cache(maxsize=None)
def _jitted_admit(cfg: ModelConfig, cache_len: int, mesh=None):
    """Fused admission: prefill + first-token sample + lane scatter in ONE
    dispatch (the batch=1 cache never materializes as a standalone output).
    Single prefills are the engine's per-request overhead; at small scale
    dispatch latency rivals compute, so fusion matters."""
    from repro.serving.slots import scatter_lane

    prefill = make_prefill_step(cfg, cache_len, with_lengths=True)

    def admit(pool, params, tokens, lengths, slot, temp, topk, greedy, key,
              axes_flat):
        logits, single = prefill(params, {"tokens": tokens}, lengths)
        tok = sample_tokens(logits, temp, topk, greedy, key)
        return tok, scatter_lane(pool, single, slot, axes_flat)

    return _with_mesh(mesh, jax.jit(admit, donate_argnums=(0,),
                                    static_argnums=(9,)))


@functools.lru_cache(maxsize=None)
def _jitted_admit_group(cfg: ModelConfig, cache_len: int, k: int, mesh=None):
    """Stacked admission (slot mode): ``k`` same-bucket prompts prefill as
    ONE batch=``k`` dispatch — prefill + per-lane first-token sample + lane
    scatter fused, amortizing the per-admission dispatch cost that
    ``BucketBatchedAdmission`` targets under bursty arrivals.  Prefill is
    batch-parallel (rows attend only within themselves; padding is masked
    by ``lengths``), so the stacked tokens are bitwise the k solo ones."""
    from repro.serving.slots import scatter_lanes

    prefill = make_prefill_step(cfg, cache_len, with_lengths=True)

    def admit(pool, params, tokens, lengths, slots, temps, topk, greedy,
              keys, axes_flat):
        logits, multi = prefill(params, {"tokens": tokens}, lengths)
        toks = sample_tokens(logits, temps, topk, greedy, keys)
        return toks, scatter_lanes(pool, multi, slots, axes_flat, k)

    return _with_mesh(mesh, jax.jit(admit, donate_argnums=(0,),
                                    static_argnums=(9,)))


@functools.lru_cache(maxsize=None)
def _jitted_admit_paged(cfg: ModelConfig, single_len: int, mesh=None):
    """Paged fused admission: the batch=1 prefill allocates only
    ``single_len`` rows (the bucket rounded up to whole pages, not the full
    ``cache_len``) and its cache is scattered straight into the lane's
    pages + per-lane leaves, with the block-table row written in the same
    dispatch."""
    prefill = make_prefill_step(cfg, single_len, with_lengths=True)

    def admit(pool, params, tokens, lengths, lane, page_ids, table_row,
              temp, topk, greedy, key):
        logits, single = prefill(params, {"tokens": tokens}, lengths)
        tok = sample_tokens(logits, temp, topk, greedy, key)
        return tok, paged_insert(pool, single, lane, page_ids, table_row,
                                 lengths[0])

    return _with_mesh(mesh, jax.jit(admit, donate_argnums=(0,)))


@functools.lru_cache(maxsize=None)
def _jitted_admit_paged_group(cfg: ModelConfig, single_len: int, k: int,
                              mesh=None):
    """Stacked admission (paged mode): ``k`` same-bucket prompts prefill as
    ONE batch=``k`` dispatch whose cache rows scatter into each lane's own
    pages (``paged_insert_many``), with every block-table row written in
    the same dispatch.  Prefill is batch-parallel and the per-lane scatter
    is the same graph as ``k`` solo inserts, so the stacked tokens are
    bitwise the k solo ones — the PR 4 slot-mode argument, carried to
    pages."""
    prefill = make_prefill_step(cfg, single_len, with_lengths=True)

    def admit(pool, params, tokens, lengths, lanes, page_ids, table_rows,
              temps, topk, greedy, keys):
        logits, multi = prefill(params, {"tokens": tokens}, lengths)
        toks = sample_tokens(logits, temps, topk, greedy, keys)
        return toks, paged_insert_many(pool, multi, lanes, page_ids,
                                       table_rows, lengths, k)

    return _with_mesh(mesh, jax.jit(admit, donate_argnums=(0,)))


@functools.lru_cache(maxsize=None)
def _jitted_decode_sample(cfg: ModelConfig, mesh=None):
    """Fused decode+sample: one jit dispatch per engine step.

    ``any_stochastic`` is static so the all-greedy trace (the default, and
    every exact-match path) lowers to a pure argmax — without it every step
    would pay sample_tokens' full-vocab sort + categorical just to discard
    the result in the greedy ``where``."""
    decode = make_serve_step(cfg, with_active=True)

    def step(params, tokens, cache, active, temps, topk, greedy, keys,
             any_stochastic: bool):
        logits, cache = decode(params, tokens, cache, active)
        if any_stochastic:
            toks = sample_tokens(logits, temps, topk, greedy, keys)
        else:
            toks = jnp.argmax(logits, axis=-1).astype(jnp.int32)
        return toks, cache

    return _with_mesh(mesh, jax.jit(step, donate_argnums=(2,),
                                    static_argnums=(8,)))


@functools.lru_cache(maxsize=None)
def _jitted_chunk_step(cfg: ModelConfig, chunk_len: int, mesh=None):
    """One chunked-prefill step (see ``paging.prefill.make_chunk_step``),
    donating the pool so chunk writes are in-place."""
    return _with_mesh(mesh, jax.jit(make_chunk_step(cfg, chunk_len),
                                    donate_argnums=(1,)))


@dataclasses.dataclass(frozen=True)
class EngineConfig:
    """Engine shape/policy knobs (model behaviour stays in ``ModelConfig``)."""

    n_slots: int = 4
    cache_len: int = 256
    max_prefills_per_step: int = 1
    # Prompt lengths are padded up to the smallest bucket >= len(prompt) so
    # the jitted prefill traces at most len(buckets) shapes. None/() = exact
    # lengths (one trace per distinct prompt length).
    prefill_buckets: Optional[tuple[int, ...]] = None
    eos_token: Optional[int] = None
    # "slot" (per-lane cache_len preallocation) | "paged" (global page pool
    # + block tables; see repro/paging/)
    cache_mode: str = "slot"
    page_size: int = DEFAULT_PAGE_SIZE
    # pool size in pages; None = the slot-equivalent KV budget
    # (configs.default_page_count)
    n_pages: Optional[int] = None
    # paged mode: prompts longer than this admit in page-aligned chunks of
    # this many tokens, interleaved with decode steps. None = one-shot
    # admission. Must be a multiple of page_size.
    prefill_chunk: Optional[int] = None
    # paged mode: shared-prefix KV cache (repro/prefix/) — admissions look
    # up the longest page-aligned cached prefix, alias its pages and
    # prefill only the uncached suffix.  Requires a chunkable stack
    # (attn/MLA/dense): the suffix resumes through the chunk step.
    prefix_cache: bool = False
    # speculative decoding (repro/spec/): draft k tokens per lane, verify
    # them in ONE batched dispatch, greedy-accept in-jit.  Requires a
    # chunkable stack (the verify window reuses the chunked-prefill
    # row-independence contract).  None / enabled=False = plain decode.
    spec: Optional[SpecConfig] = None

    @staticmethod
    def for_workload(prompt_len: int, gen_tokens: int, n_slots: int = 4,
                     **kw) -> "EngineConfig":
        """Cache sized by the shared serving policy (prompt + gen + headroom)."""
        return EngineConfig(
            n_slots=n_slots,
            cache_len=default_cache_len(prompt_len, gen_tokens),
            **kw,
        )


class ServingEngine:
    def __init__(self, cfg: ModelConfig, params, engine_cfg: EngineConfig,
                 policies: Optional[EnginePolicies] = None,
                 obs: Optional[Observability] = None,
                 mesh=None):
        if cfg.is_encoder_decoder or cfg.frontend is not None:
            raise ValueError(
                "ServingEngine handles decoder-only token-input models; "
                "enc-dec / frontend archs serve via launch.serve.serve_batch")
        buckets = tuple(sorted(engine_cfg.prefill_buckets or ()))
        if buckets and RECURRENT_KINDS & set(cfg.block_pattern):
            raise ValueError(
                f"prefill buckets pad prompts, but {sorted(RECURRENT_KINDS & set(cfg.block_pattern))} "
                "state integrates padded tokens; use exact-length prefill "
                "(prefill_buckets=None) for recurrent stacks")
        if buckets and buckets[-1] > engine_cfg.cache_len:
            raise ValueError("largest prefill bucket exceeds cache_len")
        if engine_cfg.cache_mode not in ("slot", "paged"):
            raise ValueError(f"cache_mode must be 'slot' or 'paged', got "
                             f"{engine_cfg.cache_mode!r}")
        self.cfg = cfg
        self.params = params
        self.engine_cfg = engine_cfg
        self.buckets = buckets
        self.paged = engine_cfg.cache_mode == "paged"
        # tensor-parallel serving (repro/shard/): every jitted dispatch
        # below runs under ``with mesh:`` so trace-time sharding
        # constraints activate; params arrive pre-committed (api/llm.py)
        # and the paged pool commits its own layout in PagedCache
        self.mesh = mesh

        self.policies = policies if policies is not None else EnginePolicies()
        # observability bundle (repro/obs/): the DISABLED singleton's null
        # sinks make every tracer/event/profiler call below a no-op, so the
        # hot path is instrumented unconditionally at zero disabled cost
        self.obs = obs if obs is not None else DISABLED

        n = engine_cfg.n_slots
        self.scheduler = Scheduler(n, engine_cfg.max_prefills_per_step,
                                   admission=self.policies.admission)
        self.metrics = EngineMetrics()

        # flight recorder (repro/obs/recorder.py): None when disarmed;
        # every hook below guards on that, and all of them sit on
        # per-request host paths — no device syncs, no jaxpr changes.
        # Armed, the decision clock tapes its readings so a replay can
        # script time-dependent decisions (deadline sheds/preemptions).
        self._recorder = getattr(self.obs, "recorder", None)
        self.set_clock(self._recorder.wrap_clock()
                       if self._recorder is not None else time.perf_counter)
        if self._recorder is not None:
            self._recorder.record_engine(engine_cfg)

        # whole-stack effective kinds (lead + periods + tail) from the one
        # layout-owning helper; a windowless local_attn block caches like
        # full attention (models/kvcache.py), so it pages too
        kinds = stack_kinds(cfg)
        self._has_ring = ("local_attn" in kinds and cfg.sliding_window is not None)
        self._has_paged_kinds = (
            bool(kinds & PAGED_KINDS)
            or ("local_attn" in kinds and cfg.sliding_window is None))

        if self.paged:
            ps = engine_cfg.page_size
            if self._has_ring and engine_cfg.cache_len % ps:
                raise ValueError(
                    "paged serving of local-attention stacks needs "
                    "cache_len to be a multiple of page_size (the per-lane "
                    "ring insert must match the pool's ring length)")
            if engine_cfg.prefill_chunk is not None:
                if engine_cfg.prefill_chunk % ps:
                    raise ValueError("prefill_chunk must be a multiple of "
                                     "page_size (chunks are page-aligned)")
                if not chunkable_with_state(cfg):
                    raise ValueError(
                        f"{cfg.name}: chunked prefill needs row-independent "
                        "kinds (attn/MLA/dense) or state-carrying recurrent "
                        "cells (rglru/mlstm/slstm); use prefill_chunk=None")
            self.store = PagedCache(cfg, n, engine_cfg.cache_len, ps,
                                    engine_cfg.n_pages, mesh=mesh)
            self.metrics.set_gauge("pages_total", self.store.n_pages)
            self.metrics.set_gauge("page_size", ps)
            # chunk length for BOTH long-prompt chunking and shared-prefix
            # suffix prefill; the prefix cache falls back to one page per
            # chunk (trivially page-aligned) when prefill_chunk is unset
            self._chunk_len = engine_cfg.prefill_chunk
            if engine_cfg.prefix_cache:
                if not self._has_paged_kinds:
                    raise ValueError(
                        f"{cfg.name}: prefix_cache needs attention-family KV "
                        "pages to share; this stack keeps all state per-lane")
                if not chunkable(cfg):
                    raise ValueError(
                        f"{cfg.name}: prefix_cache resumes the uncached "
                        "suffix through the chunked-prefill step, which "
                        "needs a strictly row-independent stack "
                        "(attn/MLA/dense); "
                        f"got {sorted(stack_kinds(cfg))}")
                self._chunk_len = engine_cfg.prefill_chunk or ps
                # full-prompt hits CoW-fork the boundary page and resume at
                # the final prompt token — int8 pools included: every
                # admission on an int8 + prefix pool is forced through the
                # chunk step (``_should_chunk_len``), so cold and warm runs
                # attend the same dequantized pages and stay graph-identical
                self.prefix: Optional[PrefixCache] = PrefixCache(
                    self.store.manager, ps, allow_fork=True)
            else:
                self.prefix = None
            self._chunk_fn = (
                _jitted_chunk_step(cfg, self._chunk_len, mesh)
                if self._chunk_len is not None else None)
            self._attn_blocks = _attn_block_geometry(self.store)
            self._mla_rows = _mla_gather_rows(self.store)
        else:
            if engine_cfg.prefill_chunk is not None:
                raise ValueError("chunked prefill requires cache_mode='paged'")
            if engine_cfg.prefix_cache:
                raise ValueError("prefix_cache requires cache_mode='paged' "
                                 "(shared pages live in the page pool)")
            self.store = SlotCache(cfg, n, engine_cfg.cache_len)
            self.prefix = None
            self._chunk_len = None
            self._attn_blocks = None
            self._mla_rows = None

        self._admit_fn = (None if self.paged
                          else _jitted_admit(cfg, engine_cfg.cache_len, mesh))
        self._decode_sample = _jitted_decode_sample(cfg, mesh)

        # speculative decoding (repro/spec/): verify jit + drafter.  The
        # verify window needs every row-independent property the chunked
        # prefill relies on, so the same ``chunkable`` gate applies.
        spec = engine_cfg.spec
        self._spec = spec if (spec is not None and spec.enabled) else None
        if self._spec is not None:
            if not chunkable(cfg):
                raise ValueError(
                    f"{cfg.name}: speculative decoding needs a stack of "
                    "strictly row-independent kinds (attn/MLA/dense) — the "
                    "k-token verify window reuses the chunked-prefill "
                    f"contract; got {sorted(stack_kinds(cfg))}")
            from repro.spec import make_drafter

            self._verify_fn = _with_mesh(mesh,
                                         jitted_verify(cfg, self._spec.width))
            self._drafter = make_drafter(
                self._spec, cfg, n, engine_cfg.cache_len,
                tree=self.prefix.tree if self.prefix is not None else None)
        else:
            self._verify_fn = None
            self._drafter = None

        # prefix-aware admission orders the queue by adopted-page signature;
        # the policy is engine-agnostic, so the engine hands it the lookup
        if hasattr(self.policies.admission, "bind"):
            self.policies.admission.bind(self._admission_prefix_sig)

        # per-lane state. ``_tokens`` may be a DEVICE array: between sync
        # points sampled tokens feed the next decode device-to-device (see
        # ``step``); the rest are host arrays passed to the fused step.
        self._tokens = np.zeros((n,), np.int32)
        self._temps = np.ones((n,), np.float32)
        self._topk = np.zeros((n,), np.int32)
        self._greedy = np.ones((n,), bool)
        self._keys = np.zeros((n, 2), np.uint32)
        # decode steps whose tokens haven't been pulled to host yet:
        # (device (n,) tokens, {slot: request} snapshot at that step)
        self._pending: list = []
        # per-request memoized prefix plans: req_id -> (tree epoch, plan)
        self._plan_cache: dict[int, tuple] = {}
        self._next_id = 0
        self._step_idx = 0

    # ------------------------------------------------------------------
    # Decision clock
    # ------------------------------------------------------------------
    def set_clock(self, clock) -> None:
        """Install the decision clock: every wall-time reading that can
        change a scheduling decision (submit stamps, admission lateness,
        deadline shedding/preemption) goes through it.  Recording wraps
        ``time.perf_counter`` to tape each reading; replay installs a
        ``ReplayClock`` that scripts the tape back.  Metric timestamps
        (TTFT, latency, dispatch timers) intentionally stay on real
        time — they measure the run, they don't steer it."""
        self._clock = clock
        self.scheduler.clock = clock
        if hasattr(self.policies.eviction, "bind"):
            self.policies.eviction.bind(clock, lambda: self.scheduler.waiting)

    # ------------------------------------------------------------------
    # Admission
    # ------------------------------------------------------------------
    def add_request(self, prompt: Sequence[int], max_new_tokens: int,
                    sampling: Optional[SamplingParams] = None,
                    eos_token: Optional[int] = None,
                    on_token=None, on_text=None, detokenizer=None,
                    priority: int = 0,
                    deadline_s: Optional[float] = None) -> Request:
        prompt = [int(t) for t in prompt]
        if not prompt:
            raise ValueError("empty prompt")
        if max_new_tokens < 1:
            raise ValueError("max_new_tokens must be >= 1")
        # speculative decoding writes up to k rows past the accepted
        # position (the verify window's overshoot), so the budget check and
        # the paged reservations below all carry the extra rows
        need = len(prompt) + max_new_tokens + self._spec_overshoot
        if need > self.engine_cfg.cache_len + 1:
            raise ValueError(
                f"request needs {need} cache positions but cache_len="
                f"{self.engine_cfg.cache_len}; size the engine with "
                f"default_cache_len(prompt_len, gen) [headroom={KV_CACHE_HEADROOM}]")
        if self.paged and self._has_paged_kinds:
            # reject requests the pool can NEVER reserve — otherwise the
            # head-of-line admission gate would veto them forever and the
            # engine would spin (run) or hang (stream) without an error
            pages = pages_for(self._worst_case_rows(len(prompt), max_new_tokens),
                              self.engine_cfg.page_size)
            usable = self.store.n_pages - 1  # page 0 is the trash page
            if pages > usable:
                raise ValueError(
                    f"request reserves {pages} pages but the pool only has "
                    f"{usable} usable pages; raise n_pages (or lower "
                    f"page_size / the request's budget)")
        sampling = sampling or SamplingParams()
        if deadline_s is None:
            deadline_s = sampling.deadline_s
        req = Request(
            req_id=self._next_id,
            prompt=prompt,
            max_new_tokens=max_new_tokens,
            sampling=sampling,
            eos_token=self.engine_cfg.eos_token if eos_token is None else eos_token,
            on_token=on_token,
            on_text=on_text,
            detokenizer=detokenizer,
            priority=priority,
            deadline_s=deadline_s,
            submit_time=self._clock(),
        )
        self._next_id += 1
        self.scheduler.submit(req)
        self.obs.events.emit("queued", req.req_id, prompt_len=req.prompt_len,
                             max_new_tokens=max_new_tokens,
                             priority=priority,
                             **({"deadline_s": deadline_s}
                                if deadline_s is not None else {}))
        if self._recorder is not None:
            self._recorder.record_arrival(req, self._step_idx)
        return req

    def _bucket_len(self, prompt_len: int) -> int:
        for b in self.buckets:
            if b >= prompt_len:
                return b
        return prompt_len

    def _lane_key(self, req: Request) -> np.ndarray:
        if req.sampling.greedy:
            return _ZERO_KEY
        k = request_key(req.sampling.seed, req.req_id, len(req.output_tokens))
        return np.asarray(k, np.uint32)

    def _arm_lane(self, req: Request, slot: int, tok: int) -> None:
        """First token sampled: point the lane's decode inputs at it."""
        s = req.sampling
        self._plan_cache.pop(req.req_id, None)  # admitted: plan consumed
        req.append_token(tok)  # stamps TTFT
        self.metrics.inc("prefills")
        self.obs.events.emit("first_token", req.req_id, slot=slot,
                             ttft_s=req.ttft_s)
        if self._drafter is not None:
            self._drafter.admit(slot, req.prompt)
        self._tokens = jnp.asarray(self._tokens).at[slot].set(tok)
        self._temps[slot] = s.temperature
        self._topk[slot] = s.top_k
        self._greedy[slot] = s.greedy
        self._keys[slot] = self._lane_key(req)

    def _admit(self, req: Request, slot: int) -> None:
        padded_len = self._bucket_len(req.prompt_len)
        tokens = np.zeros((1, padded_len), np.int32)
        tokens[0, :req.prompt_len] = req.prompt
        s = req.sampling
        common = (
            np.asarray([s.temperature], np.float32),
            np.asarray([s.top_k], np.int32),
            np.asarray([s.greedy]),
            self._lane_key(req)[None],
        )
        reserved = None
        if self.paged:
            # reserve/alloc BEFORE the admitted event so it journals the
            # page assignment (the operands a replay diff reports)
            reserved = self._paged_reserve(req, slot, padded_len)
            self.obs.events.emit("admitted", req.req_id, slot=slot,
                                 mode="cold",
                                 pages=[int(p) for p in reserved[1]],
                                 queue_wait_s=req.queue_wait_s)
        else:
            self.obs.events.emit("admitted", req.req_id, slot=slot,
                                 mode="cold", queue_wait_s=req.queue_wait_s)
        t0 = time.perf_counter()
        with self.obs.tracer.span("prefill", lane=slot, req=req.req_id,
                                  slot=slot, tokens=padded_len):
            if self.paged:
                tok_dev, self.store.cache = self._paged_admit(
                    req, slot, tokens, padded_len, common, reserved=reserved)
                self._record_miss(req)
                self._maybe_publish(req, slot)
            else:
                tok_dev, self.store.cache = self._admit_fn(
                    self.store.cache, self.params, tokens,
                    np.asarray([req.prompt_len], np.int32), jnp.int32(slot),
                    *common, self.store._axes_flat,
                )
        req.cost.prefill_s += time.perf_counter() - t0
        req.cost.dispatches += 1
        self.metrics.inc("prefill_dispatches")
        with self.obs.tracer.span("first_token_pull"):
            tok = int(np.asarray(tok_dev)[0])
        self._arm_lane(req, slot, tok)

    def _admit_group(self, group: list[tuple[Request, int]]) -> None:
        """Stacked admission: same-bucket requests prefill as one batch=k
        dispatch (slot mode only; the admission policy can only form >1
        groups when the engine offers them — see ``step``)."""
        k = len(group)
        padded_len = self._bucket_len(group[0][0].prompt_len)
        tokens = np.zeros((k, padded_len), np.int32)
        lengths = np.zeros((k,), np.int32)
        temps = np.ones((k,), np.float32)
        topk = np.zeros((k,), np.int32)
        greedy = np.ones((k,), bool)
        keys = np.zeros((k, 2), np.uint32)
        for i, (req, _) in enumerate(group):
            tokens[i, :req.prompt_len] = req.prompt
            lengths[i] = req.prompt_len
            s = req.sampling
            temps[i], topk[i], greedy[i] = s.temperature, s.top_k, s.greedy
            keys[i] = self._lane_key(req)
        slots = np.asarray([slot for _, slot in group], np.int32)
        for req, slot in group:
            self.obs.events.emit("admitted", req.req_id, slot=slot,
                                 mode="stacked", group=k,
                                 queue_wait_s=req.queue_wait_s)
        admit_fn = _jitted_admit_group(self.cfg, self.engine_cfg.cache_len, k,
                                       self.mesh)
        t0 = time.perf_counter()
        with self.obs.tracer.span("prefill_stacked", lanes=slots,
                                  k=k, tokens=padded_len):
            toks_dev, self.store.cache = admit_fn(
                self.store.cache, self.params, tokens, lengths, slots,
                temps, topk, greedy, keys, self.store._axes_flat)
        share = (time.perf_counter() - t0) / k
        self.metrics.inc("prefill_dispatches")
        self.metrics.inc("stacked_prefills", k)
        with self.obs.tracer.span("first_token_pull"):
            toks = np.asarray(toks_dev)
        for i, (req, slot) in enumerate(group):
            req.cost.prefill_s += share
            req.cost.dispatches += 1
            self._arm_lane(req, slot, int(toks[i]))

    def _admit_group_paged(self, group: list[tuple[Request, int]]) -> None:
        """Stacked paged admission: same-bucket requests prefill as one
        batch=k dispatch whose rows scatter into per-lane pages.  Every
        member already passed the tallied reservation gate against one
        pool snapshot, so the sequential reservations below cannot
        overcommit.  Chunked / prefix-seeded admissions never reach here
        (sentinel buckets keep them single-file)."""
        mgr = self.store.manager
        k = len(group)
        padded_len = self._bucket_len(group[0][0].prompt_len)
        single_len = self._single_len(padded_len)
        npg = (single_len // self.engine_cfg.page_size
               if self._has_paged_kinds else 0)
        tokens = np.zeros((k, padded_len), np.int32)
        lengths = np.zeros((k,), np.int32)
        temps = np.ones((k,), np.float32)
        topk = np.zeros((k,), np.int32)
        greedy = np.ones((k,), bool)
        keys = np.zeros((k, 2), np.uint32)
        page_ids = np.zeros((k, npg), np.int32)
        table_rows = np.zeros((k, self.store.max_pages), np.int32)
        for i, (req, slot) in enumerate(group):
            mgr.admit(slot, self._reserve_tokens(req)
                      if self._has_paged_kinds else 0)
            if npg:
                page_ids[i] = mgr.alloc(slot, npg)
            mgr.set_length(slot, req.prompt_len)
            tokens[i, :req.prompt_len] = req.prompt
            lengths[i] = req.prompt_len
            s = req.sampling
            temps[i], topk[i], greedy[i] = s.temperature, s.top_k, s.greedy
            keys[i] = self._lane_key(req)
            table_rows[i] = mgr.block_tables[slot]
        lanes = np.asarray([slot for _, slot in group], np.int32)
        for i, (req, slot) in enumerate(group):
            self.obs.events.emit("admitted", req.req_id, slot=slot,
                                 mode="stacked", group=k,
                                 pages=[int(p) for p in page_ids[i]],
                                 queue_wait_s=req.queue_wait_s)
        admit_fn = _jitted_admit_paged_group(self.cfg, single_len, k,
                                             self.mesh)
        t0 = time.perf_counter()
        with self.obs.tracer.span("prefill_stacked", lanes=lanes,
                                  k=k, tokens=padded_len):
            toks_dev, self.store.cache = admit_fn(
                self.store.cache, self.params, tokens, lengths, lanes,
                page_ids, table_rows, temps, topk, greedy, keys)
        share = (time.perf_counter() - t0) / k
        self.metrics.inc("prefill_dispatches")
        self.metrics.inc("stacked_prefills", k)
        with self.obs.tracer.span("first_token_pull"):
            toks = np.asarray(toks_dev)
        for i, (req, slot) in enumerate(group):
            req.cost.prefill_s += share
            req.cost.dispatches += 1
            self._record_miss(req)
            self._maybe_publish(req, slot)
            self._arm_lane(req, slot, int(toks[i]))

    # -- paged admission ------------------------------------------------
    def _single_len(self, padded_len: int) -> int:
        """Cache rows the batch=1 admission prefill allocates: the bucket
        rounded to whole pages — except local-attn-ring stacks, whose ring
        length must match the pool's (cache_len is page-aligned there)."""
        if self._has_ring:
            return self.engine_cfg.cache_len
        return _roundup(padded_len, self.engine_cfg.page_size)

    def _should_chunk_len(self, prompt_len: int) -> bool:
        c = self.engine_cfg.prefill_chunk
        force = self.prefix is not None and self.cfg.kv_cache_dtype == "int8"
        if force:
            # int8 pools attend *dequantized* pages on the chunk path but
            # raw bf16 K/V on the one-shot prefill path; forcing EVERY
            # admission (cold or warm, any length) through the chunk step
            # makes cold and warm runs graph-identical, which is what lets
            # full-prompt prefix hits stay dequant-consistent on int8
            # pools (see prefix/cache.py)
            c = self._chunk_len
        if not self.paged or c is None or (prompt_len <= c and not force):
            return False
        # the padded final chunk must stay inside the lane's block table
        return _roundup(prompt_len, c) <= self.store.max_pages * self.engine_cfg.page_size

    def _should_chunk(self, req: Request) -> bool:
        return self._should_chunk_len(req.prompt_len)

    @property
    def _spec_overshoot(self) -> int:
        """Extra cache rows the verify window may write past the accepted
        position (rejected drafts' K/V, overwritten next step)."""
        return self._spec.k if self._spec is not None else 0

    def _admit_rows(self, prompt_len: int) -> int:
        """Cache rows the admission itself touches (chunk padding or the
        page-rounded prefill bucket)."""
        if self._should_chunk_len(prompt_len):
            return _roundup(prompt_len, self._chunk_len)
        return self._single_len(self._bucket_len(prompt_len))

    def _worst_case_rows(self, prompt_len: int, max_new_tokens: int) -> int:
        """Rows a request reserves: its admission footprint or prompt +
        generation budget (+ the speculative overshoot), whichever is
        larger (capped at the block-table capacity, which ``add_request``'s
        cache_len check already bounds)."""
        worst = max(self._admit_rows(prompt_len),
                    prompt_len + max_new_tokens + self._spec_overshoot)
        return min(worst, self.store.max_pages * self.engine_cfg.page_size)

    def _reserve_tokens(self, req: Request) -> int:
        return self._worst_case_rows(req.prompt_len, req.max_new_tokens)

    # -- shared-prefix planning -----------------------------------------
    def _prefix_rows(self, req: Request, plan) -> int:
        """Rows a prefix-seeded lane reserves: the suffix-chunk footprint
        (resume + whole chunks, incl. the padded tail) or prompt +
        generation budget, whichever is larger."""
        c = self._chunk_len
        suffix = plan.resume + _roundup(req.prompt_len - plan.resume, c)
        return max(suffix,
                   req.prompt_len + req.max_new_tokens + self._spec_overshoot)

    def _prefix_plan(self, req: Request):
        """The admission's prefix decision (None = admit cold).  Plans
        whose reservation could never fit a lane's block table fall back
        to the cold path, which ``add_request`` already validated.

        Memoized per (request, tree epoch): bucket_of, the capacity gate
        and the dispatch itself all consult the SAME plan object for one
        scheduling round, and the tree is only re-walked after a
        structural change (publish / evict / remap)."""
        if self.prefix is None:
            return None
        hit = self._plan_cache.get(req.req_id)
        if hit is not None and hit[0] == self.prefix.epoch:
            return hit[1]
        plan = self.policies.prefix.plan(self.prefix, req)
        if plan is not None:
            pages = pages_for(self._prefix_rows(req, plan),
                              self.engine_cfg.page_size)
            if pages > self.store.max_pages or pages > self.store.n_pages - 1:
                plan = None
        self._plan_cache[req.req_id] = (self.prefix.epoch, plan)
        return plan

    def _prefix_draw(self, req: Request, plan) -> int:
        """Pages a prefix-seeded admission draws from the free pool."""
        pages = pages_for(self._prefix_rows(req, plan),
                          self.engine_cfg.page_size)
        return pages - len(plan.pages) + (1 if plan.fork_index is not None else 0)

    def _admit_gate(self):
        """Capacity gate for one admission *dispatch*: stateful so a
        stacked group's reservations are tallied against a single pool
        snapshot (two jointly-unfittable requests can never both pass),
        and prefix-aware — a cached prefix discounts the draw, and under
        pressure the prefix tree LRU-evicts pages no lane is using (never
        pages a candidate in this very dispatch is about to adopt)."""
        if not (self.paged and self._has_paged_kinds):
            return lambda req: True
        tally = [0]
        protected: list = []

        def gate(req: Request) -> bool:
            mgr = self.store.manager
            plan = self._prefix_plan(req)
            if plan is None:
                need = mgr.pages_for(self._reserve_tokens(req))
            else:
                need = self._prefix_draw(req, plan)
                protected.extend(plan.nodes)
            deficit = need - (mgr.available - tally[0])
            # evict only when it can actually close the gap — a request the
            # pool cannot fit even with an empty tree must not drain the
            # cache for nothing while it waits head-of-line
            if (deficit > 0 and self.prefix is not None
                    and deficit <= self.prefix.evictable_pages):
                freed = self.prefix.evict_for(deficit, protect=protected)
                if freed:
                    self.metrics.inc("prefix_evicted_pages", freed)
                    self.metrics.set_gauge("prefix_tree_pages",
                                           self.prefix.cached_pages)
                    self.obs.events.emit("prefix_evict", pages=freed,
                                         deficit=int(deficit))
            if need <= mgr.available - tally[0]:
                tally[0] += need
                return True
            self.obs.events.emit(
                "rejected", req.req_id, reason="page_capacity",
                need_pages=int(need),
                available=int(mgr.available - tally[0]))
            return False

        return gate

    def _admit_bucket(self, req: Request) -> int:
        """Bucket key for stacked admission grouping.  Chunked and
        prefix-seeded admissions are single-file (per-lane chunk streams /
        adopted tables don't stack), so they get a unique sentinel bucket
        no other request can match."""
        if self.paged and (self._should_chunk(req)
                           or self._prefix_plan(req) is not None):
            return -(req.req_id + 1)
        return self._bucket_len(req.prompt_len)

    def _paged_reserve(self, req: Request, slot: int, padded_len: int):
        """Pool-side bookkeeping for a cold paged admission: reserve the
        worst case, allocate the prefill's pages, stamp the prompt
        length.  Returns ``(single_len, page_ids)`` — the page assignment
        the admitted event journals."""
        mgr = self.store.manager
        single_len = self._single_len(padded_len)
        n_pages = single_len // self.engine_cfg.page_size if self._has_paged_kinds else 0
        mgr.admit(slot, self._reserve_tokens(req) if self._has_paged_kinds else 0)
        page_ids = mgr.alloc(slot, n_pages) if n_pages else []
        mgr.set_length(slot, req.prompt_len)
        return single_len, page_ids

    def _paged_admit(self, req: Request, slot: int, tokens, padded_len,
                     common, reserved=None):
        mgr = self.store.manager
        single_len, page_ids = (reserved if reserved is not None
                                else self._paged_reserve(req, slot, padded_len))
        admit_fn = _jitted_admit_paged(self.cfg, single_len, self.mesh)
        return admit_fn(
            self.store.cache, self.params, tokens,
            np.asarray([req.prompt_len], np.int32), jnp.int32(slot),
            np.asarray(page_ids, np.int32),
            np.asarray(mgr.block_tables[slot]),
            *common,
        )

    def _admission_prefix_sig(self, req: Request):
        """Adopted-page signature for prefix-aware admission ordering: two
        waiting requests with the same signature would alias the same
        cached pages, so admitting them back-to-back keeps those pages
        hot.  None = cold admission (no cached prefix)."""
        if self.prefix is None:
            return None
        plan = self._prefix_plan(req)
        return tuple(plan.pages) if plan is not None else None

    # -- shared-prefix bookkeeping ---------------------------------------
    def _record_miss(self, req: Request) -> None:
        if self.prefix is not None:
            self.metrics.inc("prefix_misses")

    def _maybe_publish(self, req: Request, slot: int) -> None:
        """After a prefill completes, enter the prompt's full pages into
        the prefix tree so later prompts can alias them.  Only
        prefill-written rows publish — never decode-written ones, whose
        dispatch graph differs (the bitwise cold-vs-warm contract)."""
        if self.prefix is None or not self.policies.prefix.should_publish(req):
            return
        self.prefix.publish(req.prompt, self.store.manager.lane_pages[slot])
        self.metrics.set_gauge("prefix_tree_pages", self.prefix.cached_pages)

    def _cow(self, slot: int, move) -> None:
        """Apply a copy-on-write fork on device (``move`` = (src, dst))."""
        self.store.copy_pages([move[0]], [move[1]])
        self.metrics.inc("prefix_cow_forks")
        req = self.scheduler.request_in(slot)
        self.obs.events.emit("cow_fork",
                             req.req_id if req is not None else None,
                             slot=slot, src=int(move[0]), dst=int(move[1]))

    # -- chunked prefill -------------------------------------------------
    def _begin_chunked(self, req: Request, slot: int,
                       finished: list[Request]) -> None:
        mgr = self.store.manager
        # pure-recurrent chunked stacks keep all state per-lane: reserve no
        # pages (mirrors _paged_admit), or the pool gate would veto chunked
        # admissions that touch no pool rows at all
        mgr.admit(slot, self._reserve_tokens(req)
                  if self._has_paged_kinds else 0)
        self.obs.events.emit("admitted", req.req_id, slot=slot,
                             mode="chunked",
                             reserved=int(self._reserve_tokens(req))
                             if self._has_paged_kinds else 0,
                             queue_wait_s=req.queue_wait_s)
        self.scheduler.begin_chunked(slot)
        req.prefill_done = 0
        self._record_miss(req)
        self._process_chunk(req, slot, finished)

    def _begin_prefix(self, req: Request, slot: int, plan,
                      finished: list[Request]) -> None:
        """Prefix-seeded admission: alias the cached pages into the lane's
        block table, CoW-fork the boundary page if the plan resumes inside
        one (full-prompt hit), then stream ONLY the uncached suffix through
        the chunk step — a fully-cached prompt recomputes a single token."""
        mgr = self.store.manager
        mgr.admit(slot, self._prefix_rows(req, plan),
                  adopt_pages=plan.pages,
                  forks=0 if plan.fork_index is None else 1)
        self.obs.events.emit("admitted", req.req_id, slot=slot, mode="prefix",
                             cached_tokens=plan.resume,
                             cached_pages=len(plan.pages),
                             pages=[int(p) for p in plan.pages],
                             fork=plan.fork_index is not None,
                             fork_index=plan.fork_index,
                             queue_wait_s=req.queue_wait_s)
        if plan.fork_index is not None:
            self._cow(slot, mgr.cow_fork(slot, plan.fork_index))
        self.prefix.tree.touch(plan.nodes)
        self.metrics.inc("prefix_hits")
        self.metrics.inc("prefix_hit_tokens", plan.resume)
        self.scheduler.begin_chunked(slot)
        req.prefill_done = plan.resume
        self._process_chunk(req, slot, finished)

    def _process_chunk(self, req: Request, slot: int,
                       finished: list[Request]) -> None:
        """Feed one prompt chunk; the final chunk samples the first token
        and promotes the lane into the decode batch.  Chunks are
        page-aligned except a prefix plan's first (resume) chunk, which may
        start mid-page right after a CoW fork."""
        mgr = self.store.manager
        c = self._chunk_len
        start = req.prefill_done
        n = min(c, req.prompt_len - start)
        if self.prefix is not None:
            # CoW guard: the write range must never touch a shared page
            # (structurally only possible at `start`, and the planned fork
            # already privatized it — this keeps the invariant literal)
            move = mgr.ensure_writable(slot, start)
            if move is not None:
                self._cow(slot, move)
        if self._has_paged_kinds:
            mgr.ensure(slot, start + c)  # the padded tail lands in pages
        self.store.sync_tables()
        tokens = np.zeros((1, c), np.int32)
        tokens[0, :n] = req.prompt[start:start + n]
        t0 = time.perf_counter()
        with self.obs.tracer.span("chunk", lane=slot, req=req.req_id,
                                  slot=slot, start=start, n=n):
            logits, self.store.cache = self._chunk_fn(
                self.params, self.store.cache, tokens, jnp.int32(slot),
                np.asarray([start], np.int32), np.asarray([n], np.int32))
        req.cost.prefill_s += time.perf_counter() - t0
        req.cost.dispatches += 1
        req.prefill_done = start + n
        self.metrics.inc("chunk_steps")
        self.metrics.inc("prefill_dispatches")
        self.obs.events.emit("chunk", req.req_id, slot=slot, start=start, n=n,
                             done=req.prefill_done >= req.prompt_len)
        if req.prefill_done >= req.prompt_len:
            s = req.sampling
            tok_dev = _sample_jit(
                logits, np.asarray([s.temperature], np.float32),
                np.asarray([s.top_k], np.int32), np.asarray([s.greedy]),
                self._lane_key(req)[None])
            mgr.set_length(slot, req.prompt_len)
            self.scheduler.promote(slot)
            self._maybe_publish(req, slot)
            with self.obs.tracer.span("first_token_pull"):
                tok = int(np.asarray(tok_dev)[0])
            self._arm_lane(req, slot, tok)
            if self._should_evict(req):  # max_new_tokens == 1 (or instant EOS)
                self._evict(slot, finished)

    # ------------------------------------------------------------------
    # The engine loop
    # ------------------------------------------------------------------
    def step(self) -> list[Request]:
        """One scheduler iteration: interleave admissions (or prompt
        chunks) with a batched decode over all occupied lanes. Returns
        requests finished this step."""
        obs = self.obs
        obs.profiler.step_begin()
        with obs.tracer.span("step", idx=self._step_idx + 1):
            finished = self._step_inner()
        obs.profiler.step_end()
        if obs.debug_invariants and self.paged and self._has_paged_kinds:
            bad = self.store.manager.invariant_violations()
            if bad:
                obs.events.emit("invariant_violation", step=self._step_idx,
                                violations=bad)
                raise AssertionError(
                    f"page-pool invariants violated at step {self._step_idx}: "
                    + "; ".join(bad))
        self.metrics.touch()
        return finished

    def _step_inner(self) -> list[Request]:
        self.metrics.begin()
        self._step_idx += 1
        self.metrics.inc("steps")
        finished: list[Request] = []
        tracer = self.obs.tracer
        with tracer.span("schedule"):
            self._shed_late(finished)
        budget = self.engine_cfg.max_prefills_per_step

        t0 = time.perf_counter()
        did_prefill = False
        # in-flight chunked admissions continue first (finish what's started)
        for slot, req in sorted(self.scheduler.chunking.items()):
            if budget <= 0:
                break
            self._process_chunk(req, slot, finished)
            budget -= 1
            did_prefill = True

        # admit one *dispatch* at a time: the per-dispatch capacity gate
        # tallies every member's page reservation against one pool
        # snapshot, so two jointly-unfittable requests can never both
        # pass.  The admission policy may stack several same-bucket
        # requests into one dispatch in BOTH cache modes (paged groups
        # scatter per-lane pages); chunked and prefix-seeded admissions
        # stay single-file via sentinel buckets.
        while budget > 0:
            with tracer.span("schedule"):
                group = self.scheduler.schedule_group(
                    admit_ok=self._admit_gate(),
                    bucket_of=self._admit_bucket,
                    max_group=self.scheduler.free_slots)
            if not group:
                break
            budget -= 1
            did_prefill = True
            if len(group) > 1:
                if self.paged:
                    self._admit_group_paged(group)
                else:
                    self._admit_group(group)
                for req, slot in group:
                    if self._should_evict(req):
                        self._evict(slot, finished)
                continue
            req, slot = group[0]
            plan = self._prefix_plan(req) if self.paged else None
            if plan is not None:
                self._begin_prefix(req, slot, plan, finished)
            elif self._should_chunk(req):
                self._begin_chunked(req, slot, finished)
            else:
                self._admit(req, slot)
                if self._should_evict(req):  # max_new_tokens == 1 / instant EOS
                    self._evict(slot, finished)
        if did_prefill:
            with tracer.span("admit_sync"):
                jax.block_until_ready(self.store.cache["pos"])
            self.metrics.inc("prefill_s", time.perf_counter() - t0)

        occupancy = len(self.scheduler.running) + len(self.scheduler.chunking)
        self.metrics.max_gauge("peak_running", occupancy)

        if self.scheduler.running and self._spec is not None and self._spec_ready():
            t0 = time.perf_counter()
            spec_reqs = list(self.scheduler.running.values())
            self._spec_decode(finished)
            dt = time.perf_counter() - t0
            self.metrics.inc("decode_s", dt)
            share = dt / max(len(spec_reqs), 1)
            for req in spec_reqs:
                req.cost.verify_s += share
                req.cost.dispatches += 1
        elif self.scheduler.running:
            if self._spec is not None:
                # spec configured but this batch can't speculate (a
                # non-greedy lane) — the round falls back to plain decode
                self.obs.events.emit("spec_fallback",
                                     reason="non_greedy_lane",
                                     batch=len(self.scheduler.running))
            t0 = time.perf_counter()
            running = self.scheduler.running
            if self.paged and self._has_paged_kinds:
                mgr = self.store.manager
                with tracer.span("page_tables"):
                    for slot in running:
                        row = int(mgr.lengths[slot])
                        if self.prefix is not None:
                            # a lane's first write into a shared page forks
                            # it (structurally the admission fork already
                            # covers this; the guard keeps the invariant
                            # unconditional)
                            move = mgr.ensure_writable(slot, row)
                            if move is not None:
                                self._cow(slot, move)
                        mgr.ensure(slot, row + 1)
                        # KV footprint integral: pages held x decode steps
                        running[slot].cost.page_steps += len(mgr.lane_pages[slot])
                    self.store.sync_tables()
                self.metrics.max_gauge("peak_pages_used", mgr.pages_in_use)
                if self._attn_blocks is not None:
                    self._count_attn_blocks(list(running))
                if self._mla_rows is not None:
                    # rows each running lane attends: its own and the one
                    # being written (host lengths, no device sync)
                    lens = self.store.manager.lengths[list(running)]
                    self.metrics.inc("mla_rows_live", int(lens.sum()) + len(lens))
                    self.metrics.inc("mla_rows_gathered", self._mla_rows)
            active = np.zeros((self.engine_cfg.n_slots,), bool)
            active[list(running)] = True
            with tracer.span("decode", lanes=running, batch=len(running)):
                toks, self.store.cache = self._decode_sample(
                    self.params, self._tokens, self.store.cache, active,
                    self._temps, self._topk, self._greedy, self._keys,
                    not bool(self._greedy.all()))
            if self.paged:
                self.store.manager.advance(running)
            # feed the sampled tokens into the next decode device-to-device;
            # pull them to host lazily (only when scheduling needs them),
            # so all-greedy stretches pipeline like the static loop does
            self._tokens = toks
            decoded = dict(running)  # eviction below mutates the live dict
            self._pending.append((toks, decoded))
            self.metrics.inc("decode_steps")
            if self._needs_sync():
                self._flush(finished)
            dt = time.perf_counter() - t0
            self.metrics.inc("decode_s", dt)
            share = dt / max(len(decoded), 1)
            for req in decoded.values():
                req.cost.decode_s += share
                req.cost.dispatches += 1

        # policy-triggered pool compaction: evictions above may have left
        # holes; compacting now keeps the free list contiguous for the next
        # admissions (ROADMAP PR 3 follow-up: defrag existed, untriggered)
        if (self.paged and self._has_paged_kinds
                and self.policies.defrag.should_defrag(self.store.manager)):
            with tracer.span("defrag") as sp:
                moves = self.store.defrag()
                sp.set(pages_moved=len(moves))
            if moves:
                self.metrics.inc("defrag_count")
                self.metrics.inc("defrag_pages_moved", len(moves))
                self.obs.events.emit("defrag", pages_moved=len(moves),
                                     moves=[[int(s), int(d)] for s, d in moves],
                                     step=self._step_idx)
        return finished

    def _count_attn_blocks(self, lanes: list) -> None:
        """The paged-attention kernel's blocks this decode step, per layer:
        the live blocks it walks (a running lane attends its rows plus the
        one being written, an idle lane one row) against every lane's
        whole table, from the host's lengths (no device sync)."""
        rows, n_blocks = self._attn_blocks
        lens = np.ones((self.engine_cfg.n_slots,), np.int64)
        lens[lanes] = self.store.manager.lengths[lanes] + 1
        live = np.clip(-(-lens // rows), 1, n_blocks)
        self.metrics.inc("attn_blocks_live", int(live.sum()))
        self.metrics.inc("attn_blocks_walked", len(lens) * n_blocks)

    # ------------------------------------------------------------------
    # Speculative decoding (repro/spec/)
    # ------------------------------------------------------------------
    def _spec_ready(self) -> bool:
        """Speculate only when every running lane is greedy — the fused
        accept rule is exact for argmax; a mixed batch falls back to plain
        decode wholesale (no per-lane mode split inside one dispatch)."""
        return all(r.sampling.greedy for r in self.scheduler.running.values())

    def _spec_decode(self, finished: list[Request]) -> None:
        """One draft-verify round over every running lane.

        Host-synchronous by design: the drafters read each lane's full
        token history and the accept length gates eviction, so pending
        plain-decode tokens are flushed first and this step's tokens land
        on the host immediately.  The verify dispatch itself stays
        traced-once — the window is always ``k + 1`` wide; per-lane draft
        counts and acceptance lengths are data (``n_draft`` mask, in-jit
        cumprod), never shapes.
        """
        spec = self._spec
        if self._pending:
            self._flush(finished)
        running = dict(self.scheduler.running)
        if not running:
            return
        # np.array (not asarray): a device array materializes as a read-only
        # view, and the accept loop below writes per-lane feed tokens
        self._tokens = np.array(self._tokens)
        n = self.engine_cfg.n_slots
        w = spec.width
        slots = sorted(running)
        histories = [running[s].prompt + running[s].output_tokens for s in slots]
        proposals = self._drafter.propose(slots, histories)

        toks = np.zeros((n, w), np.int32)
        n_draft = np.zeros((n,), np.int32)
        active = np.zeros((n,), bool)
        for slot, hist, props in zip(slots, histories, proposals):
            req = running[slot]
            # never draft past the lane's generation budget: the verify
            # row for draft j emits token j+1, so at most budget-1 drafts
            allow = max(0, min(spec.k,
                               req.max_new_tokens - len(req.output_tokens) - 1))
            props = [int(t) for t in props[:allow]]
            toks[slot, 0] = hist[-1]
            if props:
                toks[slot, 1:1 + len(props)] = props
            n_draft[slot] = len(props)
            active[slot] = True
            self.metrics.inc("spec_proposed", len(props))

        mgr = self.store.manager if self.paged else None
        base_row = {}
        if self.paged and self._has_paged_kinds:
            with self.obs.tracer.span("page_tables"):
                for slot in slots:
                    row = int(mgr.lengths[slot])
                    base_row[slot] = row
                    if self.prefix is not None:
                        # the whole verify window must be privately writable
                        for move in mgr.ensure_writable_range(slot, row, w):
                            self._cow(slot, move)
                    mgr.ensure(slot, row + w)
                    running[slot].cost.page_steps += len(mgr.lane_pages[slot])
                self.store.sync_tables()
            self.metrics.max_gauge("peak_pages_used", mgr.pages_in_use)

        with self.obs.tracer.span("verify", batch=len(slots), width=w,
                                  lanes=slots):
            self.store.cache, targets, accepted = self._verify_fn(
                self.params, self.store.cache, toks, n_draft, active)
        self.metrics.inc("verify_dispatches")
        self.metrics.inc("decode_steps")
        targets = np.asarray(targets)
        accepted = np.asarray(accepted)
        # journal the verify round's operands before the per-lane accept
        # loop below emits its own (eviction) events
        self.obs.events.emit(
            "spec_verify", lanes=[int(s) for s in slots],
            n_draft=[int(n_draft[s]) for s in slots],
            accepted=[int(accepted[s]) for s in slots])

        for slot in slots:
            req = running[slot]
            a = int(accepted[slot])
            # emit accepted drafts + the bonus/correction row, stopping at
            # EOS / budget exactly like the per-step plain-decode loop
            emitted = 0
            for j in range(a + 1):
                req.append_token(int(targets[slot, j]))
                emitted += 1
                if self._should_evict(req):
                    break
            self.metrics.inc("spec_accepted", min(emitted, a))
            self.metrics.observe("accept_len", min(emitted, a))
            if self.paged and self._has_paged_kinds:
                # rollback = block-table truncate: rejected rows' pages
                # stay reserved to the lane and are overwritten in place
                mgr.set_length(slot, base_row[slot] + emitted)
            self._tokens[slot] = req.output_tokens[-1]
            if self._should_evict(req):
                self._evict(slot, finished)

    def _shed_late(self, finished: list[Request]) -> None:
        """Deadline admission pre-pass: a request whose deadline already
        passed while it sat in the queue can only produce dead tokens, so
        shed it at ingress — before it burns a prefill dispatch and a lane
        another request could use.  Only policies exposing ``shed`` (e.g.
        ``DeadlineAdmission``) trigger this; FIFO et al. cost nothing."""
        shed = getattr(self.policies.admission, "shed", None)
        if shed is None or not self.scheduler.waiting:
            return
        now = self._clock()
        idxs = shed(self.scheduler.waiting, now)
        if not idxs:
            return
        for req in self.scheduler.drop(idxs):
            req.finish_reason_override = "deadline"
            self._plan_cache.pop(req.req_id, None)
            self.metrics.inc("deadline_shed")
            self.metrics.record_finished(req)
            self.obs.events.emit(
                "rejected", req.req_id, reason="deadline",
                waited_s=now - req.submit_time,
                deadline_s=req.deadline_s)
            if self._recorder is not None:
                self._recorder.record_finish(req)
            finished.append(req)

    def _should_evict(self, req: Request) -> bool:
        return self.policies.eviction.should_evict(req)

    def _needs_sync(self) -> bool:
        """Must the pending token arrays reach the host NOW?  Yes iff some
        running lane's next scheduling decision depends on token values
        (EOS armed), its PRNG key must advance (stochastic sampling), it
        streams tokens or text to a callback, or it reaches its length
        budget at this step (eviction due).  An eviction policy that
        inspects token values asks for per-step syncs wholesale."""
        if getattr(self.policies.eviction, "wants_step_sync", False):
            return True
        counts: dict[int, int] = {}
        for _, mapping in self._pending:
            for req in mapping.values():
                counts[req.req_id] = counts.get(req.req_id, 0) + 1
        for req in self.scheduler.running.values():
            if (req.eos_token is not None or not req.sampling.greedy
                    or req.on_token is not None or req.on_text is not None):
                return True
            if len(req.output_tokens) + counts.get(req.req_id, 0) >= req.max_new_tokens:
                return True
        return False

    def _flush(self, finished: list[Request]) -> None:
        """Materialize pending decode tokens, then deliver them (streaming
        callbacks), advance PRNG keys and evict completed lanes."""
        tracer = self.obs.tracer
        with tracer.span("token_pull"):
            pulled = [(np.asarray(toks_dev), mapping)
                      for toks_dev, mapping in self._pending]
        self._pending.clear()
        with tracer.span("token_deliver"):
            for toks, mapping in pulled:
                for slot, req in mapping.items():
                    req.append_token(int(toks[slot]))
            for slot, req in list(self.scheduler.running.items()):
                self._keys[slot] = self._lane_key(req)
                if self._should_evict(req):
                    self._evict(slot, finished)

    def _evict(self, slot: int, finished: list[Request]) -> None:
        req = self.scheduler.release(slot)
        self.store.free(slot)
        if self._drafter is not None:
            self._drafter.release(slot)
        self._greedy[slot] = True  # free lanes sample nothing
        reason_of = getattr(self.policies.eviction, "evict_reason", None)
        reason = reason_of(req) if reason_of is not None else req.finish_reason
        if reason == "deadline" and not req.done:
            # SLO preemption (DeadlinePreemption): the lane was taken back
            # from a request that already missed its deadline so queued
            # on-time work can have it
            req.finish_reason_override = "deadline"
            self.metrics.inc("deadline_preempt")
            self.obs.events.emit(
                "evicted", req.req_id, slot=slot, reason="deadline",
                n_tokens=len(req.output_tokens),
                deadline_s=req.deadline_s)
        self.metrics.record_finished(req)
        extra = {}
        if req.deadline_s is not None:
            extra["deadline_s"] = req.deadline_s
            extra["deadline_hit"] = req.deadline_hit
        self.obs.events.emit(
            "finished", req.req_id, slot=slot,
            n_tokens=len(req.output_tokens),
            reason=reason,
            latency_s=req.latency_s, **extra)
        if self._recorder is not None:
            self._recorder.record_finish(req)
        finished.append(req)

    @property
    def has_work(self) -> bool:
        return self.scheduler.has_work

    def run(self, arrivals=None, max_steps: int = 100_000,
            on_token=None) -> EngineMetrics:
        """Drive steps until idle.  ``arrivals``: optional list of
        ``(step_idx, prompt, max_new_tokens[, SamplingParams])`` tuples —
        requests injected when the engine reaches that step, simulating
        staggered traffic deterministically.  ``on_token(req, tok)``, if
        given, streams every arrival's tokens as they reach the host."""
        pending = sorted(arrivals or [], key=lambda a: a[0])
        i = 0
        steps_this_run = 0
        while (i < len(pending) or self.has_work) and steps_this_run < max_steps:
            while i < len(pending) and pending[i][0] <= self._step_idx:
                arr = pending[i]
                req = self.add_request(arr[1], arr[2],
                                       sampling=arr[3] if len(arr) > 3 else None)
                if on_token is not None:
                    req.on_token = functools.partial(on_token, req)
                i += 1
            if not self.has_work:
                # idle gap before the next arrival: jump to it
                self._step_idx = pending[i][0]
                continue
            self.step()
            steps_this_run += 1
        if self._pending:  # max_steps bail-out with tokens still in flight
            self._flush([])
        return self.metrics

    def stream(self, prompt: Sequence[int], max_new_tokens: int,
               sampling: Optional[SamplingParams] = None,
               eos_token: Optional[int] = None) -> Iterator[int]:
        """Submit a request and yield its tokens as the engine produces
        them, driving ``step()`` in between.  Other queued requests advance
        normally — this is the single-caller convenience over the
        ``on_token`` callback hook."""
        emitted: list[int] = []
        req = self.add_request(prompt, max_new_tokens, sampling=sampling,
                               eos_token=eos_token, on_token=emitted.append)
        i = 0
        while True:
            while i < len(emitted):
                yield emitted[i]
                i += 1
            if req.state is RequestState.FINISHED or not self.has_work:
                break
            self.step()
        yield from emitted[i:]
