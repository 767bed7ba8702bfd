"""Engine-level serving metrics, refactored onto the ``repro.obs``
metrics registry.

``EngineMetrics`` used to be a 30-field dataclass of means and counters
that engine code poked directly (``metrics.prefills += 1``).  It is now a
facade over an ``obs.MetricsRegistry``: engine code *emits* events —

    metrics.inc("prefills")            # counters / time accumulators
    metrics.set_gauge("pages_total", n)
    metrics.max_gauge("peak_running", occupancy)
    metrics.observe("accept_len", a)   # histograms

— and summaries are *derived*.  ``report()`` keeps every legacy key
bit-for-bit and adds exact p50/p95/p99 percentiles for TTFT, decode
per-token latency, queue wait and speculative acceptance length, computed
from the registry's log-bucketed histograms (which retain raw samples).

Backward compatibility: every legacy field name still reads (and writes)
through attribute access, so ``metrics.prefix_hits`` in tests and
benchmarks keeps working.  Direct *assignment* from external code is a
deprecation shim — it warns and forwards to the registry — because the
event-style API is the supported surface.

Wall-clock accounting is robust to empty runs: ``begin()`` stamps the
start once, every engine step ``touch()``-es the end, and
``record_finished`` advances it — so a run that finishes zero requests no
longer reports a wall time derived from a falsy ``end_time`` (the old
behaviour made ``wall_s`` grow forever after the run ended).
"""

from __future__ import annotations

import time
import warnings

from repro.obs.metrics import MetricsRegistry
from repro.serving.request import Request

# integer event counters (legacy dataclass fields, now registry counters)
_COUNTERS = (
    "steps", "prefills", "prefill_dispatches", "stacked_prefills",
    "decode_steps", "chunk_steps", "defrag_count", "defrag_pages_moved",
    # paged-attention kernel blocks per layer, summed over decode steps:
    # the live blocks walked, and lanes x blocks of the whole table
    "attn_blocks_live", "attn_blocks_walked",
    # paged latent (MLA) rows per layer, summed over decode steps: the rows
    # the running lanes attend, and the rows the gather builds (every lane's
    # whole table)
    "mla_rows_live", "mla_rows_gathered",
    "prefix_hits", "prefix_misses", "prefix_hit_tokens", "prefix_cow_forks",
    "prefix_evicted_pages", "spec_proposed", "spec_accepted",
    "verify_dispatches",
    # SLO accounting: per-request deadline outcome (stamped at finish) and
    # tokens from deadline-respecting requests (the goodput numerator —
    # no-deadline requests always count; a missed deadline zeroes the
    # request's contribution)
    "deadline_hits", "deadline_misses", "deadline_late_admissions",
    "goodput_tokens",
    # requests dropped at ingress by DeadlineAdmission (already late in
    # queue; they finish with reason="deadline" without holding a lane)
    "deadline_shed",
    # running lanes preempted by DeadlinePreemption (deadline already
    # missed while queued work could still hit its own)
    "deadline_preempt",
)
# float time accumulators (counters that add seconds)
_TIMERS = ("prefill_s", "decode_s")
# last-value / running-max gauges
_GAUGES = ("peak_running", "pages_total", "page_size", "peak_pages_used",
           "prefix_tree_pages", "start_time", "end_time")
_FIELDS = frozenset(_COUNTERS + _TIMERS + _GAUGES)

# request-derived latency histograms (seconds unless noted)
_HISTOGRAMS = (
    "ttft_s",        # submit -> first sampled token
    "latency_s",     # submit -> finished
    "per_token_s",   # decode-only: (latency - ttft) / (n_tokens - 1)
    "queue_wait_s",  # submit -> admitted into a lane
    "accept_len",    # accepted drafts per speculative verify round (count)
    # per-request cost attribution (from Request.cost, observed at finish)
    "cost_prefill_s",   # prefill/chunk dispatch time attributed to the req
    "cost_decode_s",    # share of batched decode dispatch time
    "cost_verify_s",    # share of batched spec draft+verify time
    "cost_page_steps",  # sum over decode steps of pages held (paged only)
)


def _mean(xs):
    xs = [x for x in xs if x is not None]
    return sum(xs) / len(xs) if xs else 0.0


class EngineMetrics:
    """Accumulated over an engine run; ``report()`` emits the summary."""

    def __init__(self):
        d = self.__dict__
        d["registry"] = MetricsRegistry()
        d["finished"] = []
        for name in _COUNTERS + _TIMERS:
            self.registry.counter(name)
        for name in _GAUGES:
            self.registry.gauge(name)
        for name in _HISTOGRAMS:
            self.registry.histogram(name)

    # -- attribute facade (legacy field names) -----------------------------
    def __getattr__(self, name):
        # only reached when ``name`` is not an instance attribute
        reg = self.__dict__["registry"]
        if name in _COUNTERS or name in _TIMERS:
            return reg.counter(name).value
        if name in _GAUGES:
            return reg.gauge(name).value
        raise AttributeError(f"EngineMetrics has no attribute {name!r}")

    def __setattr__(self, name, value):
        if name in _FIELDS:
            warnings.warn(
                f"direct assignment to EngineMetrics.{name} is deprecated; "
                "use inc()/set_gauge()/max_gauge()/observe()",
                DeprecationWarning, stacklevel=2)
            self._force(name, value)
        else:
            self.__dict__[name] = value

    def _force(self, name, value):
        """Set a metric to an absolute value (shim + internal stamps)."""
        reg = self.registry
        if name in _COUNTERS or name in _TIMERS:
            reg.counter(name).value = value
        else:
            reg.gauge(name).set(value)

    # -- the event-style emission API (what engine code calls) ------------
    def inc(self, name: str, n=1) -> None:
        self.registry.inc(name, n)

    def set_gauge(self, name: str, value) -> None:
        self.registry.set(name, value)

    def max_gauge(self, name: str, value) -> None:
        self.registry.set_max(name, value)

    def observe(self, name: str, value) -> None:
        self.registry.observe(name, value)

    # -- run lifecycle -----------------------------------------------------
    def begin(self) -> None:
        if not self.start_time:
            self._force("start_time", time.perf_counter())

    def touch(self) -> None:
        """Advance the run's end stamp (each engine step calls this, so an
        empty run — zero finished requests — still reports the true
        wall time instead of a clock that keeps running)."""
        self._force("end_time", time.perf_counter())

    def record_finished(self, req: Request) -> None:
        req.finish_time = time.perf_counter()
        self._force("end_time", req.finish_time)
        self.finished.append(req)
        if req.ttft_s is not None:
            self.observe("ttft_s", req.ttft_s)
        if req.latency_s is not None:
            self.observe("latency_s", req.latency_s)
            n = len(req.output_tokens)
            if n > 1 and req.ttft_s is not None:
                self.observe("per_token_s", (req.latency_s - req.ttft_s) / (n - 1))
        if req.queue_wait_s is not None:
            self.observe("queue_wait_s", req.queue_wait_s)
        # SLO outcome + goodput: no-deadline requests always count
        hit = req.deadline_hit
        if hit is not None:
            self.inc("deadline_hits" if hit else "deadline_misses")
            if getattr(req, "late_at_admission", False):
                self.inc("deadline_late_admissions")
        if hit is not False:
            self.inc("goodput_tokens", len(req.output_tokens))
        cost = getattr(req, "cost", None)
        if cost is not None and cost.dispatches:
            self.observe("cost_prefill_s", cost.prefill_s)
            self.observe("cost_decode_s", cost.decode_s)
            if cost.verify_s:
                self.observe("cost_verify_s", cost.verify_s)
            if cost.page_steps:
                self.observe("cost_page_steps", cost.page_steps)

    # -- summary -----------------------------------------------------------
    @property
    def wall_s(self) -> float:
        start = self.start_time
        if not start:
            return 0.0
        # mid-run report (no touch yet): live reading; afterwards the last
        # step / finish stamp bounds the run even with nothing finished
        end = self.end_time or time.perf_counter()
        return max(end - start, 1e-9)

    @property
    def generated_tokens(self) -> int:
        return sum(len(r.output_tokens) for r in self.finished)

    def _pct(self, name: str, q: float, digits: int = 6) -> float:
        return round(self.registry.histogram(name).percentile(q), digits)

    def report(self) -> dict:
        """Machine-readable summary (also what ``BENCH_serve.json`` stores).
        Every pre-observability key is preserved; the ``*_p50/_p95/_p99``
        keys are exact percentiles over finished requests (and, for
        ``accept_len``, over speculative verify rounds)."""
        reqs = self.finished
        wall = self.wall_s
        return {
            "requests": len(reqs),
            "generated_tokens": self.generated_tokens,
            "prompt_tokens": sum(r.prompt_len for r in reqs),
            "wall_s": round(wall, 4),
            "tokens_per_s": round(self.generated_tokens / max(wall, 1e-9), 2),
            "steps": self.steps,
            "prefills": self.prefills,
            "prefill_dispatches": self.prefill_dispatches,
            "stacked_prefills": self.stacked_prefills,
            "decode_steps": self.decode_steps,
            "attn_blocks_live": self.attn_blocks_live,
            "attn_blocks_walked": self.attn_blocks_walked,
            "attn_live_share": round(
                self.attn_blocks_live / self.attn_blocks_walked, 4)
            if self.attn_blocks_walked else 0.0,
            "mla_rows_live": self.mla_rows_live,
            "mla_rows_gathered": self.mla_rows_gathered,
            "mla_live_share": round(
                self.mla_rows_live / self.mla_rows_gathered, 4)
            if self.mla_rows_gathered else 0.0,
            "prefill_s": round(self.prefill_s, 4),
            "decode_s": round(self.decode_s, 4),
            "ttft_mean_s": round(_mean([r.ttft_s for r in reqs]), 4),
            "ttft_max_s": round(max([r.ttft_s or 0.0 for r in reqs], default=0.0), 4),
            "ttft_p50_s": self._pct("ttft_s", 50),
            "ttft_p95_s": self._pct("ttft_s", 95),
            "ttft_p99_s": self._pct("ttft_s", 99),
            "latency_mean_s": round(_mean([r.latency_s for r in reqs]), 4),
            "latency_max_s": round(
                max([r.latency_s or 0.0 for r in reqs], default=0.0), 4),
            "per_token_p50_s": self._pct("per_token_s", 50),
            "per_token_p95_s": self._pct("per_token_s", 95),
            "per_token_p99_s": self._pct("per_token_s", 99),
            "queue_wait_p50_s": self._pct("queue_wait_s", 50),
            "queue_wait_p95_s": self._pct("queue_wait_s", 95),
            "queue_wait_p99_s": self._pct("queue_wait_s", 99),
            "peak_running": self.peak_running,
            "chunk_steps": self.chunk_steps,
            "pages_total": self.pages_total,
            "page_size": self.page_size,
            "peak_pages_used": self.peak_pages_used,
            "defrag_count": self.defrag_count,
            "defrag_pages_moved": self.defrag_pages_moved,
            "prefix_hits": self.prefix_hits,
            "prefix_misses": self.prefix_misses,
            "prefix_hit_tokens": self.prefix_hit_tokens,
            "prefix_cow_forks": self.prefix_cow_forks,
            "prefix_evicted_pages": self.prefix_evicted_pages,
            "prefix_tree_pages": self.prefix_tree_pages,
            "spec_proposed": self.spec_proposed,
            "spec_accepted": self.spec_accepted,
            "verify_dispatches": self.verify_dispatches,
            "acceptance_rate": round(
                self.spec_accepted / self.spec_proposed, 4)
            if self.spec_proposed else 0.0,
            "accept_len_p50": self._pct("accept_len", 50, 2),
            "accept_len_p95": self._pct("accept_len", 95, 2),
            "accept_len_p99": self._pct("accept_len", 99, 2),
            "deadline_hits": self.deadline_hits,
            "deadline_misses": self.deadline_misses,
            "deadline_shed": self.deadline_shed,
            "deadline_preempt": self.deadline_preempt,
            "deadline_hit_rate": round(
                self.deadline_hits / (self.deadline_hits
                                      + self.deadline_misses), 4)
            if (self.deadline_hits + self.deadline_misses) else None,
            "goodput_tokens": self.goodput_tokens,
            "goodput_tokens_per_s": round(
                self.goodput_tokens / max(wall, 1e-9), 2),
            "cost_prefill_p99_s": self._pct("cost_prefill_s", 99),
            "cost_decode_p99_s": self._pct("cost_decode_s", 99),
            "cost_verify_p99_s": self._pct("cost_verify_s", 99),
        }

    def format_report(self) -> str:
        r = self.report()
        return (
            f"[engine] {r['requests']} requests, {r['generated_tokens']} tokens "
            f"in {r['wall_s']:.2f}s = {r['tokens_per_s']:.1f} tok/s | "
            f"{r['prefills']} prefills + {r['decode_steps']} decode steps | "
            f"TTFT mean {r['ttft_mean_s']*1e3:.0f}ms "
            f"p99 {r['ttft_p99_s']*1e3:.0f}ms max {r['ttft_max_s']*1e3:.0f}ms | "
            f"latency mean {r['latency_mean_s']:.2f}s"
        )
