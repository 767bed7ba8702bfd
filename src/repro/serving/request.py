"""Request lifecycle for the continuous-batching engine.

A request moves WAITING -> RUNNING -> FINISHED.  Single-shot admission
(prefill + first sampled token) happens inside one engine step, so a
request is RUNNING from the moment its KV cache occupies a slot; only the
paged engine's *chunked* admissions pass through PREFILLING, holding their
slot across the steps that feed the prompt in page-sized chunks.  All
bookkeeping here is host-side Python — device state lives in
``slots.SlotCache`` / ``paging.PagedCache``.
"""

from __future__ import annotations

import dataclasses
import enum
import time
from typing import Callable, Optional, Sequence

from repro.serving.sampling import SamplingParams


def default_detokenizer(token_ids: Sequence[int]) -> str:
    """Fallback detokenizer: renders each token id as ``<id>``.  The repo
    carries no vocabulary, so this keeps the text-streaming path (and the
    ``detokenize=True`` API surface) fully exercisable; real deployments
    pass their tokenizer's ``decode`` callable instead."""
    return "".join(f"<{int(t)}>" for t in token_ids)


class RequestState(enum.Enum):
    WAITING = "waiting"      # queued, no slot yet
    PREFILLING = "prefilling"  # slot held, prompt chunks still streaming in
    RUNNING = "running"      # occupies a slot, decoding
    FINISHED = "finished"    # evicted; outputs final


@dataclasses.dataclass
class RequestCost:
    """Per-request resource attribution, accumulated by the engine.

    Device-time shares are host-measured around each dispatch and split
    evenly across the requests riding it (batched decode/verify), so the
    per-phase totals sum to engine dispatch time.  Dispatch is async, so
    these measure *enqueue* + any sync the step forced; a ``jax.profiler``
    trace shows the device work against the engine's ``engine.*`` spans.
    ``page_steps`` integrates pages held per decode
    step (paged engines) — the request's KV-memory x time footprint.
    """

    prefill_s: float = 0.0
    decode_s: float = 0.0
    verify_s: float = 0.0
    dispatches: int = 0
    page_steps: int = 0

    def as_dict(self) -> dict:
        return {"prefill_s": self.prefill_s, "decode_s": self.decode_s,
                "verify_s": self.verify_s, "dispatches": self.dispatches,
                "page_steps": self.page_steps}


@dataclasses.dataclass
class Request:
    """One generation request: prompt tokens in, sampled tokens out."""

    req_id: int
    prompt: list[int]
    max_new_tokens: int
    sampling: SamplingParams = dataclasses.field(default_factory=SamplingParams)
    eos_token: Optional[int] = None
    # Admission rank for priority-aware policies (higher = sooner); the
    # default FIFO admission ignores it.  See ``policies.PriorityAdmission``.
    priority: int = 0
    # Streaming hook: called with each sampled token as it reaches the
    # host.  The engine's lazy pulls are forced eager for streaming
    # requests (tokens surface every step instead of at sync points), so a
    # callback trades a little decode-dispatch overlap for latency.
    on_token: Optional[Callable[[int], None]] = dataclasses.field(
        default=None, repr=False)
    # Text-streaming hook: called with each NEW text fragment whenever a
    # token reaches the host.  Deltas are computed by re-decoding the whole
    # output through ``detokenizer`` (incremental-safe for tokenizers whose
    # decode of a prefix is a prefix of the decode — e.g. BPE byte-level),
    # so multi-token characters surface only once complete.  Forces eager
    # host pulls exactly like ``on_token``.
    on_text: Optional[Callable[[str], None]] = dataclasses.field(
        default=None, repr=False)
    # Pluggable ``decode(token_ids) -> str`` used by ``on_text`` / ``text``;
    # defaults to the vocabulary-free ``default_detokenizer``.
    detokenizer: Optional[Callable[[Sequence[int]], str]] = dataclasses.field(
        default=None, repr=False)

    # SLO deadline (seconds from submit); resolved from
    # ``sampling.deadline_s`` at add_request unless passed explicitly.
    deadline_s: Optional[float] = None
    # stamped by the scheduler when the deadline already expired in queue
    # (the request was doomed before it ever held a slot)
    late_at_admission: bool = False
    # engine-stamped terminal reason that overrides the eos/length
    # inference (e.g. "deadline" for requests shed at ingress)
    finish_reason_override: Optional[str] = None

    state: RequestState = RequestState.WAITING
    slot: Optional[int] = None
    # resource attribution (see RequestCost)
    cost: RequestCost = dataclasses.field(default_factory=RequestCost,
                                          repr=False)
    output_tokens: list[int] = dataclasses.field(default_factory=list)
    # text already emitted through ``on_text`` (delta bookkeeping)
    emitted_text: str = dataclasses.field(default="", repr=False)
    # chunked admission progress: prompt tokens already prefilled
    prefill_done: int = 0

    # wall-clock timeline (engine-stamped)
    submit_time: float = 0.0
    admit_time: Optional[float] = None
    first_token_time: Optional[float] = None
    finish_time: Optional[float] = None

    @property
    def prompt_len(self) -> int:
        return len(self.prompt)

    @property
    def done(self) -> bool:
        if len(self.output_tokens) >= self.max_new_tokens:
            return True
        return (self.eos_token is not None and self.output_tokens
                and self.output_tokens[-1] == self.eos_token)

    def append_token(self, tok: int) -> None:
        if self.first_token_time is None:
            self.first_token_time = time.perf_counter()
        self.output_tokens.append(tok)
        if self.on_token is not None:
            self.on_token(tok)
        if self.on_text is not None:
            full = self.decode_text()
            delta = full[len(self.emitted_text):]
            if delta:
                self.on_text(delta)
            self.emitted_text = full

    def decode_text(self) -> str:
        """The output so far through the request's detokenizer."""
        detok = self.detokenizer or default_detokenizer
        return detok(self.output_tokens)

    @property
    def ttft_s(self) -> Optional[float]:
        """Time to first token (submit -> first sampled token)."""
        if self.first_token_time is None:
            return None
        return self.first_token_time - self.submit_time

    @property
    def latency_s(self) -> Optional[float]:
        if self.finish_time is None:
            return None
        return self.finish_time - self.submit_time

    @property
    def queue_wait_s(self) -> Optional[float]:
        """Time spent WAITING (submit -> admitted into a lane)."""
        if self.admit_time is None:
            return None
        return self.admit_time - self.submit_time

    @property
    def deadline_hit(self) -> Optional[bool]:
        """Did the request finish inside its deadline?  ``None`` while in
        flight or when no deadline was set (no-deadline requests always
        count toward goodput, but report no hit/miss)."""
        if self.deadline_s is None or self.latency_s is None:
            return None
        return self.latency_s <= self.deadline_s

    @property
    def finish_reason(self) -> Optional[str]:
        """Why generation stopped: ``"eos"``, ``"length"`` or an engine
        override like ``"deadline"`` (None while still in flight)."""
        if self.state is not RequestState.FINISHED:
            return None
        if self.finish_reason_override is not None:
            return self.finish_reason_override
        if (self.eos_token is not None and self.output_tokens
                and self.output_tokens[-1] == self.eos_token):
            return "eos"
        return "length"
