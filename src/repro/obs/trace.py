"""Span-based tracer exporting Chrome trace-event JSON.

``Tracer.span("decode_step", step=12)`` opens a nested, context-managed
span; closed spans accumulate as Chrome trace *complete events* (``"ph":
"X"``) that ``save()`` writes as a ``{"traceEvents": [...]}`` document —
drop it onto https://ui.perfetto.dev (or ``chrome://tracing``) and the
engine's prefill/chunk/decode/verify/defrag dispatches render as a
timeline.

Two properties the serving engine depends on:

* **On the profiler's clock.**  Every span, recorded or not, is also a
  ``jax.profiler.TraceAnnotation`` named ``engine.<name>``: inside a
  ``jax.profiler`` trace the engine's phases land on the host plane, on
  the same clock as the device's events, so a device gap can be read
  against the host work around it.  JAX dispatches return before the
  device finishes, so a dispatch span (``decode``, ``prefill``) measures
  the *enqueue*; the device events themselves show when the work ran.
* **Cheap when disabled.**  ``NULL_TRACER`` (a ``NullTracer``) hands out
  a bare ``ProfilerSpan``: one TraceMe, which records nothing outside a
  profiler session — no event list grows, no timestamp is taken, no
  arguments are kept, nothing waits on the device.  Engine code traces
  unconditionally.

Spans nest by call structure: the tracer keeps a stack, stamps each span
with its ``depth``, and Perfetto reconstructs the hierarchy from timestamp
containment on the single engine thread (``tid`` 1).

**Per-lane tracks.**  A span may additionally name the engine lanes
(slots) it covers — ``tracer.span("decode", lanes=running)`` or
``span("prefill", lane=slot)``.  The span still lands on the engine
track, and a copy is emitted per lane at ``tid = slot + 2`` (tid 1 is
the engine stack), with ``thread_name`` metadata so Perfetto renders one
track per lane: batched decode/verify dispatches show up as concurrent
bars across every participating request instead of one engine-thread
stack.
"""

from __future__ import annotations

import json
import time
from typing import Optional

from jax.profiler import TraceAnnotation


def _now_us() -> float:
    return time.perf_counter_ns() / 1000.0


class ProfilerSpan(TraceAnnotation):
    """A span on the profiler's clock only: ``NullTracer``'s span, and the
    profiler half of a recorded ``Span``."""

    def set(self, **kw) -> None:
        pass


class Span:
    """One in-flight span; use via ``with tracer.span(...) as sp``."""

    __slots__ = ("_tracer", "name", "args", "_t0", "_depth", "_lanes",
                 "_annotation")

    def __init__(self, tracer: "Tracer", name: str, args: dict,
                 lanes: tuple = ()):
        self._tracer = tracer
        self.name = name
        self.args = args
        self._t0 = 0.0
        self._depth = 0
        self._lanes = lanes
        self._annotation = ProfilerSpan(f"engine.{name}")

    def set(self, **kw) -> None:
        """Attach (or update) span args after entry."""
        self.args.update(kw)

    def __enter__(self) -> "Span":
        self._annotation.__enter__()
        self._depth = len(self._tracer._stack)
        self._tracer._stack.append(self)
        self._t0 = _now_us()
        return self

    def __exit__(self, exc_type, exc, tb) -> bool:
        t1 = _now_us()
        stack = self._tracer._stack
        if stack and stack[-1] is self:
            stack.pop()
        self._tracer._emit(self, t1)
        self._annotation.__exit__(exc_type, exc, tb)
        return False


class Tracer:
    """Collects spans / instants; exports Chrome trace-event JSON."""

    enabled = True

    def __init__(self):
        # finished events, already in Chrome trace-event dict form
        self.events: list[dict] = []
        self._stack: list[Span] = []
        self._epoch_us = _now_us()
        self._lane_tids: set = set()

    # -- recording ---------------------------------------------------------
    def span(self, name: str, lanes=None, lane=None, **args) -> Span:
        """Open a span.  ``lanes``/``lane`` name the engine slots the
        dispatch covers (any iterable of slot ids, read here); the span is
        mirrored onto each lane's track."""
        if lane is not None:
            lanes = (lane,)
        return Span(self, name, args,
                    tuple(lanes) if lanes is not None else ())

    def instant(self, name: str, **args) -> None:
        """A zero-duration marker (Chrome ``ph: "i"``)."""
        ev = {"name": name, "ph": "i", "ts": _now_us() - self._epoch_us,
              "pid": 1, "tid": 1, "s": "t"}
        if args:
            ev["args"] = args
        self.events.append(ev)

    def _emit(self, span: Span, t1_us: float) -> None:
        ev = {
            "name": span.name,
            "ph": "X",
            "ts": span._t0 - self._epoch_us,
            "dur": t1_us - span._t0,
            "pid": 1,
            "tid": 1,
            "cat": "engine",
        }
        args = dict(span.args)
        args["depth"] = span._depth
        ev["args"] = args
        self.events.append(ev)
        # mirror onto per-lane tracks (tid = slot + 2; tid 1 = engine)
        for slot in span._lanes:
            tid = int(slot) + 2
            self._lane_tids.add(tid)
            lane_ev = dict(ev)
            lane_ev["tid"] = tid
            lane_ev["cat"] = "lane"
            lane_ev["args"] = {**args, "lane": int(slot)}
            self.events.append(lane_ev)

    # -- export ------------------------------------------------------------
    def to_chrome(self) -> dict:
        """The full Chrome trace-event document (Perfetto-loadable)."""
        meta = [
            {"name": "process_name", "ph": "M", "pid": 1, "tid": 1,
             "args": {"name": "repro.serving"}},
            {"name": "thread_name", "ph": "M", "pid": 1, "tid": 1,
             "args": {"name": "engine"}},
        ]
        for tid in sorted(self._lane_tids):
            meta.append({"name": "thread_name", "ph": "M", "pid": 1,
                         "tid": tid, "args": {"name": f"lane {tid - 2}"}})
        return {"traceEvents": meta + self.events, "displayTimeUnit": "ms"}

    def save(self, path: str) -> str:
        with open(path, "w") as f:
            json.dump(self.to_chrome(), f)
        return path


class NullTracer:
    """Disabled tracer: every span is a bare ``ProfilerSpan``."""

    enabled = False
    events: tuple = ()

    def span(self, name: str, **args) -> ProfilerSpan:
        return ProfilerSpan(f"engine.{name}")

    def instant(self, name: str, **args) -> None:
        pass

    def to_chrome(self) -> dict:
        return {"traceEvents": [], "displayTimeUnit": "ms"}

    def save(self, path: str) -> Optional[str]:
        return None


NULL_TRACER = NullTracer()
