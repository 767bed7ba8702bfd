"""The engine's own spans in a profiler trace: the ``engine.*``
annotations that the program's tracer (``repro.obs.trace``) puts on the
host plane, on the device trace's clock, which ``trace_reduce.read_events``
keeps beside the harness's annotations.

``host_spans`` clips them to the ``measured_slice``; ``label_at`` names
the host work around an instant, innermost first: the harness's
``token_cb``, then the innermost ``engine.*`` span, then the harness's
``admit``, ``await_arrival`` and ``step``; ``idle_by_label`` splits all of
the device's idle time by those labels; ``host_ms_per_step`` prices the
host work that each decode step's tokens wait on.  A trace of a program
without the spans holds none: every reading is then empty or ``None``.
"""

from __future__ import annotations

import statistics

from chipbench import trace_reduce

PREFIX = trace_reduce.ENGINE_PREFIX
STEP, DECODE = PREFIX + "step", PREFIX + "decode"
# the engine's blocking waits on the device, not host work
WAITS = (PREFIX + "token_pull", PREFIX + "first_token_pull", PREFIX + "admit_sync")
HARNESS = ("admit", "await_arrival", "step")      # outermost last


def host_spans(host) -> dict:
    """The slice's bounds and the ``engine.*`` spans inside it, clipped."""
    lo, hi = next((a, b) for name, a, b in host if name == trace_reduce.SLICE)
    spans = [[name, max(a, lo), min(b, hi)] for name, a, b in host
             if name.startswith(PREFIX) and b > lo and a < hi]
    return {"slice": [lo, hi], "spans": sorted(spans, key=lambda s: s[1])}


def label_at(host, t) -> str:
    """The innermost annotation around host time ``t``: a streaming
    callback, else the engine phase, else what the harness was doing."""
    covering = [(name, a, b) for name, a, b in host
                if a <= t < b and name != trace_reduce.SLICE]
    names = {name for name, _, _ in covering}
    if "token_cb" in names:
        return "token_cb"
    engine = [(a, -b, name) for name, a, b in covering if name.startswith(PREFIX)]
    if engine:
        return max(engine)[2]
    return next((name for name in HARNESS if name in names), "between_steps")


def idle_intervals(host, devices) -> list:
    """The first device's idle intervals inside the slice, ``(a, b)`` ns."""
    lo, hi = next((a, b) for name, a, b in host if name == trace_reduce.SLICE)
    evs = devices[min(devices)] if devices else []
    merged = trace_reduce.union([(max(a, lo), min(b, hi)) for _, a, b, _ in evs
                                 if b > lo and a < hi])
    edges = [lo] + [x for iv in merged for x in iv] + [hi]
    return [(a, b) for a, b in zip(edges[::2], edges[1::2]) if b > a]


def split(host, a, b) -> dict:
    """Seconds of the host interval ``[a, b)`` under each ``label_at``
    label, cut wherever an annotation starts or ends."""
    cuts = sorted({a, b} | {x for _, s, e in host for x in (s, e) if a < x < b})
    out: dict = {}
    for x, y in zip(cuts, cuts[1:]):
        label = label_at(host, (x + y) / 2)
        out[label] = out.get(label, 0.0) + (y - x) / 1e9
    return out


def idle_by_label(host, devices) -> dict:
    """The slice's device idle time split by what the host was doing,
    longest first: where the device waits, and on what."""
    out: dict = {}
    for a, b in idle_intervals(host, devices):
        for label, s in split(host, a, b).items():
            out[label] = out.get(label, 0.0) + s
    return dict(sorted(out.items(), key=lambda kv: -kv[1]))


def host_ms_per_step(hs: dict):
    """Median over the slice's decode steps (``engine.step`` spans that
    hold an ``engine.decode``, and that the slice does not cut) of the
    step's time less its blocking waits on the device, in milliseconds:
    the host work each streamed token waits on.  ``None`` without one."""
    lo, hi = hs["slice"]
    spans = hs["spans"]
    per_step = []
    for name, a, b in spans:
        if name != STEP or a <= lo or b >= hi:
            continue
        inside = [(n, x, y) for n, x, y in spans if a <= x and y <= b]
        if not any(n == DECODE for n, _, _ in inside):
            continue
        waits = sum(y - x for n, x, y in inside if n in WAITS)
        per_step.append((b - a - waits) / 1e6)
    return statistics.median(per_step) if per_step else None


def phase_seconds(hs: dict) -> dict:
    """Seconds and count of each ``engine.*`` span name in the slice."""
    out: dict = {}
    for name, a, b in hs["spans"]:
        s, n = out.get(name, (0.0, 0))
        out[name] = (s + (b - a) / 1e9, n + 1)
    return {k: {"s": s, "n": n} for k, (s, n) in sorted(out.items())}
