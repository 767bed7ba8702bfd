"""Reduce a profiler trace (``*.xplane.pb``) to what the per-layer
metrics read: device busy time, device self time per operation, device
time per kernel, the idle gaps labelled by what the harness was doing,
each device op's HLO scope, and the program's ``engine.*`` host spans.

The harness marks the slice it measures with a ``measured_slice``
annotation on the host; everything is clipped to that slice.  Device
operations are the events of each TPU plane's ``XLA Ops`` line; a
kernel's events are those whose name, or whose HLO text, names it.  Host
labels are the harness's own annotations (``step``, ``admit``,
``await_arrival``, ``token_cb``); a gap that none of them covers is the
harness's own bookkeeping between steps.  An op's scope (``scopes``,
``op_scopes.read_scopes``) and the engine's spans (``host_spans``,
``host_spans.host_spans``) are kept apart from the rest, which they leave
as it was.
"""

from __future__ import annotations

import glob
import os
import re
from collections import defaultdict

SLICE = "measured_slice"
HOST_LABELS = ("token_cb", "admit", "await_arrival", "step")   # innermost first
ENGINE_PREFIX = "engine."       # the program's own host spans
DEVICE_PLANE = re.compile(r"^/device:TPU:(\d+)$")
OPS_LINE = "XLA Ops"
TOP_N = 10


def find_xplane(trace_dir: str) -> str:
    files = sorted(glob.glob(os.path.join(trace_dir, "**", "*.xplane.pb"),
                          recursive=True), key=os.path.getmtime)
    if not files:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return files[-1]


def _stats(ev) -> dict:
    try:
        return {k: v for k, v in ev.stats}
    except (TypeError, ValueError):
        return {}


def read_events(path: str):
    """(host annotations, device ops per TPU index) from one trace file:
    the slice, the harness's annotations and the program's ``engine.*``
    spans, each (name, start_ns, end_ns); each device op (name, start_ns,
    end_ns, hlo_text)."""
    from jax.profiler import ProfileData

    pd = ProfileData.from_file(path)
    host, devices = [], defaultdict(list)
    for plane in pd.planes:
        m = DEVICE_PLANE.match(plane.name)
        for line in plane.lines:
            if m is not None:
                if line.name != OPS_LINE:
                    continue
                for ev in line.events:
                    st = _stats(ev)
                    text = str(st.get("long_name", "")) + " " + str(st.get("tf_op", ""))
                    devices[int(m.group(1))].append(
                        (ev.name, ev.start_ns, ev.start_ns + ev.duration_ns, text))
            elif plane.name.startswith("/host"):
                for ev in line.events:
                    if (ev.name == SLICE or ev.name in HOST_LABELS
                            or ev.name.startswith(ENGINE_PREFIX)):
                        host.append((ev.name, ev.start_ns,
                                     ev.start_ns + ev.duration_ns))
    return host, dict(devices)


def union(intervals):
    """Merged, sorted intervals."""
    out = []
    for a, b in sorted(intervals):
        if out and a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return out


def label_at(host, t) -> str:
    """The innermost harness annotation around host time ``t``."""
    covering = [name for name, a, b in host if a <= t < b and name != SLICE]
    for name in HOST_LABELS:
        if name in covering:
            return name
    return "between_steps"


def reduce_events(host, devices, chips: int) -> dict:
    """Busy time, per-op time, idle gaps and the clipped device events of
    the first ``chips`` devices, within the measured slice."""
    spans = [(a, b) for name, a, b in host if name == SLICE]
    if not spans:
        raise ValueError(f"the trace holds no {SLICE!r} annotation")
    lo, hi = spans[0]
    window_s = (hi - lo) / 1e9
    busy, per_op, events = [], defaultdict(float), []
    gaps = []
    for idx in sorted(devices)[:chips]:
        evs = [(n, max(a, lo), min(b, hi), text)
               for n, a, b, text in devices[idx] if b > lo and a < hi]
        merged = union([(a, b) for _, a, b, _ in evs])
        busy.append(sum(b - a for a, b in merged) / 1e9)
        for (n, a, b, text), own in zip(evs, self_times(evs)):
            per_op[_op_key(n)] += own / 1e9 / chips
            events.append((n, (b - a) / 1e9 / chips, text))
        if idx == sorted(devices)[0]:
            edges = [lo] + [x for iv in merged for x in iv] + [hi]
            for a, b in zip(edges[::2], edges[1::2]):
                if b > a:
                    gaps.append((label_at(host, (a + b) / 2), (b - a) / 1e9))
    top = sorted(per_op.items(), key=lambda kv: -kv[1])[:TOP_N]
    longest = sorted(gaps, key=lambda g: -g[1])[:TOP_N]
    return {"window_s": window_s,
            "busy_s": sum(busy) / len(busy) if busy else 0.0,
            "ops_s": dict(per_op), "events": events,
            "top_ops": [[k, v] for k, v in top],
            "idle_gaps": [[k, v] for k, v in longest]}


def self_times(evs) -> list[float]:
    """Each event's time less that of the events nested inside it (a
    ``while`` of the layer scan holds the ops of every layer), so that
    per-op times add up to the busy time instead of counting it twice."""
    own = [b - a for _, a, b, _ in evs]
    stack: list[int] = []
    for i in sorted(range(len(evs)), key=lambda i: (evs[i][1], -evs[i][2])):
        _, a, b, _ = evs[i]
        while stack and evs[stack[-1]][2] <= a:
            stack.pop()
        if stack and b <= evs[stack[-1]][2]:
            own[stack[-1]] -= b - a
        stack.append(i)
    return own


def _op_key(name: str) -> str:
    """Group an op's events by its HLO name without the instance suffix,
    and its output type where the event carries the HLO text
    (``%fusion.148 = s8[14336,5120]{...} fusion(...)`` -> ``fusion
    s8[14336,5120]``), so that recompiles do not split them."""
    head, _, rest = name.lstrip("%").partition(" = ")
    key = re.sub(r"[.:]\d+$", "", head)
    out = re.match(r"[a-z0-9]+\[[0-9,]*\]", rest)
    return f"{key} {out.group(0)}" if out else key


def reduce_trace(trace_dir: str, chips: int) -> dict:
    """``reduce_events`` of the newest trace under ``trace_dir``, with each
    device op's HLO scope (``scopes``: event name -> ``tf_op``) and the
    slice's ``engine.*`` spans (``host_spans``)."""
    from chipbench import host_spans, op_scopes

    path = find_xplane(trace_dir)
    host, devices = read_events(path)
    out = reduce_events(host, devices, chips)
    out["scopes"] = op_scopes.read_scopes(path)
    out["host_spans"] = host_spans.host_spans(host)
    return out


def kernel_seconds(reduced: dict, needle: str) -> tuple[float, int]:
    """Device seconds (per chip) and event count of the kernel whose name
    or HLO text holds ``needle``."""
    hits = [s for n, s, text in reduced["events"] if needle in n or needle in text]
    return sum(hits), len(hits)
