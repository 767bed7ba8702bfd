"""The one traffic generator: a mix is a data file of parameters
(``traffic/<mix>.json``), and this module turns it and a seed into
requests.  The program sees only the generated requests.

A mix file's keys (every shape below is data; a new mix is a new file):

* ``kind``: ``open_loop`` — independent users, each request due at its
  offset from the window's open on a wall-clock schedule, whether or not
  earlier ones have finished; or ``backlog`` — an offline batch of
  ``backlog`` requests, all due at once, more than a window can finish.
* ``rate_rps`` (open loop): the mean arrival rate.  The gaps between
  arrivals follow the exponential distribution of a Poisson process.
* ``burst`` (open loop, optional): ``{"period_s": P, "on_s": T}``:
  arrivals come only in the first ``T`` seconds of every ``P``, at
  ``rate_rps * P / T`` there, so the mean rate stays ``rate_rps``.
* ``fill_s`` (open loop, optional): seconds of the same arrivals served
  before the window opens, so that the window opens on the engine as its
  load leaves it, not on an idle one.  They count as set-up and are not
  measured.
* ``stream``, ``prompt``, ``output``: whether a request streams its tokens,
  and the distributions of prompt and output lengths (``quantiles``).
* ``classes`` (optional): a list of request classes, each with its
  ``share`` of the requests and any of ``stream``, ``prompt``, ``output``,
  ``prefix``, ``repeat``, ``topics`` (what a class leaves out it takes
  from the mix).  Without it the mix is one class of its own keys.
* ``prefix`` (a class's): ``{"tokens": L, "count": K}``: ``K`` prefixes of
  ``L`` tokens, the same through the run; each request of the class opens
  with one of them (in turn, in a seeded order), then its own ``prompt``.
* ``repeat`` (a class's): each distinct prompt of the class is sent this
  many times, its copies spread through the block in a seeded order.
* ``topics`` (a class's): ``{"count": K, "tokens": V, "zipf": s}``: each
  request takes one of ``K`` topics (shares ``1 / rank**s``) and draws its
  tokens from that topic's ``V`` ids, so routing sees skewed inputs.
* ``extends``: a mix whose keys this file's replace (``spec.load_mix``).

Every seed gets the same work in another order.  A block of ``n``
requests takes each class's count from its share (largest remainder), its
prompt lengths, output lengths and arrival gaps each from the ``n``
stratified quantiles ``(i + 1/2) / n`` of their distribution, its topics
by the same rule, and the seed draws a permutation of each; token ids are
uniform over the vocabulary (or the topic's ids).  The window holds
exactly ``round(rate_rps * window_s)`` requests whose gaps fill it; the
arrivals that keep flowing after its close (``after_s``) and those of the
fill before it are blocks of their own.  So the requests due in the
window, and their sizes, are the same set for every seed, and a seed
changes which size comes when.
"""

from __future__ import annotations

import dataclasses
import math
from statistics import NormalDist

import numpy as np


@dataclasses.dataclass(frozen=True)
class TrafficRequest:
    due_s: float             # offset from the window's open (< 0: in the fill)
    prompt: np.ndarray       # int32 token ids
    max_new_tokens: int      # generated to this length (no EOS)
    stream: bool = True      # tokens taken by a streaming callback


def quantiles(spec: dict, n: int) -> np.ndarray:
    """The ``n`` stratified quantiles of a length distribution, clipped
    to ``[min, max]``, as whole numbers in ascending order."""
    u = (np.arange(n) + 0.5) / n
    kind, lo, hi = spec["dist"], spec["min"], spec["max"]
    if kind == "lognormal":
        z = np.array([NormalDist().inv_cdf(p) for p in u])
        x = np.exp(np.log(spec["median"]) + spec["sigma"] * z)
    elif kind == "loguniform":
        x = np.exp(np.log(lo) + u * (np.log(hi) - np.log(lo)))
    elif kind == "uniform":
        x = lo + u * (hi + 1 - lo)
    else:
        raise ValueError(f"unknown length distribution {kind!r}")
    return np.clip(np.floor(x), lo, hi).astype(np.int64)


def split(shares, n: int) -> np.ndarray:
    """``n`` parted by ``shares`` into whole counts (largest remainder,
    ties to the first)."""
    w = np.asarray(shares, np.float64)
    exact = n * w / w.sum()
    counts = np.floor(exact).astype(np.int64)
    rest = np.argsort(-(exact - counts), kind="stable")[: n - counts.sum()]
    counts[rest] += 1
    return counts


def classes(mix: dict) -> list[dict]:
    """The mix's request classes, each with every key it leaves out taken
    from the mix."""
    own = {k: mix[k] for k in ("stream", "prompt", "output", "prefix", "repeat",
                               "topics") if k in mix}
    return [dict(own, **c) for c in mix.get("classes", [dict(share=1.0)])]


def max_prompt_len(mix: dict) -> int:
    return max(c["prompt"]["max"] + c.get("prefix", {}).get("tokens", 0)
               for c in classes(mix))


def max_output_len(mix: dict) -> int:
    return max(c["output"]["max"] for c in classes(mix))


def _class_block(c: dict, rng, n: int) -> dict:
    """Sizes of ``n`` requests of class ``c``: prompt and output lengths
    (seeded permutations of stratified quantiles), and for a repeated
    class which distinct prompt each request sends."""
    rep = int(c.get("repeat", 1))
    n_distinct = -(-n // rep)
    plen = rng.permutation(quantiles(c["prompt"], n_distinct))
    out = rng.permutation(quantiles(c["output"], n))
    copy = np.arange(n) if rep == 1 else rng.permutation(np.arange(n) % n_distinct)
    return {"plen": plen, "out": out, "copy": copy}


def _arrivals(rng, n: int, span_s: float, burst: dict | None) -> np.ndarray:
    """Due offsets of ``n`` arrivals over ``span_s``, the first at 0:
    stratified exponential gaps in a seeded order, scaled to fill the span
    (or, with ``burst``, the on-phases of its periods)."""
    gaps = rng.permutation(-np.log1p(-(np.arange(n) + 0.5) / n))
    if burst is None:
        gaps *= span_s / gaps.sum()
        return np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    on, period = float(burst["on_s"]), float(burst["period_s"])
    gaps *= span_s * on / period / gaps.sum()
    tau = np.concatenate([[0.0], np.cumsum(gaps)[:-1]])
    return np.floor(tau / on) * period + np.mod(tau, on)


def _block(mix, cls, rng, n: int, span_s: float | None):
    """``n`` requests: (due offsets, class index, per-class sizes, index of
    each request within its class).  Counts per class follow the shares;
    which class comes when is a seeded permutation."""
    counts = split([c["share"] for c in cls], n)
    sizes = [_class_block(c, rng, int(k)) for c, k in zip(cls, counts)]
    which = np.repeat(np.arange(len(cls)), counts)
    if len(cls) > 1:
        which = rng.permutation(which)
    index = np.zeros(n, np.int64)
    for ci in range(len(cls)):
        index[which == ci] = np.arange(counts[ci])
    due = np.zeros(n) if span_s is None else _arrivals(rng, n, span_s, mix.get("burst"))
    return due, which, sizes, index


def _topic_of(c: dict, rng, n: int) -> np.ndarray:
    t = c["topics"]
    shares = 1.0 / np.arange(1, t["count"] + 1) ** float(t.get("zipf", 1.0))
    return rng.permutation(np.repeat(np.arange(t["count"]), split(shares, n)))


def generate(mix: dict, seed: int, window_s: float, vocab: int,
             after_s: float = 0.0, rate_rps: float | None = None
             ) -> list[TrafficRequest]:
    """The mix's requests for a window of ``window_s`` seconds and, in an
    open loop, ``after_s`` seconds of arrivals after it and the mix's
    ``fill_s`` before it (``rate_rps`` overrides the mix's rate, for the
    knee sweep), in order of due time."""
    ss = np.random.SeedSequence(int(seed) % (1 << 64))
    r_perm, r_tok, r_extra = (np.random.default_rng(s) for s in ss.spawn(3))
    cls = classes(mix)
    blocks = []   # (due offset, block)
    if mix["kind"] == "backlog":
        blocks.append((0.0, _block(mix, cls, r_perm, int(mix["backlog"]), None)))
    elif mix["kind"] == "open_loop":
        rate = mix["rate_rps"] if rate_rps is None else rate_rps
        blocks.append((0.0, _block(mix, cls, r_perm, max(1, round(rate * window_s)),
                                   window_s)))
        n_a = math.ceil(rate * after_s)
        if n_a:
            blocks.append((window_s, _block(mix, cls, r_perm, n_a, n_a / rate)))
        fill = float(mix.get("fill_s", 0.0))
        n_f = round(rate * fill)
        if n_f:
            blocks.insert(0, (-fill, _block(mix, cls, r_perm, n_f, fill)))
    else:
        raise ValueError(f"unknown traffic kind {mix['kind']!r}")

    # shared prefixes and topic vocabularies: the same through the run
    prefixes = [None if "prefix" not in c else r_extra.integers(
        1, vocab, (c["prefix"]["count"], c["prefix"]["tokens"]), dtype=np.int32)
        for c in cls]
    pools = [None if "topics" not in c else np.stack([
        r_extra.choice(np.arange(1, vocab, dtype=np.int32), c["topics"]["tokens"],
                       replace=False) for _ in range(c["topics"]["count"])])
        for c in cls]

    # what each request sends: (due, class, own prompt length, output
    # length, prefix row, topic, distinct-prompt key)
    plan = []
    for b, (offset, (due, which, sizes, index)) in enumerate(blocks):
        topics = [None if pools[ci] is None
                  else _topic_of(cls[ci], r_extra, len(sizes[ci]["plen"]))
                  for ci in range(len(cls))]
        for i in range(len(due)):
            ci, j = int(which[i]), int(index[i])
            s = sizes[ci]
            k = int(s["copy"][j])
            plan.append((offset + float(due[i]), ci, int(s["plen"][k]), int(s["out"][j]),
                         None if prefixes[ci] is None else k % len(prefixes[ci]),
                         None if topics[ci] is None else int(topics[ci][k]),
                         (b, ci, k)))

    # the uniform ids, in one draw in request order (each distinct prompt
    # drawn once); a topic's ids come from its own pool
    seen, n_uniform = set(), 0
    for p in plan:
        if p[5] is None and p[6] not in seen:
            seen.add(p[6])
            n_uniform += p[2]
    toks = r_tok.integers(1, vocab, n_uniform, dtype=np.int32)
    own, at = {}, 0
    out = []
    for due, ci, plen, olen, pre, topic, key in plan:
        if key not in own:
            if topic is None:
                own[key] = toks[at:at + plen]
                at += plen
            else:
                own[key] = pools[ci][topic][r_extra.integers(0, len(pools[ci][topic]), plen)]
        prompt = own[key] if pre is None else np.concatenate([prefixes[ci][pre], own[key]])
        out.append(TrafficRequest(due, prompt, olen, bool(cls[ci]["stream"])))
    return out      # blocks are in order of due time, and so is each block
