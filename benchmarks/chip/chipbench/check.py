"""The comparison that decides ``correct``.

Once the window has closed and the program's state is freed, a sample of
the requests the program finished (drawn from the seed, the longest one
always in it) is run through the plain float32 reference over each
prompt followed by its served tokens.  At every served position the
number compared is how far the served token's reference logit lies below
the reference's best logit there; the run's reading is the widest such
gap.  The limit is the configuration's (``check.max_logit_gap``), set
from readings of the program on a dozen seeds and of the control (the
reference one precision step lower) as ``PERF.md`` records.

Every request due in the window has to be served, too: one refused, or
with no token by the time the run gave up on it, is missing.
"""

from __future__ import annotations

import numpy as np


def sample(tracked, seed: int, target_tokens: int) -> list:
    """Finished requests to compare: the longest (prompt plus output)
    first, then others in an order drawn from the seed, until the sample
    holds ``target_tokens`` served tokens."""
    done = [t for t in tracked if t.req is not None and t.done and t.error is None]
    if not done:
        return []
    longest = max(done, key=lambda t: (t.req.prompt_len + len(t.req.output_tokens),
                                       -done.index(t)))
    rest = [t for t in done if t is not longest]
    order = np.random.default_rng(int(seed) % (1 << 64) ^ 0x5EED).permutation(len(rest))
    out, n = [longest], len(longest.req.output_tokens)
    for i in order:
        if n >= target_tokens:
            break
        out.append(rest[i])
        n += len(rest[i].req.output_tokens)
    return out


def gaps(fam: str, m: dict, seed: int, pairs, quant_ctrl=None) -> dict:
    """Reference gaps for ``pairs`` of (prompt ids, served ids), through the
    configuration's reference family ``fam``.

    Returns the per-position gap of the served tokens (``served``) and,
    with ``quant_ctrl``, of the tokens the control puts first
    (``control``) at the same positions."""
    from reference import decoder

    rows = decoder.scored_rows(fam, m, seed, pairs)
    ctrl = (None if quant_ctrl is None
            else decoder.scored_rows(fam, m, seed, pairs, quant=quant_ctrl))
    served = np.concatenate([np.asarray(s, np.int32) for _, s in pairs])
    sc = decoder.head_scores(m, seed, rows, served, ctrl)
    out = {"served": sc["best"] - sc["served"]}
    if "control" in sc:
        out["control"] = sc["best"] - sc["control"]
    return out


def missing(tracked, window) -> int:
    """Requests due in the window that were refused, or that had produced
    no token by the time the run gave up on them.  One still streaming at
    the drain's limit (an answer of up to a thousand tokens can outlast
    it) is late, not missing: its tail shows in the latency metrics."""
    t0, t1 = window
    return sum(1 for t in tracked if t0 <= t.due < t1
               and (t.error is not None or t.req is None or not t.req.output_tokens))


def verdict(checks: dict) -> bool:
    """``correct``: every number compared has a reading within its limit
    (no reading, as where nothing finished, is not correct)."""
    return all(c["value"] is not None and c["value"] <= c["limit"]
               for c in checks.values())
