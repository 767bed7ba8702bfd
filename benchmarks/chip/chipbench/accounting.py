"""Metric arithmetic from a run's record: latency percentiles over every
request due in the window, gaps between streamed tokens, tokens per
second, and the logical operations and bytes that utilisation and
roofline shares are taken against.

A run's record (built by ``run.py`` from ``drive``) holds, per request,
host times in seconds on one clock: ``due``, ``token_times`` (each output
token as the host received it), ``prefill_events`` (``[time, first_row,
last_row_exclusive]`` for prompt rows whose prefill finished), and
``prompt_len``; and the engine steps (``steps``: host start and end, and
what each dispatched).
"""

from __future__ import annotations

import numpy as np


def percentile(values, q: float) -> float:
    """The ``q``-th percentile (linear interpolation between order
    statistics, numpy's default)."""
    if len(values) == 0:
        raise ValueError("percentile of no values")
    return float(np.percentile(np.asarray(values, np.float64), q))


def in_window(rec: dict) -> list[dict]:
    """Requests due inside the measured window."""
    t0, t1 = rec["window"]
    return [r for r in rec["requests"] if t0 <= r["due"] < t1]


def ttft_values(rec: dict) -> list[float]:
    """Due time to first streamed token, for every request due in the
    window.  One that never produced a token counts as having waited until
    the run gave up on it (``rec["end"]``): it sits at the top of the tail."""
    out = []
    for r in in_window(rec):
        first = r["token_times"][0] if r["token_times"] else rec["end"]
        out.append(first - r["due"])
    return out


def itl_values(rec: dict) -> list[float]:
    """Every gap between consecutive streamed tokens of every request due
    in the window (its drain included)."""
    out = []
    for r in in_window(rec):
        t = r["token_times"]
        out.extend(b - a for a, b in zip(t, t[1:]))
    return out


def tokens_in_window(rec: dict) -> tuple[int, int]:
    """(prompt rows prefilled, output tokens received) inside the window."""
    t0, t1 = rec["window"]
    prompt = sum(b - a for r in rec["requests"] for t, a, b in r["prefill_events"]
                 if t0 <= t < t1)
    out = sum(1 for r in rec["requests"] for t in r["token_times"] if t0 <= t < t1)
    return prompt, out


def tok_s(rec: dict) -> float:
    t0, t1 = rec["window"]
    prompt, out = tokens_in_window(rec)
    return (prompt + out) / (t1 - t0)


# ---------------------------------------------------------------------------
# Logical work of the model, from the configuration's published sizes
# ---------------------------------------------------------------------------

def layer_counts(fam, m: dict) -> dict:
    """The logical sizes of the reference family module ``fam``, layer by
    layer (``reference.layer_kinds``): each layer's ``layer_params`` and
    ``kernel_gemms``, and the (query-key, value) width of an attention
    head.  A run's record keeps them as ``counts``."""
    from reference import attn_dims, layer_kinds

    kinds = layer_kinds(fam, m)
    return {"layer_params": [fam.layer_params(m, k) for k in kinds],
            "kernel_gemms": [fam.kernel_gemms(m, k) for k in kinds],
            "attn_dims": list(attn_dims(fam, m))}


def body_params(rec: dict) -> int:
    """Active matmul parameters a token passes through below the head,
    summed over the layers of ``rec["counts"]["layer_params"]``."""
    return sum(p["attn"] + p["ffn_active"] for p in rec["counts"]["layer_params"])


def head_params(m: dict) -> int:
    return m["vocab_size"] * m["hidden_size"]


def attn_ops(m: dict, first: int, last: int, dims=None) -> float:
    """Operations of attention for query positions ``[first, last)``, each
    attending every key up to and including itself: two matrix products,
    scores of ``2 * keys * qk_dim`` and values of ``2 * keys * v_dim``, per
    query head per layer (``dims``: ``(qk_dim, v_dim)``, ``head_dim`` both
    where not given)."""
    qk, v = dims or (m["head_dim"], m["head_dim"])
    keys = (last * (last + 1) - first * (first + 1)) / 2.0
    return (2.0 * keys * (qk + v) * m["num_attention_heads"]
            * m["num_hidden_layers"])


def model_ops_in_window(rec: dict, m: dict) -> float:
    """Logical operations of the tokens served in the window: prompt rows
    through the body with causal attention, each output token's head, and
    each decoded token's body and attention over its context.  Padding,
    idle lanes and re-quantization are not counted."""
    t0, t1 = rec["window"]
    body, head = 2.0 * body_params(rec), 2.0 * head_params(m)
    dims = rec["counts"]["attn_dims"]
    ops = 0.0
    for r in rec["requests"]:
        p = r["prompt_len"]
        for t, a, b in r["prefill_events"]:
            if t0 <= t < t1:
                ops += body * (b - a) + attn_ops(m, a, b, dims)
        for j, t in enumerate(r["token_times"]):
            if not t0 <= t < t1:
                continue
            ops += head
            if j > 0:   # token j-1 was decoded at position p + j - 1
                ops += body + attn_ops(m, p + j - 1, p + j, dims)
    return ops


def step_seconds(rec: dict, lo: float, hi: float) -> float:
    """Wall time of the engine steps wholly inside ``[lo, hi]``."""
    return sum(s["t1"] - s["t0"] for s in steps_in(rec, lo, hi))


def mfu(rec: dict, m: dict, peak_ops: float) -> float:
    """The model step's share (%) of the chip's peak: the window's logical
    work over the wall time its engine steps took (time spent waiting for
    arrivals is not a step's)."""
    spent = step_seconds(rec, *rec["window"])
    return 100.0 * model_ops_in_window(rec, m) / (spent * peak_ops)


# ---------------------------------------------------------------------------
# Kernel rooflines
# ---------------------------------------------------------------------------

def gemm_least_time(mm: int, k: int, n: int, peaks: dict) -> float:
    """Least time of one int8 GEMM ``(M, K) @ (K, N)`` with per-row and
    per-column f32 scales and an f32 output: ``2 M K N`` operations at the
    int8 peak, or its operand, scale and output bytes at the HBM rate,
    whichever is larger."""
    ops = 2.0 * mm * k * n
    nbytes = mm * k + k * n + 4 * (mm + n) + 4 * mm * n
    return max(ops / peaks["int8_ops_per_s"], nbytes / peaks["hbm_bytes_per_s"])


def paged_attn_bytes(m: dict, lengths) -> float:
    """Least bytes one decode step's paged attention reads for one layer:
    the int8 K and V rows and their f32 per-(row, head) scales of every
    active lane's context (``lengths``: keys each lane attends)."""
    hkv, hd = m["num_key_value_heads"], m["head_dim"]
    rows = float(sum(lengths))
    return rows * hkv * (2 * hd + 2 * 4)


def steps_in(rec: dict, lo: float, hi: float) -> list[dict]:
    """Engine steps wholly inside host times ``[lo, hi]``."""
    return [s for s in rec["steps"] if s["t0"] >= lo and s["t1"] <= hi]


def gemm_rows(step: dict, serving: dict) -> list[int]:
    """Rows (M) of the GEMMs one engine step dispatched: the whole lane
    pool per decode, the padded bucket per one-shot prefill, a chunk per
    chunk step (``serving``: the deployment's geometry)."""
    buckets = sorted(serving.get("prefill_buckets") or [])
    rows = [serving["n_slots"]] * step["decode"]
    rows += [next((b for b in buckets if b >= p), p) for p in step["oneshot_prompts"]]
    rows += [serving.get("prefill_chunk") or 0] * step["chunks"]
    return rows


def gemm_least_seconds(rec: dict, lo: float, hi: float) -> float:
    """Least time of every quantized GEMM the steps in ``[lo, hi]``
    dispatched (``rec["counts"]["kernel_gemms"]``: the (K, N) of each
    layer's plain linear GEMMs, from the reference family)."""
    peaks = rec["peaks"]
    gemms = [kn for layer in rec["counts"]["kernel_gemms"] for kn in layer]
    total = 0.0
    for st in steps_in(rec, lo, hi):
        for mm in gemm_rows(st, rec["serving"]):
            total += sum(gemm_least_time(mm, k, n, peaks) for k, n in gemms)
    return total


def paged_attn_least_seconds(rec: dict, lo: float, hi: float) -> float:
    """Least time of the paged-attention reads of the decode steps in
    ``[lo, hi]``, every layer, at the HBM rate."""
    m = rec["model"]
    nbytes = sum(m["num_hidden_layers"] * paged_attn_bytes(m, st["decode_keys"])
                 for st in steps_in(rec, lo, hi))
    return nbytes / rec["peaks"]["hbm_bytes_per_s"]


def share(least_s: float, spent_s: float):
    """A roofline share in %, or None where the kernel did not run."""
    if spent_s <= 0:
        return None
    return 100.0 * least_s / spent_s
