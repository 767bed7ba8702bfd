"""Recurrent blocks: RG-LRU (RecurrentGemma/Griffin) and xLSTM (mLSTM/sLSTM).

* RG-LRU trains/prefills with ``jax.lax.associative_scan`` (parallel scan
  over the linear recurrence) and decodes with an O(1) state update.
* mLSTM uses the chunkwise-parallel formulation: quadratic attention-like
  math inside fixed chunks, a sequential scan over chunk boundaries carrying
  the (C, n) matrix memory.  Gates are sigmoidal (log-space decay products),
  a documented simplification of the paper's exponential-gate stabilizer.
* sLSTM is inherently sequential (recurrent R weights); ``lax.scan``.

All projection GEMMs run under the SPOGA quant modes; the elementwise
recurrences stay fp32 (they are not GEMMs — outside SPOGA's scope, see
DESIGN.md §Arch-applicability).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.configs.base import ModelConfig
from repro.models.layers import (
    causal_conv1d,
    conv1d_decode,
    init_conv1d,
    init_linear,
    linear,
    truncated_normal_init,
)

# ---------------------------------------------------------------------------
# RG-LRU
# ---------------------------------------------------------------------------

_RGLRU_C = 8.0


def init_rglru(key, cfg: ModelConfig):
    d = cfg.d_model
    lru = cfg.lru_width or d
    ks = jax.random.split(key, 7)
    return {
        "w_gate_branch": init_linear(ks[0], d, lru),
        "w_x_branch": init_linear(ks[1], d, lru),
        "conv_w": init_conv1d(ks[2], lru, cfg.conv_width),
        "w_rec_gate": init_linear(ks[3], lru, lru),
        "w_in_gate": init_linear(ks[4], lru, lru),
        # Λ init so that a = exp(-c softplus(Λ)) lands in [0.9, 0.999]; fp32
        "lam": truncated_normal_init(ks[5], (lru,), scale=0.1, dtype=jnp.float32) - 4.0,
        "w_out": init_linear(ks[6], lru, d),
    }


def _rglru_coeffs(xb, p, quant_mode, backend=None):
    r = jax.nn.sigmoid(linear(xb, p["w_rec_gate"], quant_mode, backend, tp="col").astype(jnp.float32))
    i = jax.nn.sigmoid(linear(xb, p["w_in_gate"], quant_mode, backend, tp="col").astype(jnp.float32))
    log_a = -_RGLRU_C * jax.nn.softplus(p["lam"]) * r          # (B, S, lru), <= 0
    a = jnp.exp(log_a)
    gated_x = i * xb.astype(jnp.float32)
    b = jnp.sqrt(jnp.maximum(1.0 - jnp.exp(2.0 * log_a), 1e-9)) * gated_x
    return a, b


def rglru_scan(xb, p, quant_mode, h0=None, backend=None):
    """xb: (B, S, lru) conv'd branch -> (y (B,S,lru) fp32, h_last (B,lru))."""
    a, b = _rglru_coeffs(xb, p, quant_mode, backend)
    if h0 is not None:
        # fold the carried state into the first step: h_1 = a_1 h_0 + b_1
        b = b.at[:, 0, :].add(a[:, 0, :] * h0)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h, h[:, -1, :]


def rglru_block(x, p, cfg: ModelConfig, state=None):
    """Griffin recurrent block. state: None | {"h": (B,lru), "conv": (B,W-1,lru)}."""
    qm, be = cfg.quant_mode, cfg.gemm_backend
    gate = jax.nn.gelu(linear(x, p["w_gate_branch"], qm, be, tp="col").astype(jnp.float32))
    xb_raw = linear(x, p["w_x_branch"], qm, be, tp="col")
    xb = causal_conv1d(xb_raw, p["conv_w"])
    h, h_last = rglru_scan(xb, p, qm, None, backend=be)
    y = (gate * h).astype(x.dtype)
    out = linear(y, p["w_out"], qm, be, tp="row")
    new_state = None
    if state is not None:
        # decode continues from here: conv state holds the last W-1 *raw*
        # branch inputs (pre-conv), h the last recurrent state.
        w = cfg.conv_width
        raw = jnp.pad(xb_raw.astype(jnp.float32), ((0, 0), (w - 1, 0), (0, 0)))
        new_state = {"h": h_last, "conv": raw[:, -(w - 1):, :]}
    return out, new_state


def rglru_chunk(x, p, cfg: ModelConfig, state, n_valid):
    """Chunked prefill step: (1, C, d) chunk with only the first
    ``n_valid`` rows real, carrying cell state across chunks.

    ``state`` holds the previous chunk's carry — ``h`` (B, lru) and
    ``conv`` (B, W-1, lru), the last W-1 *raw* pre-conv branch rows in
    fp32 (zeros for the first chunk ≡ ``causal_conv1d``'s left padding).
    Pad rows are forced to the identity recurrence (a=1, b=0) so the
    hidden state holds its last valid value past the boundary: ``h_last``
    and the conv carry are exact regardless of padding, and pad-row
    outputs are garbage confined to rows no later block ever reads (the
    same argument chunked attention makes for its padded tail)."""
    qm, be = cfg.quant_mode, cfg.gemm_backend
    w = cfg.conv_width
    c = x.shape[1]
    gate = jax.nn.gelu(linear(x, p["w_gate_branch"], qm, be, tp="col").astype(jnp.float32))
    xb_raw = linear(x, p["w_x_branch"], qm, be, tp="col")
    # depthwise causal conv with carried context in place of zero padding
    full_raw = jnp.concatenate(
        [state["conv"].astype(jnp.float32), xb_raw.astype(jnp.float32)],
        axis=1)                                           # (B, W-1+C, lru)
    xb = jnp.zeros_like(full_raw[:, w - 1:, :])
    for i in range(w):  # width is tiny (4); matches causal_conv1d's order
        xb = xb + full_raw[:, i: i + c, :] * p["conv_w"][i]
    xb = xb.astype(xb_raw.dtype)
    a, bcoef = _rglru_coeffs(xb, p, qm, be)
    valid = (jnp.arange(c) < n_valid)[None, :, None]
    a = jnp.where(valid, a, 1.0)
    bcoef = jnp.where(valid, bcoef, 0.0)
    h, h_last = rglru_scan_coeffs(a, bcoef, state["h"])
    y = (gate * h).astype(x.dtype)
    out = linear(y, p["w_out"], qm, be, tp="row")
    # conv carry: raw rows at positions [n_valid - W + 1, n_valid) of the
    # ctx+chunk concat — the last W-1 rows ending at the chunk's last
    # valid token (n_valid >= 1 always; the engine never feeds empty chunks)
    new_conv = jax.lax.dynamic_slice_in_dim(full_raw, n_valid, w - 1, axis=1)
    return out, {"h": h_last, "conv": new_conv}


def rglru_scan_coeffs(a, b, h0):
    """The associative scan over precomputed (a, b) with carried ``h0``."""
    b = b.at[:, 0, :].add(a[:, 0, :] * h0)

    def combine(l, r):
        al, bl = l
        ar, br = r
        return al * ar, ar * bl + br

    _, h = jax.lax.associative_scan(combine, (a, b), axis=1)
    return h, h[:, -1, :]


def rglru_decode(x_t, p, cfg: ModelConfig, state):
    """One step. x_t: (B, 1, d); state {"h": (B,lru), "conv": (B,W-1,lru)}."""
    qm, be = cfg.quant_mode, cfg.gemm_backend
    gate = jax.nn.gelu(linear(x_t, p["w_gate_branch"], qm, be, tp="col").astype(jnp.float32))
    xb = linear(x_t, p["w_x_branch"], qm, be, tp="col")[:, 0, :]
    xb_c, conv_state = conv1d_decode(xb, state["conv"], p["conv_w"])
    a, b = _rglru_coeffs(xb_c[:, None, :], p, qm, be)
    h = a[:, 0, :] * state["h"] + b[:, 0, :]
    y = (gate[:, 0, :] * h).astype(x_t.dtype)
    out = linear(y[:, None, :], p["w_out"], qm, be, tp="row")
    return out, {"h": h, "conv": conv_state}


# ---------------------------------------------------------------------------
# mLSTM (xLSTM matrix-memory cell) — chunkwise parallel
# ---------------------------------------------------------------------------

def init_mlstm(key, cfg: ModelConfig):
    d, h = cfg.d_model, cfg.n_heads
    dh = d // h
    ks = jax.random.split(key, 7)
    return {
        "wq": init_linear(ks[0], d, h * dh),
        "wk": init_linear(ks[1], d, h * dh),
        "wv": init_linear(ks[2], d, h * dh),
        "w_igate": init_linear(ks[3], d, h),
        "w_fgate": init_linear(ks[4], d, h),
        "w_ogate": init_linear(ks[5], d, d),
        "w_out": init_linear(ks[6], d, d),
    }


_MLSTM_CHUNK = 256


def _mlstm_chunk_math(q, k, v, logf, logi, C0, n0):
    """One chunk. q,k,v: (B,H,L,dh) fp32; logf,logi: (B,H,L); C0: (B,H,dh,dh)."""
    L = q.shape[2]
    cum_f = jnp.cumsum(logf, axis=-1)                     # log F_t (inclusive)
    # intra-chunk decay: D[t, s] = exp(cum_f[t] - cum_f[s]) * exp(logi[s]), s <= t
    dmat = cum_f[..., :, None] - cum_f[..., None, :] + logi[..., None, :]
    mask = jnp.tril(jnp.ones((L, L), bool))
    dmat = jnp.where(mask, dmat, -jnp.inf)
    D = jnp.exp(dmat)
    scores = jnp.einsum("bhld,bhsd->bhls", q, k) * D
    intra = jnp.einsum("bhls,bhsd->bhld", scores, v)
    Ft = jnp.exp(cum_f)[..., None]                        # (B,H,L,1)
    inter = Ft * jnp.einsum("bhld,bhde->bhle", q, C0)
    num = intra + inter
    # normalizer: n_t = F_t n0 + sum_s (F_t/F_s) i_s k_s ; den = |q . n_t|
    inter_n = Ft * jnp.einsum("bhld,bhd->bhl", q, n0)[..., None]
    n_intra = jnp.einsum("bhls,bhsd->bhld", D, k)
    qn = jnp.einsum("bhld,bhld->bhl", q, n_intra)[..., None] + inter_n
    den = jnp.maximum(jnp.abs(qn), 1.0)
    h = num / den
    # carry to next chunk
    FL = jnp.exp(cum_f[..., -1])[..., None, None]         # (B,H,1,1)
    decay_to_end = jnp.exp(cum_f[..., -1:] - cum_f + logi)  # (B,H,L)
    C1 = FL * C0 + jnp.einsum("bhl,bhld,bhle->bhde", decay_to_end, k, v)
    n1 = FL[..., 0] * n0 + jnp.einsum("bhl,bhld->bhd", decay_to_end, k)
    return h, C1, n1


def mlstm_block(x, p, cfg: ModelConfig, state=None):
    """x: (B, S, d) -> (out, new_state). Chunkwise-parallel mLSTM."""
    qm, be = cfg.quant_mode, cfg.gemm_backend
    b, s, d = x.shape
    h_heads = cfg.n_heads
    dh = d // h_heads

    def heads(t):
        return t.reshape(b, s, h_heads, dh).transpose(0, 2, 1, 3).astype(jnp.float32)

    q = heads(linear(x, p["wq"], qm, be, tp="col")) * (dh ** -0.5)
    k = heads(linear(x, p["wk"], qm, be, tp="col")) * (dh ** -0.5)
    v = heads(linear(x, p["wv"], qm, be, tp="col"))
    logi = jax.nn.log_sigmoid(
        linear(x, p["w_igate"], qm, be).astype(jnp.float32)
    ).transpose(0, 2, 1)
    logf = jax.nn.log_sigmoid(
        linear(x, p["w_fgate"], qm, be).astype(jnp.float32)
    ).transpose(0, 2, 1)

    L = min(_MLSTM_CHUNK, s)
    assert s % L == 0, f"seq {s} not divisible by mLSTM chunk {L}"
    nc = s // L

    def to_chunks(t):
        return t.reshape(b, h_heads, nc, L, *t.shape[3:]).transpose(2, 0, 1, 3, *range(4, t.ndim + 1))

    qc, kc, vc = to_chunks(q), to_chunks(k), to_chunks(v)
    fic = logi.reshape(b, h_heads, nc, L).transpose(2, 0, 1, 3)
    ffc = logf.reshape(b, h_heads, nc, L).transpose(2, 0, 1, 3)

    C0 = jnp.zeros((b, h_heads, dh, dh), jnp.float32) if state is None else state["C"]
    n0 = jnp.zeros((b, h_heads, dh), jnp.float32) if state is None else state["n"]

    def body(carry, xs):
        C, n = carry
        qi, ki, vi, lfi, lii = xs
        h, C1, n1 = _mlstm_chunk_math(qi, ki, vi, lfi, lii, C, n)
        return (C1, n1), h

    (C_f, n_f), hs = jax.lax.scan(body, (C0, n0), (qc, kc, vc, ffc, fic))
    h = hs.transpose(1, 2, 0, 3, 4).reshape(b, h_heads, s, dh)
    h = h.transpose(0, 2, 1, 3).reshape(b, s, d)
    o = jax.nn.sigmoid(linear(x, p["w_ogate"], qm, be, tp="col").astype(jnp.float32))
    out = linear((o * h).astype(x.dtype), p["w_out"], qm, be, tp="row")
    new_state = None if state is None else {"C": C_f, "n": n_f}
    return out, new_state


def mlstm_chunk(x, p, cfg: ModelConfig, state, n_valid):
    """Chunked prefill step: (1, C, d) chunk, first ``n_valid`` rows real,
    carrying the (C, n) matrix memory across chunks.

    Pad rows are neutralized in the gate domain — ``log f = 0`` (decay 1:
    cumulative products past the boundary are unchanged) and
    ``log i = -inf`` (zero injection: exp() zeroes every pad contribution
    to the intra-chunk D matrix and the chunk-boundary carry) — so the
    carried (C, n) equal the exact-length computation's."""
    qm, be = cfg.quant_mode, cfg.gemm_backend
    b, s, d = x.shape
    h_heads = cfg.n_heads
    dh = d // h_heads

    def heads(t):
        return t.reshape(b, s, h_heads, dh).transpose(0, 2, 1, 3).astype(jnp.float32)

    q = heads(linear(x, p["wq"], qm, be, tp="col")) * (dh ** -0.5)
    k = heads(linear(x, p["wk"], qm, be, tp="col")) * (dh ** -0.5)
    v = heads(linear(x, p["wv"], qm, be, tp="col"))
    logi = jax.nn.log_sigmoid(
        linear(x, p["w_igate"], qm, be).astype(jnp.float32)
    ).transpose(0, 2, 1)
    logf = jax.nn.log_sigmoid(
        linear(x, p["w_fgate"], qm, be).astype(jnp.float32)
    ).transpose(0, 2, 1)
    valid = (jnp.arange(s) < n_valid)[None, None, :]      # (1, 1, S)
    logf = jnp.where(valid, logf, 0.0)
    logi = jnp.where(valid, logi, -jnp.inf)

    L = min(_MLSTM_CHUNK, s)
    assert s % L == 0, f"seq {s} not divisible by mLSTM chunk {L}"
    nc = s // L

    def to_chunks(t):
        return t.reshape(b, h_heads, nc, L, *t.shape[3:]).transpose(2, 0, 1, 3, *range(4, t.ndim + 1))

    qc, kc, vc = to_chunks(q), to_chunks(k), to_chunks(v)
    fic = logi.reshape(b, h_heads, nc, L).transpose(2, 0, 1, 3)
    ffc = logf.reshape(b, h_heads, nc, L).transpose(2, 0, 1, 3)

    def body(carry, xs):
        C, n = carry
        qi, ki, vi, lfi, lii = xs
        h, C1, n1 = _mlstm_chunk_math(qi, ki, vi, lfi, lii, C, n)
        return (C1, n1), h

    (C_f, n_f), hs = jax.lax.scan(body, (state["C"], state["n"]),
                                  (qc, kc, vc, ffc, fic))
    h = hs.transpose(1, 2, 0, 3, 4).reshape(b, h_heads, s, dh)
    h = h.transpose(0, 2, 1, 3).reshape(b, s, d)
    o = jax.nn.sigmoid(linear(x, p["w_ogate"], qm, be, tp="col").astype(jnp.float32))
    out = linear((o * h).astype(x.dtype), p["w_out"], qm, be, tp="row")
    return out, {"C": C_f, "n": n_f}


def mlstm_decode(x_t, p, cfg: ModelConfig, state):
    """One step recurrent mLSTM. state: {"C": (B,H,dh,dh), "n": (B,H,dh)}."""
    qm, be = cfg.quant_mode, cfg.gemm_backend
    b, _, d = x_t.shape
    h_heads = cfg.n_heads
    dh = d // h_heads

    def heads(t):
        return t.reshape(b, h_heads, dh).astype(jnp.float32)

    q = heads(linear(x_t, p["wq"], qm, be, tp="col")[:, 0]) * (dh ** -0.5)
    k = heads(linear(x_t, p["wk"], qm, be, tp="col")[:, 0]) * (dh ** -0.5)
    v = heads(linear(x_t, p["wv"], qm, be, tp="col")[:, 0])
    i = jax.nn.sigmoid(linear(x_t, p["w_igate"], qm, be).astype(jnp.float32))[:, 0][..., None]
    f = jax.nn.sigmoid(linear(x_t, p["w_fgate"], qm, be).astype(jnp.float32))[:, 0][..., None]
    C = f[..., None] * state["C"] + (i * k)[..., :, None] * v[..., None, :]
    n = f * state["n"] + i * k
    num = jnp.einsum("bhd,bhde->bhe", q, C)
    den = jnp.maximum(jnp.abs(jnp.einsum("bhd,bhd->bh", q, n))[..., None], 1.0)
    h = (num / den).reshape(b, 1, d)
    o = jax.nn.sigmoid(linear(x_t, p["w_ogate"], qm, be, tp="col").astype(jnp.float32))
    out = linear((o * h).astype(x_t.dtype), p["w_out"], qm, be, tp="row")
    return out, {"C": C, "n": n}


# ---------------------------------------------------------------------------
# sLSTM (scalar-memory cell with recurrent block-diagonal weights)
# ---------------------------------------------------------------------------

def init_slstm(key, cfg: ModelConfig):
    d, h = cfg.d_model, cfg.n_heads
    dh = d // h
    ks = jax.random.split(key, 6)
    return {
        "w_zifo": init_linear(ks[0], d, 4 * d),
        "r_zifo": truncated_normal_init(ks[1], (4, h, dh, dh), scale=0.02),
        "w_out": init_linear(ks[2], d, d),
    }


def _slstm_step(p, cfg, carry, zifo_t):
    """carry: (c, n, h) each (B, H, dh); zifo_t: (B, 4, H, dh) pre-activations."""
    c, n, h = carry
    rec = jnp.einsum("bhd,ghde->bghe", h, p["r_zifo"].astype(jnp.float32))
    z_t, i_t, f_t, o_t = [zifo_t[:, g] + rec[:, g] for g in range(4)]
    z = jnp.tanh(z_t)
    i = jax.nn.sigmoid(i_t)
    f = jax.nn.sigmoid(f_t)
    o = jax.nn.sigmoid(o_t)
    c1 = f * c + i * z
    n1 = f * n + i
    h1 = o * c1 / jnp.maximum(n1, 1e-6)
    return (c1, n1, h1), h1


def slstm_block(x, p, cfg: ModelConfig, state=None):
    qm, be = cfg.quant_mode, cfg.gemm_backend
    b, s, d = x.shape
    hh = cfg.n_heads
    dh = d // hh
    zifo = linear(x, p["w_zifo"], qm, be, tp="col").astype(jnp.float32).reshape(b, s, 4, hh, dh)
    if state is None:
        zeros = jnp.zeros((b, hh, dh), jnp.float32)
        carry = (zeros, zeros, zeros)
    else:
        carry = (state["c"], state["n"], state["h"])

    def step(carry, z_t):
        return _slstm_step(p, cfg, carry, z_t)

    (c, n, h_last), hs = jax.lax.scan(step, carry, zifo.transpose(1, 0, 2, 3, 4))
    h = hs.transpose(1, 0, 2, 3).reshape(b, s, d).astype(x.dtype)
    out = linear(h, p["w_out"], qm, be, tp="row")
    new_state = None if state is None else {"c": c, "n": n, "h": h_last}
    return out, new_state


def slstm_chunk(x, p, cfg: ModelConfig, state, n_valid):
    """Chunked prefill step: (1, C, d) chunk, first ``n_valid`` rows real.
    The scan is inherently sequential, so masking is a per-step carry
    freeze: pad steps compute and discard, keeping the carried (c, n, h)
    bitwise the exact-length run's (identical op sequence on valid rows)."""
    qm, be = cfg.quant_mode, cfg.gemm_backend
    b, s, d = x.shape
    hh = cfg.n_heads
    dh = d // hh
    zifo = linear(x, p["w_zifo"], qm, be, tp="col").astype(jnp.float32).reshape(b, s, 4, hh, dh)
    carry0 = (state["c"], state["n"], state["h"])

    def step(carry, xs):
        z_t, t = xs
        stepped, h1 = _slstm_step(p, cfg, carry, z_t)
        keep = t < n_valid
        return tuple(jnp.where(keep, sc, c)
                     for sc, c in zip(stepped, carry)), h1

    (c, n, h_last), hs = jax.lax.scan(
        step, carry0,
        (zifo.transpose(1, 0, 2, 3, 4), jnp.arange(s, dtype=jnp.int32)))
    h = hs.transpose(1, 0, 2, 3).reshape(b, s, d).astype(x.dtype)
    out = linear(h, p["w_out"], qm, be, tp="row")
    return out, {"c": c, "n": n, "h": h_last}


def slstm_decode(x_t, p, cfg: ModelConfig, state):
    qm, be = cfg.quant_mode, cfg.gemm_backend
    b, _, d = x_t.shape
    hh = cfg.n_heads
    dh = d // hh
    zifo = linear(x_t, p["w_zifo"], qm, be, tp="col").astype(jnp.float32).reshape(b, 4, hh, dh)
    carry = (state["c"], state["n"], state["h"])
    (c, n, h), h_out = _slstm_step(p, cfg, carry, zifo)
    out = linear(h_out.reshape(b, 1, d).astype(x_t.dtype), p["w_out"], qm, be, tp="row")
    return out, {"c": c, "n": n, "h": h}
