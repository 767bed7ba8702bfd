"""Reference family ``mla_moe``: multi-head latent attention with YaRN
rope over a stack of one (or more) leading dense layers and then MoE
layers, as DeepSeek-V2's published modelling code computes them.

Attention is the non-absorbed form: a direct query projection
(``q_lora_rank`` null) split per head into ``qk_nope_head_dim`` and
``qk_rope_head_dim`` columns; the shared latent ``kv_lora_rank`` (RMS
normed) and one rope key per token; the latent's up-projection to each
head's key (nope) and value columns; rope on the rope columns, published
interleaved in pairs (the published code's view and transpose before the
half rotation); YaRN inverse frequencies, cos and sin scaled by
``mscale(factor, mscale) / mscale(factor, mscale_all_dim)`` and the
softmax by ``qk_dim ** -0.5 * mscale(factor, mscale_all_dim) ** 2``.
The MoE: a softmax router over all ``router_experts``, the greedy top
``num_experts_per_tok``, renormalised only with ``norm_topk_prob``, times
``routed_scaling_factor``; this chip's share of it, the held experts
``[first_held_expert, first_held_expert + n_routed_experts)`` (the
configuration's ``n_routed_experts`` counts those held here, the router
keeps the published width), plus the shared experts as one SiLU-gated MLP
of ``n_shared_experts * moe_intermediate_size``.  What the other chips'
experts would add is left out, as the program leaves it out.

Each program leaf is drawn as its own part of the published tensors
(``chipbench.weights`` fills the program's tree leaf by leaf, by name):
the published ``kv_a_proj_with_mqa`` is ``[w_dkv | w_kr]``, ``kv_b_proj``
per head ``[w_uk | w_uv]``; this module assembles them and computes in the
published layout.  The layer reads the rope scaling from the model's flat
``rope_scaling_*`` keys (the reference decoder passes a layer no nested
group); ``layer_kinds`` holds them to the configuration's
``rope_scaling``.

Layer kinds ``dense`` and ``moe`` (the family interface:
``reference/__init__.py``).  Nothing here imports the program (``repro``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.weights import LINEAR_STD
from reference import common as C

TOKEN_BLOCK = 512
ROPE_SCALING_KEYS = ("factor", "original_max_position_embeddings", "beta_fast",
                     "beta_slow", "mscale", "mscale_all_dim")
NORM = (0.25 / math.sqrt(3.0), 1.0)       # norm scales: spread, offset


def layer_kinds(m: dict) -> list[str]:
    rs = m["rope_scaling"]
    for key in ROPE_SCALING_KEYS:
        if m[f"rope_scaling_{key}"] != rs[key]:
            raise ValueError(f"rope_scaling_{key} is {m[f'rope_scaling_{key}']}, "
                             f"rope_scaling.{key} {rs[key]}")
    lead = m["first_k_dense_replace"]
    return ["dense"] * lead + ["moe"] * (m["num_hidden_layers"] - lead)


def attn_dims(m: dict) -> tuple[int, int]:
    return m["qk_nope_head_dim"] + m["qk_rope_head_dim"], m["v_head_dim"]


def _attn_specs(m: dict) -> dict:
    d, h, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, v = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "norm1": ((d,), f32, *NORM),
        "norm2": ((d,), f32, *NORM),
        "attn/w_q": ((d, h * (nope + rope)), bf, LINEAR_STD, 0.0),
        "attn/w_dkv": ((d, r), bf, LINEAR_STD, 0.0),
        "attn/w_kr": ((d, rope), bf, LINEAR_STD, 0.0),
        "attn/kv_norm": ((r,), f32, *NORM),
        "attn/w_uk": ((r, h * nope), bf, LINEAR_STD, 0.0),
        "attn/w_uv": ((r, h * v), bf, LINEAR_STD, 0.0),
        "attn/wo": ((h * v, d), bf, LINEAR_STD, 0.0),
    }


def _shared_width(m: dict) -> int:
    return m["n_shared_experts"] * m["moe_intermediate_size"]


def layer_specs(m: dict, kind="moe") -> dict:
    d = m["hidden_size"]
    if kind == "dense":
        return {**_attn_specs(m), **C.mlp_specs(d, m["intermediate_size"])}
    e, f, s = m["n_routed_experts"], m["moe_intermediate_size"], _shared_width(m)
    bf = jnp.bfloat16
    return {**_attn_specs(m),
            "moe/router": ((d, m["router_experts"]), jnp.float32, LINEAR_STD, 0.0),
            "moe/experts_gate": ((e, d, f), bf, LINEAR_STD, 0.0),
            "moe/experts_up": ((e, d, f), bf, LINEAR_STD, 0.0),
            "moe/experts_down": ((e, f, d), bf, LINEAR_STD, 0.0),
            "moe/shared/w_gate": ((d, s), bf, LINEAR_STD, 0.0),
            "moe/shared/w_up": ((d, s), bf, LINEAR_STD, 0.0),
            "moe/shared/w_down": ((s, d), bf, LINEAR_STD, 0.0)}


# ---------------------------------------------------------------------------
# YaRN rope on interleaved pairs
# ---------------------------------------------------------------------------

def _mscale(factor: float, mscale: float) -> float:
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn(m: dict, dim: int):
    """(inverse frequencies (dim/2,), cos/sin factor, softmax scale
    factor) of the published YaRN rotary embedding."""
    base, factor = float(m["rope_theta"]), m["rope_scaling_factor"]
    orig = m["rope_scaling_original_max_position_embeddings"]

    def corr(rot):
        return dim * math.log(orig / (rot * 2 * math.pi)) / (2 * math.log(base))

    low = max(math.floor(corr(m["rope_scaling_beta_fast"])), 0)
    high = min(math.ceil(corr(m["rope_scaling_beta_slow"])), dim - 1)
    if low == high:
        high += 0.001
    extra = 1.0 / base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim)
    inter = 1.0 / (factor * base ** (jnp.arange(0, dim, 2, dtype=jnp.float32) / dim))
    mask = 1.0 - jnp.clip((jnp.arange(dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    inv = inter * (1 - mask) + extra * mask
    all_dim = _mscale(factor, m["rope_scaling_mscale_all_dim"])
    return inv, _mscale(factor, m["rope_scaling_mscale"]) / all_dim, all_dim ** 2


def _rope(x, pos, m):
    """x (S, H, dim), rope columns interleaved in pairs as published."""
    s, h, dim = x.shape
    inv, attn_factor, _ = _yarn(m, dim)
    x = x.reshape(s, h, dim // 2, 2).swapaxes(-1, -2).reshape(s, h, dim)
    ang = pos[:, None].astype(jnp.float32) * inv
    ang = jnp.concatenate([ang, ang], axis=-1)
    cos = (jnp.cos(ang) * attn_factor)[:, None, :]
    sin = (jnp.sin(ang) * attn_factor)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return x * cos + jnp.concatenate([-x2, x1], axis=-1) * sin


# ---------------------------------------------------------------------------
# The layer
# ---------------------------------------------------------------------------

def _attention(q, k, v, scale):
    """Causal attention, every query head on its own key head. q/k (S, H,
    Dqk), v (S, H, Dv) -> (S, H, Dv); query blocks bound the scores."""
    s, h, _ = q.shape
    qb = min(C.QUERY_BLOCK, s)
    kpos = jnp.arange(s)

    def block(args):
        qc, i = args
        sc = jnp.einsum("qhd,khd->hqk", qc, k, precision=C.HI) * scale
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        return jnp.einsum("hqk,khd->qhd", jax.nn.softmax(sc, axis=-1), v, precision=C.HI)

    out = jax.lax.map(block, (q.reshape(s // qb, qb, h, -1), jnp.arange(s // qb)))
    return out.reshape(s, h, v.shape[-1])


def _mla(h, w, m, quant):
    s = h.shape[0]
    nh, r = m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    eps = float(m["rms_norm_eps"])
    pos = jnp.arange(s)
    q = C.lin(h, w["attn/w_q"], quant).reshape(s, nh, nope + rope)
    q = jnp.concatenate([q[..., :nope], _rope(q[..., nope:], pos, m)], axis=-1)
    # kv_a_proj_with_mqa: the latent, then one rope key shared by the heads
    kv_a = C.lin(h, jnp.concatenate([w["attn/w_dkv"], w["attn/w_kr"]], axis=1), quant)
    latent = C.rmsnorm(kv_a[:, :r], w["attn/kv_norm"], eps)
    k_pe = _rope(kv_a[:, None, r:], pos, m)
    # kv_b_proj: per head, the key's nope columns then the value
    kv_b = jnp.concatenate([w["attn/w_uk"].reshape(r, nh, nope),
                            w["attn/w_uv"].reshape(r, nh, dv)], axis=-1).reshape(r, -1)
    kv = C.lin(latent, kv_b, quant).reshape(s, nh, nope + dv)
    k = jnp.concatenate([kv[..., :nope], jnp.broadcast_to(k_pe, (s, nh, rope))], axis=-1)
    scale = (nope + rope) ** -0.5 * _yarn(m, rope)[2]
    out = _attention(q, k, kv[..., nope:], scale).reshape(s, nh * dv)
    return C.lin(out, w["attn/wo"], quant)


def _shared(h, w, quant):
    gate = jax.nn.silu(C.lin(h, w["moe/shared/w_gate"], quant))
    return C.lin(gate * C.lin(h, w["moe/shared/w_up"], quant), w["moe/shared/w_down"], quant)


def _moe(h, w, m, quant):
    k, held, first = m["num_experts_per_tok"], m["n_routed_experts"], m["first_held_expert"]
    bits = C.QUANT[quant]
    probs = jax.nn.softmax(jnp.matmul(h, w["moe/router"], precision=C.HI), axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    if m["norm_topk_prob"]:
        topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    topw = topw * m["routed_scaling_factor"]
    gates = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], topi].set(topw)
    gates = gates[:, first:first + held]

    def ew(name):
        wt = w[name].astype(jnp.float32)
        return C.fq(wt, bits[1], 1) if bits else wt

    def block(args):
        hb, gb = args
        xin = C.fq(hb, bits[0], -1) if bits else hb
        a = jax.nn.silu(jnp.einsum("td,edf->tef", xin, ew("moe/experts_gate"), precision=C.HI))
        a = a * jnp.einsum("td,edf->tef", xin, ew("moe/experts_up"), precision=C.HI)
        if bits:
            a = C.fq(a, bits[0], -1)
        y = jnp.einsum("tef,efd->ted", a, ew("moe/experts_down"), precision=C.HI)
        return jnp.einsum("ted,te->td", y, gb, precision=C.HI)

    t = h.shape[0]
    tb = min(TOKEN_BLOCK, t)
    out = jax.lax.map(block, (h.reshape(t // tb, tb, -1), gates.reshape(t // tb, tb, -1)))
    return out.reshape(t, -1) + _shared(h, w, quant)


def layer(x, w, m, quant, kind="moe"):
    """One pre-norm decoder layer over a whole (padded) sequence,
    positions 0..S-1."""
    eps = float(m["rms_norm_eps"])
    x = x + _mla(C.rmsnorm(x, w["norm1"], eps), w, m, quant)
    ffn = C.mlp if kind == "dense" else _moe
    return x + ffn(C.rmsnorm(x, w["norm2"], eps), w, m, quant)


# ---------------------------------------------------------------------------
# Logical sizes
# ---------------------------------------------------------------------------

def _attn_params(m: dict) -> int:
    d, nh, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    return d * nh * (nope + rope) + d * (r + rope) + r * nh * (nope + dv) + nh * dv * d


def layer_params(m: dict, kind="moe") -> dict:
    """Matmul parameters a token passes through in one layer on this chip:
    ``attn`` and ``ffn_active`` (the router, the held experts' expected
    share of the top-k, ``k * held / router_experts`` of them, and the
    shared experts; or the dense layer's MLP)."""
    d = m["hidden_size"]
    if kind == "dense":
        return {"attn": _attn_params(m), "ffn_active": 3 * d * m["intermediate_size"]}
    e = m["router_experts"]
    held_k = m["num_experts_per_tok"] * m["n_routed_experts"] / e
    return {"attn": _attn_params(m),
            "ffn_active": d * e + (held_k * m["moe_intermediate_size"]
                                   + _shared_width(m)) * 3 * d}


def kernel_gemms(m: dict, kind="moe") -> list[tuple[int, int]]:
    """(K, N) of one layer's plain linear GEMMs on the decode path: the
    query, latent, rope-key and output projections, and the dense MLP or
    the shared experts (the latent up-projections run absorbed in decode,
    and the held experts' grouped GEMMs are not plain linears)."""
    d, nh, r = m["hidden_size"], m["num_attention_heads"], m["kv_lora_rank"]
    nope, rope, dv = m["qk_nope_head_dim"], m["qk_rope_head_dim"], m["v_head_dim"]
    attn = [(d, nh * (nope + rope)), (d, r), (d, rope), (nh * dv, d)]
    width = m["intermediate_size"] if kind == "dense" else _shared_width(m)
    return attn + C.mlp_gemms(d, width)
