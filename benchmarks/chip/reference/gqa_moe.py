"""Reference family ``gqa_moe``: GQA attention with a dropless top-k
mixture of SiLU-gated experts (Granite 3.0 MoE, Mixtral), as the
published configurations describe it: the softmax router's top
``num_experts_per_tok`` renormalised over the chosen experts, every
routed token kept.  Each expert's width is ``intermediate_size``.  The
first ``first_k_dense_replace`` layers (DeepSeekMoE's key; none if absent)
are dense: the same attention with a SiLU-gated MLP of width
``dense_intermediate_size`` (``intermediate_size`` if absent) in the
experts' place.

Layer kinds ``dense`` and ``moe`` (the family interface:
``reference/__init__.py``).  Nothing here imports the program (``repro``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.weights import LINEAR_STD
from reference import common as C

TOKEN_BLOCK = 512


def layer_kinds(m: dict) -> list[str]:
    lead = m.get("first_k_dense_replace", 0)
    return ["dense"] * lead + ["moe"] * (m["num_hidden_layers"] - lead)


def _dense_width(m: dict) -> int:
    return m.get("dense_intermediate_size", m["intermediate_size"])


def layer_specs(m: dict, kind="moe") -> dict:
    d, f, e = m["hidden_size"], m["intermediate_size"], m["num_local_experts"]
    if kind == "dense":
        return {**C.gqa_specs(m), **C.mlp_specs(d, _dense_width(m))}
    bf = jnp.bfloat16
    return {**C.gqa_specs(m),
            "moe/router": ((d, e), jnp.float32, LINEAR_STD, 0.0),
            "moe/experts_gate": ((e, d, f), bf, LINEAR_STD, 0.0),
            "moe/experts_up": ((e, d, f), bf, LINEAR_STD, 0.0),
            "moe/experts_down": ((e, f, d), bf, LINEAR_STD, 0.0)}


def _moe(h, w, m, quant):
    k = m["num_experts_per_tok"]
    bits = C.QUANT[quant]
    logits = jnp.matmul(h, w["moe/router"], precision=C.HI)
    probs = jax.nn.softmax(logits, axis=-1)
    topw, topi = jax.lax.top_k(probs, k)
    topw = topw / jnp.sum(topw, axis=-1, keepdims=True)
    gates = jnp.zeros_like(probs).at[jnp.arange(h.shape[0])[:, None], topi].set(topw)

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
    out = jax.lax.map(block, (h.reshape(t // tb, tb, -1),
                              gates.reshape(t // tb, tb, -1)))
    return out.reshape(t, -1)


def layer(x, w, m, quant, kind="moe"):
    return C.gqa_layer(x, w, m, quant, C.mlp if kind == "dense" else _moe)


def layer_params(m: dict, kind="moe") -> dict:
    """Matmul parameters of one layer: ``attn`` and ``ffn_active`` (per
    token: the router plus ``num_experts_per_tok`` experts, or the dense
    layer's MLP)."""
    d, f = m["hidden_size"], m["intermediate_size"]
    e, k = m["num_local_experts"], m["num_experts_per_tok"]
    if kind == "dense":
        return {"attn": C.gqa_params(m), "ffn_active": 3 * d * _dense_width(m)}
    return {"attn": C.gqa_params(m), "ffn_active": d * e + k * 3 * d * f}


def kernel_gemms(m: dict, kind="moe") -> list[tuple[int, int]]:
    """(K, N) of one layer's plain linear GEMMs: the attention
    projections, and a dense layer's MLP (the experts' grouped GEMMs are
    not plain linears)."""
    if kind == "dense":
        return C.gqa_gemms(m) + C.mlp_gemms(m["hidden_size"], _dense_width(m))
    return C.gqa_gemms(m)
