"""Pieces the plain float32 reference families share: fake quantization
for the control, products at ``Precision.HIGHEST`` (on a TPU a float32
product otherwise runs in bfloat16 passes), RMSNorm, rotary embeddings on
the two halves of each head (Hugging Face's ``rotate_half``), and causal
GQA attention with query head ``j`` on KV head ``j // G``.

Nothing here imports the program (``repro``).
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp

from chipbench.weights import LINEAR_STD

HI = jax.lax.Precision.HIGHEST
QUERY_BLOCK = 256

# the control: (activation bits, weight bits) of every product the
# configuration runs in int8, one step lower
QUANT = {None: None, "w4a8": (8, 4)}


def fq(x, bits: int, axis):
    """Symmetric round-to-nearest fake quantization along ``axis``."""
    qmax = 2 ** (bits - 1) - 1
    s = jnp.maximum(jnp.max(jnp.abs(x), axis=axis, keepdims=True), 1e-8) / qmax
    return jnp.clip(jnp.round(x / s), -qmax, qmax) * s


def lin(x, w, quant, w_axis=0):
    w = w.astype(jnp.float32)
    bits = QUANT[quant]
    if bits is not None:
        x = fq(x, bits[0], -1)
        w = fq(w, bits[1], w_axis)
    return jnp.matmul(x, w, precision=HI)


def rmsnorm(x, g, eps):
    return x * jax.lax.rsqrt(jnp.mean(x * x, axis=-1, keepdims=True) + eps) * g


def rope(x, pos, theta):
    d = x.shape[-1]
    freqs = 1.0 / (theta ** (jnp.arange(0, d, 2, dtype=jnp.float32) / d))
    ang = pos[:, None].astype(jnp.float32) * freqs
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = jnp.split(x, 2, axis=-1)
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)


def attention(q, k, v):
    """Causal GQA. q (S, Hq, D), k/v (S, Hkv, D) -> (S, Hq, D); query
    blocks bound the score buffer."""
    s, hq, d = q.shape
    hkv = k.shape[1]
    g = hq // hkv
    qb = min(QUERY_BLOCK, s)
    qs = q.reshape(s // qb, qb, hkv, g, d)
    kpos = jnp.arange(s)

    def block(args):
        qc, i = args
        sc = jnp.einsum("qhgd,khd->hgqk", qc, k, precision=HI) / math.sqrt(d)
        qpos = i * qb + jnp.arange(qb)
        sc = jnp.where(kpos[None, :] <= qpos[:, None], sc, -jnp.inf)
        p = jax.nn.softmax(sc, axis=-1)
        return jnp.einsum("hgqk,khd->qhgd", p, v, precision=HI)

    out = jax.lax.map(block, (qs, jnp.arange(s // qb)))
    return out.reshape(s, hq, d)


def gqa_specs(m: dict) -> dict:
    """name -> (shape, dtype, std, offset) of the norms and attention
    projections of one layer, published layout."""
    d, hd = m["hidden_size"], m["head_dim"]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    bf, f32 = jnp.bfloat16, jnp.float32
    return {
        "norm1": ((d,), f32, 0.25 / math.sqrt(3.0), 1.0),
        "norm2": ((d,), f32, 0.25 / math.sqrt(3.0), 1.0),
        "attn/wq": ((d, hq * hd), bf, LINEAR_STD, 0.0),
        "attn/wk": ((d, hkv * hd), bf, LINEAR_STD, 0.0),
        "attn/wv": ((d, hkv * hd), bf, LINEAR_STD, 0.0),
        "attn/wo": ((hq * hd, d), bf, LINEAR_STD, 0.0),
    }


def mlp_specs(d: int, f: int) -> dict:
    """name -> spec of a SiLU-gated MLP of width ``f``, published layout."""
    bf = jnp.bfloat16
    return {"mlp/w_gate": ((d, f), bf, LINEAR_STD, 0.0),
            "mlp/w_up": ((d, f), bf, LINEAR_STD, 0.0),
            "mlp/w_down": ((f, d), bf, LINEAR_STD, 0.0)}


def mlp(h, w, m, quant):
    """The SiLU-gated MLP of ``mlp_specs``."""
    gate = jax.nn.silu(lin(h, w["mlp/w_gate"], quant))
    return lin(gate * lin(h, w["mlp/w_up"], quant), w["mlp/w_down"], quant)


def mlp_gemms(d: int, f: int) -> list[tuple[int, int]]:
    """(K, N) of the MLP's gate, up and down projections."""
    return [(d, f), (d, f), (f, d)]


def gqa_layer(x, w, m, quant, ffn):
    """One pre-norm decoder layer over a whole (padded) sequence,
    positions 0..S-1: GQA attention, then ``ffn(h, w, m, quant)``."""
    s = x.shape[0]
    hq, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    eps, theta = float(m["rms_norm_eps"]), float(m["rope_theta"])
    pos = jnp.arange(s)
    h = rmsnorm(x, w["norm1"], eps)
    q = rope(lin(h, w["attn/wq"], quant).reshape(s, hq, hd), pos, theta)
    k = rope(lin(h, w["attn/wk"], quant).reshape(s, hkv, hd), pos, theta)
    v = lin(h, w["attn/wv"], quant).reshape(s, hkv, hd)
    if QUANT[quant]:
        k, v = fq(k, 8, -1), fq(v, 8, -1)
    a = attention(q, k, v).reshape(s, hq * hd)
    x = x + lin(a, w["attn/wo"], quant)
    return x + ffn(rmsnorm(x, w["norm2"], eps), w, m, quant)


def gqa_params(m: dict) -> int:
    """Matmul parameters of one layer's four attention projections."""
    d, hd = m["hidden_size"], m["head_dim"]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return d * hq * hd * 2 + d * hkv * hd * 2


def gqa_gemms(m: dict) -> list[tuple[int, int]]:
    """(K, N) of one layer's attention projections."""
    d, hd = m["hidden_size"], m["head_dim"]
    hq, hkv = m["num_attention_heads"], m["num_key_value_heads"]
    return [(d, hq * hd), (d, hkv * hd), (d, hkv * hd), (hq * hd, d)]
