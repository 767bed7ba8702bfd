"""Base layers: norms, embeddings, MLPs and the SPOGA quantized linear.

Functional style: ``init_*`` build param dicts, ``apply``-style functions
are pure and traceable (the dry-run lowers them with ShapeDtypeStructs).
Compute dtype is bf16 (params stored fp32, cast on use); integer modes
route through the :mod:`repro.backends` registry — quantize -> fused GEMM
-> dequant as one pipeline, with int32 accumulation (the paper's >=16-bit
accumulation requirement) and the dequantizing epilogue fused into the
kernel's single output write on the Pallas backends.
"""

from __future__ import annotations

import functools
import math

import jax
import jax.numpy as jnp

from repro.backends import quantized_linear

COMPUTE_DTYPE = jnp.bfloat16
# Weights are STORED bf16 (fp32 master copies live in the optimizer state):
# FSDP all-gathers and activation-matmuls move half the bytes, and the fp32
# path stays exact inside AdamW.  Norm scales / router / Λ stay fp32.
PARAM_DTYPE = jnp.bfloat16


def truncated_normal_init(key, shape, scale=0.02, dtype=PARAM_DTYPE):
    return (jax.random.truncated_normal(key, -2.0, 2.0, shape, jnp.float32) * scale).astype(dtype)


# ---------------------------------------------------------------------------
# Quantized linear: dynamic quantization + registry-selected GEMM backend
# forward, straight-through backward (QAT-compatible).
# ---------------------------------------------------------------------------

def _quantized_forward(x, w, mode, backend, tp):
    """x (..., K) fp, w (K, N) fp -> (..., N) fp via the backend pipeline."""
    return quantized_linear(x, w, mode, backend=backend, out_dtype=x.dtype,
                            tp=tp)


@functools.partial(jax.custom_vjp, nondiff_argnums=(2, 3, 4))
def _qmatmul_ste(x, w, mode: str, backend, tp):
    return _quantized_forward(x, w, mode, backend, tp)


def _qmatmul_fwd(x, w, mode, backend, tp):
    return _quantized_forward(x, w, mode, backend, tp), (x, w)


def _qmatmul_bwd(mode, backend, tp, res, g):
    # Straight-through: gradients as if the matmul were full-precision.
    x, w = res
    gf = g.astype(jnp.float32)
    dx = (gf @ w.astype(jnp.float32).T).astype(x.dtype)
    x2 = x.reshape(-1, x.shape[-1]).astype(jnp.float32)
    g2 = gf.reshape(-1, gf.shape[-1])
    dw = (x2.T @ g2).astype(w.dtype)
    return dx, dw


_qmatmul_ste.defvjp(_qmatmul_fwd, _qmatmul_bwd, symbolic_zeros=False)


def linear(x, w, quant_mode: str = "bf16", backend: str | None = None,
           tp: str | None = None):
    """The single matmul entry point for every model layer.

    ``backend`` is an optional GEMM-backend registry name (from
    ``ModelConfig.gemm_backend`` / ``--gemm-backend``); ``None`` defers to
    the registry's platform auto-selection.  ``tp`` ("col" | "row") is the
    weight's tensor-parallel layout under the Megatron rules of
    ``runtime/sharding.py``; a Pallas kernel under a mesh needs it to run
    on its own shard (``shard/kernels.py``).
    """
    if quant_mode == "bf16":
        return jnp.einsum(
            "...k,kn->...n", x.astype(COMPUTE_DTYPE), w.astype(COMPUTE_DTYPE)
        )
    return _qmatmul_ste(x.astype(COMPUTE_DTYPE), w, quant_mode, backend, tp)


def init_linear(key, d_in, d_out, scale=0.02):
    return truncated_normal_init(key, (d_in, d_out), scale)


# ---------------------------------------------------------------------------
# Norms
# ---------------------------------------------------------------------------

def init_rmsnorm(d):
    return jnp.ones((d,), jnp.float32)


def rmsnorm(x, gamma, eps=1e-6):
    xf = x.astype(jnp.float32)
    var = jnp.mean(xf * xf, axis=-1, keepdims=True)
    return ((xf * jax.lax.rsqrt(var + eps)) * gamma).astype(x.dtype)


def init_layernorm(d):
    return {"gamma": jnp.ones((d,), jnp.float32), "beta": jnp.zeros((d,), jnp.float32)}


def layernorm(x, p, eps=1e-6):
    xf = x.astype(jnp.float32)
    mu = jnp.mean(xf, axis=-1, keepdims=True)
    var = jnp.var(xf, axis=-1, keepdims=True)
    return ((xf - mu) * jax.lax.rsqrt(var + eps) * p["gamma"] + p["beta"]).astype(x.dtype)


# ---------------------------------------------------------------------------
# MLPs
# ---------------------------------------------------------------------------

def _act(name):
    return {"silu": jax.nn.silu, "gelu": jax.nn.gelu, "relu": jax.nn.relu}[name]


def init_glu_mlp(key, d_model, d_ff):
    k1, k2, k3 = jax.random.split(key, 3)
    return {
        "w_gate": init_linear(k1, d_model, d_ff),
        "w_up": init_linear(k2, d_model, d_ff),
        "w_down": init_linear(k3, d_ff, d_model),
    }


def glu_mlp(x, p, act="silu", quant_mode="bf16", backend=None):
    g = _act(act)(linear(x, p["w_gate"], quant_mode, backend, tp="col"))
    u = linear(x, p["w_up"], quant_mode, backend, tp="col")
    return linear(g * u, p["w_down"], quant_mode, backend, tp="row")


def init_mlp(key, d_model, d_ff):
    k1, k2 = jax.random.split(key)
    return {"w_in": init_linear(k1, d_model, d_ff), "w_out": init_linear(k2, d_ff, d_model)}


def mlp(x, p, act="gelu", quant_mode="bf16", backend=None):
    return linear(
        _act(act)(linear(x, p["w_in"], quant_mode, backend, tp="col")),
        p["w_out"], quant_mode, backend, tp="row",
    )


# ---------------------------------------------------------------------------
# Embedding / unembedding
# ---------------------------------------------------------------------------

def init_embedding(key, vocab, d_model):
    return truncated_normal_init(
        key, (vocab, d_model), scale=1.0 / (d_model ** 0.5), dtype=PARAM_DTYPE
    )


def embed(tokens, table):
    return jnp.take(table, tokens, axis=0).astype(COMPUTE_DTYPE)


def unembed(x, table, quant_mode="bf16"):
    # Output head kept in bf16 even in quantized mode: the paper's INT8
    # accumulation rounds to 8-bit *between* layers; logits need full range.
    return jnp.einsum(
        "...d,vd->...v", x.astype(COMPUTE_DTYPE), table.astype(COMPUTE_DTYPE)
    ).astype(jnp.float32)


# ---------------------------------------------------------------------------
# Rotary embeddings
# ---------------------------------------------------------------------------

def yarn_mscale(factor: float, mscale: float = 1.0) -> float:
    """YaRN's attention temperature for a context extended ``factor``-fold."""
    return 1.0 if factor <= 1 else 0.1 * mscale * math.log(factor) + 1.0


def _yarn_correction_dim(rotations, head_dim, theta, positions):
    """The rotary dim that turns ``rotations`` times over ``positions``."""
    return (head_dim * math.log(positions / (rotations * 2 * math.pi))
            / (2 * math.log(theta)))


def rope_frequencies(head_dim, theta, scaling=None):
    """Inverse frequencies of the ``head_dim // 2`` rotary pairs; with YaRN
    ``scaling`` (``configs.base.RopeScaling``), blended from the original
    (fast dims) to the ``factor``-interpolated (slow dims) over a linear
    ramp between the dims that ``beta_fast`` and ``beta_slow`` rotations
    over the original context fix."""
    freqs = 1.0 / (theta ** (jnp.arange(0, head_dim, 2, dtype=jnp.float32) / head_dim))
    if scaling is None:
        return freqs
    orig = scaling.original_max_position_embeddings
    low = max(math.floor(_yarn_correction_dim(scaling.beta_fast, head_dim, theta, orig)), 0)
    high = min(math.ceil(_yarn_correction_dim(scaling.beta_slow, head_dim, theta, orig)),
               head_dim - 1)
    if low == high:
        high += 0.001
    ramp = jnp.clip((jnp.arange(head_dim // 2, dtype=jnp.float32) - low) / (high - low), 0, 1)
    return freqs / scaling.factor * ramp + freqs * (1 - ramp)


def rope_attention_factor(scaling=None) -> float:
    """The factor YaRN scales cos and sin by (1 without scaling)."""
    if scaling is None:
        return 1.0
    return (yarn_mscale(scaling.factor, scaling.mscale)
            / yarn_mscale(scaling.factor, scaling.mscale_all_dim))


def apply_rope(x, positions, theta=10_000.0, scaling=None):
    """x: (B, S, H, D) ; positions: (B, S) int32; ``scaling``: YaRN's
    ``RopeScaling`` or None (plain rope)."""
    d = x.shape[-1]
    freqs = rope_frequencies(d, theta, scaling)              # (D/2,)
    angles = positions[..., None].astype(jnp.float32) * freqs  # (B, S, D/2)
    cos = jnp.cos(angles)[:, :, None, :]
    sin = jnp.sin(angles)[:, :, None, :]
    if scaling is not None:
        mscale = rope_attention_factor(scaling)
        cos, sin = cos * mscale, sin * mscale
    x1, x2 = jnp.split(x.astype(jnp.float32), 2, axis=-1)
    out = jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], axis=-1)
    return out.astype(x.dtype)


# ---------------------------------------------------------------------------
# Causal depthwise 1D conv (Griffin / xLSTM temporal conv)
# ---------------------------------------------------------------------------

def init_conv1d(key, d, width):
    return truncated_normal_init(key, (width, d), scale=0.1)


def causal_conv1d(x, w):
    """x: (B, S, D), w: (W, D) depthwise causal convolution."""
    width = w.shape[0]
    xf = x.astype(jnp.float32)
    pad = jnp.pad(xf, ((0, 0), (width - 1, 0), (0, 0)))
    out = jnp.zeros_like(xf)
    for i in range(width):  # width is tiny (4); unrolled adds, fusable
        out = out + pad[:, i : i + x.shape[1], :] * w[i]
    return out.astype(x.dtype)


def conv1d_decode(x_t, conv_state, w):
    """Single-step conv: x_t (B, D), conv_state (B, W-1, D) -> (y_t, new_state)."""
    width = w.shape[0]
    xf = x_t.astype(jnp.float32)
    hist = jnp.concatenate([conv_state, xf[:, None, :]], axis=1)  # (B, W, D)
    y = jnp.einsum("bwd,wd->bd", hist, w)
    return y.astype(x_t.dtype), hist[:, 1:, :]
