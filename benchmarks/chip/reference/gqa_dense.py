"""Reference family ``gqa_dense``: a dense GQA decoder with a SiLU-gated
MLP (Llama / Mistral), as the published configurations describe it.

A family file gives the layer's weights in the published layout
(``layer_specs``), the float32 layer (``layer``), and the logical sizes
the metrics are taken against (``layer_params``, ``kernel_gemms``).
Nothing here imports the program (``repro``).
"""

from __future__ import annotations

import jax
import jax.numpy as jnp

from chipbench.weights import LINEAR_STD
from reference import common as C


def layer_specs(m: dict) -> dict:
    d, f = m["hidden_size"], m["intermediate_size"]
    bf = jnp.bfloat16
    return {**C.gqa_specs(m),
            "mlp/w_gate": ((d, f), bf, LINEAR_STD, 0.0),
            "mlp/w_up": ((d, f), bf, LINEAR_STD, 0.0),
            "mlp/w_down": ((f, d), bf, LINEAR_STD, 0.0)}


def _mlp(h, w, m, quant):
    gate = jax.nn.silu(C.lin(h, w["mlp/w_gate"], quant))
    return C.lin(gate * C.lin(h, w["mlp/w_up"], quant), w["mlp/w_down"], quant)


def layer(x, w, m, quant):
    return C.gqa_layer(x, w, m, quant, _mlp)


def layer_params(m: dict) -> dict:
    """Matmul parameters of one layer: ``attn`` (the four projections) and
    ``ffn_active`` (what one token passes through: the gated MLP)."""
    return {"attn": C.gqa_params(m), "ffn_active": 3 * m["hidden_size"] * m["intermediate_size"]}


def kernel_gemms(m: dict) -> list[tuple[int, int]]:
    """(K, N) of one layer's plain linear GEMMs: attention and the MLP."""
    d, f = m["hidden_size"], m["intermediate_size"]
    return C.gqa_gemms(m) + [(d, f), (d, f), (f, d)]
