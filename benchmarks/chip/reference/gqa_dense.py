"""Reference family ``gqa_dense``: a dense GQA decoder with a SiLU-gated
MLP (Llama / Mistral), as the published configurations describe it.

One kind of layer throughout (the family interface: ``reference/__init__.py``).
Nothing here imports the program (``repro``).
"""

from __future__ import annotations

from reference import common as C


def layer_specs(m: dict, kind=None) -> dict:
    return {**C.gqa_specs(m), **C.mlp_specs(m["hidden_size"], m["intermediate_size"])}


def layer(x, w, m, quant, kind=None):
    return C.gqa_layer(x, w, m, quant, C.mlp)


def layer_params(m: dict, kind=None) -> dict:
    """Matmul parameters of one layer: ``attn`` (the four projections) and
    ``ffn_active`` (what one token passes through: the gated MLP)."""
    return {"attn": C.gqa_params(m), "ffn_active": 3 * m["hidden_size"] * m["intermediate_size"]}


def kernel_gemms(m: dict, kind=None) -> list[tuple[int, int]]:
    """(K, N) of one layer's plain linear GEMMs: attention and the MLP."""
    return C.gqa_gemms(m) + C.mlp_gemms(m["hidden_size"], m["intermediate_size"])
