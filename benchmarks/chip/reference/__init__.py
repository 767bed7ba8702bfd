"""The plain float32 reference the benchmark's ``correct`` is decided
against (see ``decoder.py``).

A family file (``reference/<family>.py``) gives, for one layer of a given
kind, its weights in the published layout (``layer_specs(m, kind)``), the
float32 layer (``layer(x, w, m, quant, kind)``) and the logical sizes the
metrics are taken against (``layer_params(m, kind)``,
``kernel_gemms(m, kind)``).  It may give ``layer_kinds(m)``, one kind per
layer, for a stack whose layers differ (a leading dense layer, a layer
pattern); a family without it is one kind (``None``) throughout.  It may
give ``attn_dims(m)``, the (query-key, value) width of an attention head,
where the two differ from ``head_dim``.
"""


def layer_kinds(fam, m: dict) -> list:
    """One kind per layer of the family module ``fam`` for model ``m``."""
    kinds = getattr(fam, "layer_kinds", None)
    out = list(kinds(m)) if kinds else [None] * m["num_hidden_layers"]
    if len(out) != m["num_hidden_layers"]:
        raise ValueError(f"{fam.__name__}: {len(out)} layer kinds for "
                         f"{m['num_hidden_layers']} layers")
    return out


def attn_dims(fam, m: dict) -> tuple[int, int]:
    """(query-key, value) width of one attention head."""
    dims = getattr(fam, "attn_dims", None)
    return tuple(dims(m)) if dims else (m["head_dim"], m["head_dim"])
