"""Latent attention's share of its roofline over the traced slice: the
latent rows of every served lane's context (the keys each decoded token
attends, ``decode_keys``), every layer, ``kv_lora_rank +
qk_rope_head_dim`` bf16 values a row, at the HBM rate, over the device
time of the ops under the program's ``mla_attn`` scope (the latent row
write, the page gather, the absorbed products and the softmax).  None
where no op carries the scope."""

from chipbench import accounting

SCOPE = "mla_attn"


def latent_bytes(m: dict, lengths) -> float:
    """Least bytes one decode step's latent attention reads for one layer:
    each active lane's latent and rope-key rows (``lengths``: keys each
    lane attends), bf16."""
    return float(sum(lengths)) * (m["kv_lora_rank"] + m["qk_rope_head_dim"]) * 2


def read(rec):
    tr = rec["trace"]
    if tr is None:
        return None
    spent = sum(s for name, s, _ in tr["events"] if SCOPE in tr["scopes"].get(name, ""))
    m = rec["model"]
    nbytes = sum(m["num_hidden_layers"] * latent_bytes(m, st["decode_keys"])
                 for st in accounting.steps_in(rec, *rec["slice"]))
    return accounting.share(nbytes / rec["peaks"]["hbm_bytes_per_s"], spent)
