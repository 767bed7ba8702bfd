"""What the GQA families share on the program's side: the repo's
registered architecture with every published size set from the
configuration file, and the query-head order of the program's
attention."""

from __future__ import annotations


def base_config(conf: dict, **extra):
    """The program's ``ModelConfig``: the repo's architecture named by the
    file's ``program.arch``, with the published sizes of ``model`` (and
    ``extra``) set over it, so the file is what runs."""
    from repro.configs import get_config

    m = conf["model"]
    return get_config(conf["program"]["arch"]).with_(
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_dim=m["head_dim"], vocab_size=m["vocab_size"],
        rope_theta=float(m["rope_theta"]), norm_eps=float(m["rms_norm_eps"]),
        tie_embeddings=bool(m["tie_word_embeddings"]), act=m["hidden_act"],
        d_ff=m["intermediate_size"], **extra)


def gqa_to_program(name: str, w, m: dict):
    """The program groups query head ``g * H_kv + h`` onto KV head ``h``;
    the published layout puts query head ``h * G + g`` there.  Permute
    ``wq``'s column blocks and ``wo``'s row blocks to match (the same
    conversion a checkpoint loader would need)."""
    hq, hkv, hd = (m["num_attention_heads"], m["num_key_value_heads"],
                   m["head_dim"])
    g = hq // hkv
    if name == "attn/wq":
        d = w.shape[0]
        return w.reshape(d, hkv, g, hd).transpose(0, 2, 1, 3).reshape(d, hq * hd)
    if name == "attn/wo":
        d = w.shape[1]
        return w.reshape(hkv, g, hd, d).transpose(1, 0, 2, 3).reshape(hq * hd, d)
    return w
