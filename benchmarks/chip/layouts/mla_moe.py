"""The program's side of the ``mla_moe`` family: the repo's registered
architecture with every published size set from the configuration file
(latent attention in every block, YaRN, the router's published width and
the experts held here), and the rope columns' order: the published
weights interleave each rope pair, the program rotates the two halves."""

from __future__ import annotations

import jax.numpy as jnp


def model_config(conf: dict):
    from repro.configs import get_config
    from repro.configs.base import MLAConfig, MoEConfig, RopeScaling

    m = conf["model"]
    if m["scoring_func"] != "softmax" or m["topk_method"] != "greedy":
        raise ValueError("the program routes by a greedy top-k of a softmax")
    if m["routed_scaling_factor"] != 1:
        raise ValueError("the program applies no routed scaling factor")
    rs = m["rope_scaling"]
    if rs["type"] != "yarn":
        raise ValueError(f"the program's rope scaling is YaRN, not {rs['type']!r}")
    return get_config(conf["program"]["arch"]).with_(
        n_layers=m["num_hidden_layers"], d_model=m["hidden_size"],
        n_heads=m["num_attention_heads"], n_kv_heads=m["num_key_value_heads"],
        head_dim=m["qk_nope_head_dim"] + m["qk_rope_head_dim"],
        vocab_size=m["vocab_size"], rope_theta=float(m["rope_theta"]),
        norm_eps=float(m["rms_norm_eps"]), tie_embeddings=bool(m["tie_word_embeddings"]),
        act=m["hidden_act"], d_ff=m["moe_intermediate_size"],
        rope_scaling=RopeScaling(
            factor=float(rs["factor"]),
            original_max_position_embeddings=rs["original_max_position_embeddings"],
            beta_fast=float(rs["beta_fast"]), beta_slow=float(rs["beta_slow"]),
            mscale=float(rs["mscale"]), mscale_all_dim=float(rs["mscale_all_dim"])),
        use_mla=True,
        mla=MLAConfig(q_lora_rank=m["q_lora_rank"], kv_lora_rank=m["kv_lora_rank"],
                      qk_nope_head_dim=m["qk_nope_head_dim"],
                      qk_rope_head_dim=m["qk_rope_head_dim"], v_head_dim=m["v_head_dim"]),
        moe=MoEConfig(
            num_experts=m["router_experts"], top_k=m["num_experts_per_tok"],
            num_shared_experts=m["n_shared_experts"], d_expert=m["moe_intermediate_size"],
            capacity_factor=float(conf["program"]["moe_capacity_factor"]),
            first_k_dense=m["first_k_dense_replace"], d_ff_dense=m["intermediate_size"],
            norm_topk_prob=bool(m["norm_topk_prob"]),
            n_held=m["n_routed_experts"], first_held=m["first_held_expert"]))


def _halves(rope: int):
    """Published rope column order -> the program's: the even members of
    the pairs, then the odd (the published code's view and transpose)."""
    return jnp.concatenate([jnp.arange(0, rope, 2), jnp.arange(1, rope, 2)])


def to_program(name: str, w, m: dict):
    rope = m["qk_rope_head_dim"]
    if name == "attn/w_q":
        d, nope = w.shape[0], m["qk_nope_head_dim"]
        q = w.reshape(d, m["num_attention_heads"], nope + rope)
        q = jnp.concatenate([q[..., :nope], q[..., nope:][..., _halves(rope)]], axis=-1)
        return q.reshape(w.shape)
    if name == "attn/w_kr":
        return w[:, _halves(rope)]
    return w
