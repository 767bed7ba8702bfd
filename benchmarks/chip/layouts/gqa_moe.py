"""The program's side of the ``gqa_moe`` family: the program's MoE with
its capacity factor from the file's ``program.moe_capacity_factor``, and
the leading dense layers (``first_k_dense_replace``, of width
``dense_intermediate_size``) as the program's ``first_k_dense`` layers of
width ``d_ff_dense``."""

from __future__ import annotations

from layouts.common import base_config, gqa_to_program

to_program = gqa_to_program


def model_config(conf: dict):
    from repro.configs.base import MoEConfig

    m = conf["model"]
    return base_config(conf, moe=MoEConfig(
        num_experts=m["num_local_experts"], top_k=m["num_experts_per_tok"],
        d_expert=m["intermediate_size"],
        capacity_factor=float(conf["program"]["moe_capacity_factor"]),
        first_k_dense=m.get("first_k_dense_replace", 0),
        d_ff_dense=m.get("dense_intermediate_size", 0)))
