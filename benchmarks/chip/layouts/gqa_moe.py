"""The program's side of the ``gqa_moe`` family: the program's MoE with
its capacity factor from the file's ``program.moe_capacity_factor``."""

from __future__ import annotations

from chipbench import weights
from layouts.common import base_config, gqa_to_program
from reference import gqa_moe as ref

to_program = gqa_to_program


def model_config(conf: dict):
    from repro.configs.base import MoEConfig

    m = conf["model"]
    return base_config(conf, moe=MoEConfig(
        num_experts=m["num_local_experts"], top_k=m["num_experts_per_tok"],
        d_expert=m["intermediate_size"],
        capacity_factor=float(conf["program"]["moe_capacity_factor"])))


def program_params(m: dict, seed: int, shapes):
    return weights.program_params(m, seed, shapes, ref.layer_specs(m), to_program)
