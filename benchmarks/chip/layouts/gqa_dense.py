"""The program's side of the ``gqa_dense`` family."""

from __future__ import annotations

from chipbench import weights
from layouts.common import base_config, gqa_to_program
from reference import gqa_dense as ref

to_program = gqa_to_program


def model_config(conf: dict):
    return base_config(conf)


def program_params(m: dict, seed: int, shapes):
    return weights.program_params(m, seed, shapes, ref.layer_specs(m), to_program)
