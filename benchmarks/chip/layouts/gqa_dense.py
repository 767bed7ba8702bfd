"""The program's side of the ``gqa_dense`` family."""

from __future__ import annotations

from layouts.common import base_config, gqa_to_program

to_program = gqa_to_program


def model_config(conf: dict):
    return base_config(conf)
