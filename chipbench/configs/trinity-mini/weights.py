"""Weights of this configuration from the seed: the generator of the
family with sliding-window attention layers between full attention
layers (``chipbench/weight_families/swa_gqa_moe.py``) read with this
directory's ``config.json``.
"""

from chipbench.weight_families.swa_gqa_moe import (  # noqa: F401
    head_params, layer_kinds, layer_params, program_layer, program_tree)
