"""Weights of this configuration from the seed: the generator of the
family with delta-rule layers between gated attention layers
(``chipbench/weight_families/kda_gqa_moe.py``) read with this
directory's ``config.json``.
"""

from chipbench.weight_families.kda_gqa_moe import (  # noqa: F401
    head_params, layer_kinds, layer_params, program_layer, program_tree)
