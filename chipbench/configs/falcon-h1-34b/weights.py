"""Weights of this configuration from the seed: the generator of the
family with a mixer beside attention
(``chipbench/weight_families/gqa_ssm_decoder.py``) read with this
directory's ``config.json``.
"""

from chipbench.weight_families.gqa_ssm_decoder import (  # noqa: F401
    head_params, layer_kinds, layer_params, program_layer, program_tree)
