"""Weights of this configuration from the seed: the generator of the
power-retention decoder
(``chipbench/weight_families/power_retention_decoder.py``) read with this
directory's ``config.json``.
"""

from chipbench.weight_families.power_retention_decoder import (  # noqa: F401
    head_params, layer_kinds, layer_params, program_layer, program_tree)
