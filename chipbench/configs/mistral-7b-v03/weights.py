"""Weights of this configuration from the seed: the dense grouped-query
decoder's generator (``chipbench/weight_families/gqa_decoder.py``) read
with this directory's ``config.json``.
"""

from chipbench.weight_families.gqa_decoder import (  # noqa: F401
    head_params, layer_kinds, layer_params, program_tree)
