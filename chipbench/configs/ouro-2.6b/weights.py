"""Weights of this configuration from the seed: the looped dense
decoder's generator (``chipbench/weight_families/looped_decoder.py``)
read with this directory's ``config.json``.
"""

from chipbench.weight_families.looped_decoder import (  # noqa: F401
    head_params, layer_kinds, layer_params, program_tree)
