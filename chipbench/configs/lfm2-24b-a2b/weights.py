"""Weights of this configuration from the seed: the generator of the
family whose layers differ in kind
(``chipbench/weight_families/conv_gqa_moe.py``) read with this
directory's ``config.json``: one convolution layer with a dense
feed-forward, then two periods of an attention layer and three
convolution layers over 64 routed experts each, all of them held here.
"""

from chipbench.weight_families.conv_gqa_moe import (  # noqa: F401
    head_params, layer_kinds, layer_params, program_tree)
