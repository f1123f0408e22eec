"""Weights of this configuration from the seed: the latent-attention,
sparse-expert family's generator
(``chipbench/weight_families/latent_moe.py``) read with this directory's
``config.json``: one dense layer, then sparse layers of 256 experts, all
of them held here.
"""

from chipbench.weight_families.latent_moe import (  # noqa: F401
    head_params, layer_kinds, layer_params, program_tree)
