"""Plain reference of LFM2-24B-A2B's language model: the body of
``chipbench/reference/conv_gqa_moe.py`` (float32, ``highest``, nothing
imported from the program) read with this directory's ``config.json``:
a gated 3-tap convolution or 32 query heads over 8 key-value heads of
64 (normed per head, rotated half against half at theta 1e6) by
``layer_types``; a SwiGLU of 11,776 in the first layer; in the others
the 4 best of 64 sigmoid-scored experts by score plus selection bias,
weighted by their scores over their sum plus 1e-6.
"""

from chipbench.reference.conv_gqa_moe import (  # noqa: F401
    embed, forward, layer, logits, mm_f32)
