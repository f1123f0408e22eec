"""Plain reference of Trinity-Mini's language model: the body of
``chipbench/reference/swa_gqa_moe.py`` (float32, ``highest``, the whole
sequence under a mask for the window, nothing imported from the program)
read with this directory's ``config.json``: 24 sliding-window layers
(2,048 positions, rotated) and 8 full layers (not rotated) of 32 / 4
heads of 128 with q and k normed a head and a gated output, four norms a
layer, two dense layers of 6,144 and 30 layers over a router of 128
experts, 8 a token, of which this chip holds 16, and one shared expert.
"""

from chipbench.reference.swa_gqa_moe import (  # noqa: F401
    attention, embed, experts, forward, gate_map, layer, layer_kinds,
    logits, mm_f32)
