"""Plain reference of Falcon-H1-34B-Instruct's language model: the body
of ``chipbench/reference/gqa_ssm_decoder.py`` (float32, ``highest``, the
recurrence token by token, nothing imported from the program) read with
this directory's ``config.json``: grouped-query attention (20 / 4 heads
of 128) and a Mamba-2 mixer (32 heads of 128 over a state of 256, 2
groups, a 4-tap filter) on the same normed input in every layer, then a
SwiGLU of 21,504; the family's twelve multipliers where the published
code applies them.
"""

from chipbench.reference.gqa_ssm_decoder import (  # noqa: F401
    embed, forward, layer, layer_kinds, logits, mixer, mm_f32)
