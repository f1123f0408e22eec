"""Plain reference of Solar-Open2-250B's language model: the body of
``chipbench/reference/kda_gqa_moe.py`` (float32, ``highest``, the delta
rule token by token, nothing imported from the program) read with this
directory's ``config.json``: one gated grouped-query attention layer
(64 / 8 heads of 128, no rotation) and three Kimi Delta Attention layers
(64 heads of 128 x 128, a 4-tap filter) a period, each over a router of
320 experts, 8 a token, of which this chip holds 40, and one shared
expert.
"""

from chipbench.reference.kda_gqa_moe import (  # noqa: F401
    attention, delta_attention, embed, experts, forward, gate_map, layer,
    layer_kinds, logits, mm_f32)
