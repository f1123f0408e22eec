"""Plain reference of Mistral-7B-Instruct-v0.3's language model: the dense
grouped-query decoder of ``chipbench/reference/gqa_decoder.py`` (float32,
``highest``, nothing imported from the program) read with this
directory's ``config.json``, which declares ``sliding_window: null``:
every layer attends to the whole causal history.
"""

from chipbench.reference.gqa_decoder import (  # noqa: F401
    embed, forward, layer, logits, mm_f32)
