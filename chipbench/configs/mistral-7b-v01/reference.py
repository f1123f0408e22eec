"""Plain reference of Mistral-7B-v0.1's language model: the dense
grouped-query decoder of ``chipbench/reference/gqa_decoder.py`` (float32,
``highest``, nothing imported from the program) read with this
directory's ``config.json``, which declares ``sliding_window: 4096``:
in every layer a query at position p attends to positions
p - 4095 .. p and to nothing older.
"""

from chipbench.reference.gqa_decoder import (  # noqa: F401
    embed, forward, layer, logits, mm_f32)
