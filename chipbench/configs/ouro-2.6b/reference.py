"""Plain reference of Ouro-2.6B's language model: the looped dense
decoder of ``chipbench/reference/looped_decoder.py`` (float32,
``highest``, nothing imported from the program) read with this
directory's ``config.json``: 48 layers run ``total_ut_steps`` = 4 times
over the same weights, the final norm after every pass, every position
through every pass (``early_exit_threshold`` 1).
"""

from chipbench.reference.looped_decoder import (  # noqa: F401
    embed, exit_probabilities, forward, forward_with_exits, layer, logits,
    mm_f32)
