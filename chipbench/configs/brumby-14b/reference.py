"""Plain reference of Brumby-14B-Base: the body of
``chipbench/reference/power_retention_decoder.py`` (float32, ``highest``,
the ATTENTION form over the whole sequence in blocks of queries, nothing
imported from the program) read with this directory's ``config.json``:
power retention of degree 2 over 40 / 8 heads of 128 with one sigmoid
decay a key-value head in every layer, q and k normed a head and rotated
at theta 1e6, then a SwiGLU of 17,408; an untied head over 151,936.
"""

from chipbench.reference.power_retention_decoder import (  # noqa: F401
    embed, forward, layer, layer_kinds, logits, mm_f32, retention)
