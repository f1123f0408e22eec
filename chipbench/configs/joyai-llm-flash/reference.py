"""Plain reference of JoyAI-LLM-Flash's language model: the
latent-attention, sparse-expert body of
``chipbench/reference/latent_moe.py`` (float32, ``highest``, nothing
imported from the program) read with this directory's ``config.json``:
32 heads over a 512 + 64 latent row, un-absorbed; a SwiGLU of 7168 in
layer 0; in the others the 8 best of 256 sigmoid-scored experts by score
plus selection bias (one group: no group limit), weighted by their
scores over their sum times 2.5, and one shared expert. The multi-token
module (``num_nextn_predict_layers``) is no part of the next-token
logits and of nothing here.
"""

from chipbench.reference.latent_moe import (  # noqa: F401
    embed, forward, layer, logits, mm_f32)
