"""Operations and bytes that latent (absorbed) decode attention needs for
ONE new token of one sequence, all layers, computed from shapes.

The cache holds one row of ``kv_lora_rank + qk_rope_head_dim`` values a
position a layer, which every head shares. The token's Hq absorbed
queries attend over ``n`` cached positions (its own included):

ops:   per layer and position 2 * Hq * (rank + rope) for the scores and
       2 * Hq * rank for the weighted sum of the latent rows.
bytes: per layer n rows of (rank + rope) elements in the served type,
       read ONCE (keys and values are the same rows); plus the absorbed
       queries in (Hq * (rank + rope)) and the latent outputs out
       (Hq * rank). The new token's own row write belongs to the writer.
"""

from typing import Any, Dict, Tuple

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    hq = int(cfg["num_attention_heads"])
    r, rope = int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"])
    layers = int(cfg["num_hidden_layers"])
    size = ITEMSIZE[cfg.get("torch_dtype") or "bfloat16"]
    n = context + 1
    flops = layers * 2.0 * n * hq * ((r + rope) + r)
    bytes_ = layers * (n * (r + rope) + hq * (r + rope) + hq * r) * size
    return flops, bytes_
