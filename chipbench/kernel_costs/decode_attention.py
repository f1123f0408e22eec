"""Operations and bytes that decode attention needs for ONE new token of
one sequence, all layers, computed from shapes.

The token's query (Hq heads of Dh) attends over ``n`` cached positions:
n = context for full attention, min(context, window) where the
configuration sets a sliding window, since nothing older can be attended.

ops:   per layer 2*n*Hq*Dh for Q.K^T and the same for P.V.
bytes: per layer n positions of K and of V, Hkv*Dh elements each, in the
       served type; plus the query in and the output out (Hq*Dh each).
       The new token's own K/V write belongs to the writer kernel.
"""

from typing import Any, Dict, Tuple

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    hq = int(cfg["num_attention_heads"])
    hkv = int(cfg.get("num_key_value_heads") or hq)
    dh = int(cfg.get("head_dim") or cfg["hidden_size"] // hq)
    layers = int(cfg["num_hidden_layers"])
    size = ITEMSIZE[cfg.get("torch_dtype") or "bfloat16"]
    window = int(cfg.get("sliding_window") or 0)
    n = min(context + 1, window) if window else context + 1
    flops = layers * 4.0 * n * hq * dh
    bytes_ = layers * (2.0 * n * hkv * dh + 2.0 * hq * dh) * size
    return flops, bytes_
