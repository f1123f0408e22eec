"""Operations and bytes that the routed experts of sparse layers need for
what was routed, computed from counts: the same work whatever
implements it.

``assignments``: (row, expert) pairs computed; each is one SwiGLU expert
over one row: three products of hidden x expert width.
``experts_touched``: experts that received at least one row, summed over
layers: each one's three matrices are read once.

ops:   assignments * 3 * 2 * hidden * expert_width.
bytes: experts_touched * 3 * hidden * expert_width in the served type,
       plus each assignment's row in and out (hidden each) and its
       intermediate (expert width, written and read).
"""

from typing import Any, Dict, Tuple

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}


def cost(assignments: float, experts_touched: float, cfg: Dict[str, Any]
         ) -> Tuple[float, float]:
    d, f = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    size = ITEMSIZE[cfg.get("torch_dtype") or "bfloat16"]
    flops = assignments * 3.0 * 2.0 * d * f
    bytes_ = (experts_touched * 3.0 * d * f
              + assignments * (2.0 * d + 2.0 * f)) * size
    return flops, bytes_
