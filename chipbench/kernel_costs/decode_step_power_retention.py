"""Operations and bytes of WHOLE decode steps of a power-retention
decoder (``brumby``: Qwen3's block, a retention layer in place of
attention in EVERY layer), computed from shapes.

A step reads every layer's matrices once (q, k, v, o, the decay's
projection; the feed-forward's three) and the output head once, whatever
its rows. Each row multiplies by all of them, reads and writes its
states (``power_retention_decode_update``) and reads its embedding row.
There are NO keys and values: a row costs the same at every position.
The norms' weights and the new token's small operands are left out
(under 1 MB a step against 8 GB), as the other step costs leave them
out.

``cost(steps, contexts, cfg)``: ``steps`` decode steps that between them
decoded one row at each of ``contexts`` (the positions behind the row,
which move nothing here).
"""

from typing import Any, Dict, Iterable, Tuple

from chipbench.kernel_costs import (decode_attention,
                                    power_retention_decode_update)

ITEMSIZE = decode_attention.ITEMSIZE


def layer_weights(cfg: Dict[str, Any]) -> int:
    """Elements of one layer's matrices."""
    d, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dh = int(cfg["head_dim"])
    retention = d * hq * dh + 2 * d * hkv * dh + hq * dh * d + d * hkv
    return retention + 3 * d * f


def step_cost(cfg: Dict[str, Any]) -> Tuple[float, float]:
    """What one step costs whatever its rows: no operations, and the
    bytes of the weights it walks (every layer once, the head once)."""
    size = ITEMSIZE[cfg.get("torch_dtype") or "bfloat16"]
    head = int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    return 0.0, (int(cfg["num_hidden_layers"]) * layer_weights(cfg)
                 + head) * float(size)


def row_cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    """One row of one step: its multiplications by every matrix and by
    the head, its state update, and the bytes of its states and of its
    embedding row."""
    size = ITEMSIZE[cfg.get("torch_dtype") or "bfloat16"]
    d = int(cfg["hidden_size"])
    s_ops, s_bytes = power_retention_decode_update.cost(context, cfg)
    ops = 2.0 * (int(cfg["num_hidden_layers"]) * layer_weights(cfg)
                 + d * int(cfg["vocab_size"])) + s_ops
    return ops, s_bytes + d * float(size)


def cost(steps: int, contexts: Iterable[int], cfg: Dict[str, Any]
         ) -> Tuple[float, float]:
    ops, bytes_ = 0.0, steps * step_cost(cfg)[1]
    for n in contexts:
        o, b = row_cost(n, cfg)
        ops += o
        bytes_ += b
    return ops, bytes_
