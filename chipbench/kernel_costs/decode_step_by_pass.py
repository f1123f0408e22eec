"""Operations and bytes of WHOLE decode steps of a dense decoder whose
layer stack runs ``total_ut_steps`` times over the same weights (1
without the key), computed from shapes.

A step reads every layer's weights once a PASS (they are one stack, but
a pass is a walk over all of it and nothing of it stays on the chip
between passes), the output head once, and for each row the keys and
values its attention needs (``decode_attention_by_pass``); it multiplies
each row by those weights. A row's embedding is counted; the norms'
weights and the exit gate (3 MB a step against 20 GB) are left out, and
so is the new token's own key and value write (16 KB a row a slot), as
the attention cost leaves it out.

``cost(steps, contexts, cfg)``: ``steps`` decode steps that between them
decoded one row at each of ``contexts`` (the positions the row attends
over before its own).
"""

from typing import Any, Dict, Iterable, Tuple

from chipbench.kernel_costs import decode_attention_by_pass

ITEMSIZE = decode_attention_by_pass.decode_attention.ITEMSIZE


def _dims(cfg: Dict[str, Any]):
    hq = int(cfg["num_attention_heads"])
    d = int(cfg["hidden_size"])
    return (d, int(cfg["intermediate_size"]), hq,
            int(cfg.get("num_key_value_heads") or hq),
            int(cfg.get("head_dim") or d // hq), int(cfg["vocab_size"]),
            int(cfg["num_hidden_layers"]), int(cfg.get("total_ut_steps", 1)),
            ITEMSIZE[cfg.get("torch_dtype") or "bfloat16"])


def layer_weights(cfg: Dict[str, Any]) -> int:
    """Elements of one layer's matrices: q, k, v, o and the three of the
    feed-forward."""
    d, f, hq, hkv, dh, *_ = _dims(cfg)
    return d * hq * dh + 2 * d * hkv * dh + hq * dh * d + 3 * d * f


def step_cost(cfg: Dict[str, Any]) -> Tuple[float, float]:
    """What one step costs whatever its rows: no operations, and the
    bytes of the weights it walks (the layers once a pass, the head
    once)."""
    d, _f, _hq, _hkv, _dh, v, layers, passes, size = _dims(cfg)
    return 0.0, (passes * layers * layer_weights(cfg) + d * v) * float(size)


def row_cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    """One row of one step: its multiplications by every matrix a pass
    and by the head, its attention over ``context`` positions, and the
    bytes of its embedding row and of the keys and values it reads."""
    d, _f, _hq, _hkv, _dh, v, layers, passes, size = _dims(cfg)
    a_ops, a_bytes = decode_attention_by_pass.cost(context, cfg)
    ops = 2.0 * (passes * layers * layer_weights(cfg) + d * v) + a_ops
    return ops, a_bytes + d * float(size)


def cost(steps: int, contexts: Iterable[int], cfg: Dict[str, Any]
         ) -> Tuple[float, float]:
    ops, bytes_ = 0.0, steps * step_cost(cfg)[1]
    for n in contexts:
        o, b = row_cost(n, cfg)
        ops += o
        bytes_ += b
    return ops, bytes_
