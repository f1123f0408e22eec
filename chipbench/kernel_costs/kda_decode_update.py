"""Operations and bytes that a delta-rule layer's one-token state update
(Kimi Delta Attention) needs for ONE new token of one sequence, all its
layers, computed from shapes.

A delta-rule layer keeps ``linear_attn_config.num_heads`` matrices of
``head_dim`` x ``head_dim`` in float32 (the configuration's ``assumed``:
the state is kept in float32). A step reads each and writes each:

bytes: per layer 2 x heads x head_dim x head_dim x 4, plus the row's
       small operands in and its output out (the decay, k, q and beta k
       a key channel; v and o a value channel; float32).
ops:   per layer about 8 a state element: the decay's product, the
       product with k and its sum over key channels, the rank-one
       correction's product and its add, the product with q and its sum
       (7), and the residual v - S'^T k and beta k among the operands.

The layers are those NOT in ``gqa_layers`` (below ``num_hidden_layers``).
``context`` is taken and ignored, so that the reader of the kernel
rooflines (``readers/decode_attn_roofline_share.py``) can call it: the
update costs the same at every position.
"""

from typing import Any, Dict, Tuple

STATE_ITEMSIZE = 4          # float32, whatever type the weights are served in
OPS_PER_ELEMENT = 8.0


def delta_layers(cfg: Dict[str, Any]) -> int:
    n = int(cfg["num_hidden_layers"])
    return n - sum(int(i) < n for i in cfg["gqa_layers"])


def state_elements(cfg: Dict[str, Any]) -> int:
    """Elements of one layer's state of one sequence."""
    la = cfg["linear_attn_config"]
    return int(la["num_heads"]) * int(la["head_dim"]) ** 2


def cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    del context
    la = cfg["linear_attn_config"]
    layers = delta_layers(cfg)
    inner = int(la["num_heads"]) * int(la["head_dim"])
    n = state_elements(cfg)
    small = 6 * inner                   # decay, k, q, beta k; v, o
    return (layers * OPS_PER_ELEMENT * n,
            layers * (2.0 * n + small) * STATE_ITEMSIZE)
