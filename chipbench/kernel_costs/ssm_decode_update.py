"""Operations and bytes that a Mamba-2 mixer's one-token state update
needs for ONE new token of one sequence, all layers, computed from
shapes.

A layer keeps ``mamba_n_heads`` matrices of ``mamba_d_head`` x
``mamba_d_state`` in float32 (the configuration's ``assumed``: the state
is kept in float32). A step reads each and writes each:

bytes: per layer 2 x heads x head width x state x 4, plus the row's
       small operands in and its output out (decay and dt x of heads x
       head width, B and C of groups x state, y of heads x head width,
       float32).
ops:   per layer about 6 a state element: the decay's product, the outer
       product dt x (outer) B and its add, the product with C and its
       sum over the state.

``context`` is taken and ignored, so that the reader of the kernel
rooflines (``readers/decode_attn_roofline_share.py``) can call it: the
update costs the same at every position.
"""

from typing import Any, Dict, Tuple

STATE_ITEMSIZE = 4          # float32, whatever type the weights are served in
OPS_PER_ELEMENT = 6.0


def state_elements(cfg: Dict[str, Any]) -> int:
    """Elements of one layer's state of one sequence."""
    return (int(cfg["mamba_n_heads"]) * int(cfg["mamba_d_head"])
            * int(cfg["mamba_d_state"]))


def cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    del context
    layers = int(cfg["num_hidden_layers"])
    inner = int(cfg["mamba_n_heads"]) * int(cfg["mamba_d_head"])
    groups = int(cfg["mamba_n_groups"]) * int(cfg["mamba_d_state"])
    n = state_elements(cfg)
    small = 3 * inner + 2 * groups      # decay, dt x, y; B, C
    return (layers * OPS_PER_ELEMENT * n,
            layers * (2.0 * n + small) * STATE_ITEMSIZE)
