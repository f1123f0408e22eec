"""Operations and bytes that a power-retention layer's one-token state
update needs for ONE new token of one sequence, all layers, computed from
shapes.

A layer keeps, a key-value head, a matrix of ``rows`` x ``head_dim`` and
a normaliser of ``rows`` values in float32 (the configuration's
``assumed``), ``rows = head_dim (head_dim + 1) / 2``: the symmetric half
of the degree-2 products of a key's channels (8,256 at a head of 128).
That count is the ARCHITECTURE's, from ``head_dim`` alone: whatever
layout, padding or tiling a program keeps its state in (the program here
pads a head's matrix to 8,320 rows and its normaliser to 72 x 128; a
program that took the full 16,384-row product would read twice the
bytes) the share reads the same work. A step reads each and writes each:

bytes: per layer 2 x kv heads x (rows x head_dim + rows) x 4, plus the
       row's small operands in and its output out (k, v and the decay a
       key-value head, q and o a query head, float32). The expanded key
       and queries are made where they are used and cost no byte.
ops:   per layer, a key-value head: the expansion of k and of the
       group's queries (2 a product: the pair's product and its
       coefficient), the matrix's decay, outer product and add (3 a
       matrix element), the normaliser's (2 a row), and each query
       head's read of matrix and normaliser (2 a matrix element and 2 a
       row).

``context`` is taken and ignored, so that the reader of the kernel
rooflines (``readers/decode_attn_roofline_share.py``) can call it: the
update costs the same at every position.
"""

from typing import Any, Dict, Tuple

STATE_ITEMSIZE = 4          # float32, whatever type the weights are served in


def state_rows(cfg: Dict[str, Any]) -> int:
    """Rows of a key-value head's state: the symmetric products of its
    key channels."""
    d = int(cfg["head_dim"])
    return d * (d + 1) // 2


def state_elements(cfg: Dict[str, Any]) -> int:
    """Elements of one layer's state of one sequence: every key-value
    head's matrix and normaliser."""
    return int(cfg["num_key_value_heads"]) * state_rows(cfg) \
        * (int(cfg["head_dim"]) + 1)


def cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    del context
    layers = int(cfg["num_hidden_layers"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, rows = int(cfg["head_dim"]), state_rows(cfg)
    group = hq // hkv
    ops_head = (2.0 * rows * (1 + group)            # expand k and the q's
                + 3.0 * rows * d + 2.0 * rows       # advance S and z
                + group * (2.0 * rows * d + 2.0 * rows))    # the reads
    small = (2 * hkv + 2 * hq) * d + hkv            # k, v, q, o; the decay
    return (layers * hkv * ops_head,
            layers * (2.0 * state_elements(cfg) + small) * STATE_ITEMSIZE)
