"""Operations and bytes of WHOLE decode steps of a decoder with a
Mamba-2 mixer beside attention in every layer (``falcon_h1``), computed
from shapes.

A step reads every layer's matrices once (attention's q, k, v, o; the
mixer's input projection [hidden, z | xBC | dt] and its output
projection; the feed-forward's three) and the output head once, whatever
its rows. Each row multiplies by all of them, reads and writes its
matrix states (``ssm_decode_update``), reads the keys and values its
attention needs (``decode_attention``) and its embedding row. The norms'
weights, the filter, the multipliers, the mixer's per-head scalars and
the new token's own key, value and tail writes are left out (under 1 MB
a step against 8 GB), as the other step costs leave them out.

``cost(steps, contexts, cfg)``: ``steps`` decode steps that between them
decoded one row at each of ``contexts`` (the positions the row attends
over before its own).
"""

from typing import Any, Dict, Iterable, Tuple

from chipbench.kernel_costs import decode_attention, ssm_decode_update

ITEMSIZE = decode_attention.ITEMSIZE


def layer_weights(cfg: Dict[str, Any]) -> int:
    """Elements of one layer's matrices."""
    d, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dh = int(cfg["head_dim"])
    inner = int(cfg["mamba_d_ssm"])
    conv = inner + 2 * int(cfg["mamba_n_groups"]) * int(cfg["mamba_d_state"])
    attention = d * hq * dh + 2 * d * hkv * dh + hq * dh * d
    mixer = d * (inner + conv + int(cfg["mamba_n_heads"])) + inner * d
    return attention + mixer + 3 * d * f


def step_cost(cfg: Dict[str, Any]) -> Tuple[float, float]:
    """What one step costs whatever its rows: no operations, and the
    bytes of the weights it walks (every layer once, the head once)."""
    size = ITEMSIZE[cfg.get("torch_dtype") or "bfloat16"]
    head = int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    return 0.0, (int(cfg["num_hidden_layers"]) * layer_weights(cfg)
                 + head) * float(size)


def row_cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    """One row of one step: its multiplications by every matrix and by
    the head, its attention over ``context`` positions, its state
    update, and the bytes of its states, of the keys and values it reads
    and of its embedding row."""
    size = ITEMSIZE[cfg.get("torch_dtype") or "bfloat16"]
    d = int(cfg["hidden_size"])
    a_ops, a_bytes = decode_attention.cost(context, cfg)
    s_ops, s_bytes = ssm_decode_update.cost(context, cfg)
    ops = 2.0 * (int(cfg["num_hidden_layers"]) * layer_weights(cfg)
                 + d * int(cfg["vocab_size"])) + a_ops + s_ops
    return ops, a_bytes + s_bytes + d * float(size)


def cost(steps: int, contexts: Iterable[int], cfg: Dict[str, Any]
         ) -> Tuple[float, float]:
    ops, bytes_ = 0.0, steps * step_cost(cfg)[1]
    for n in contexts:
        o, b = row_cost(n, cfg)
        ops += o
        bytes_ += b
    return ops, bytes_
