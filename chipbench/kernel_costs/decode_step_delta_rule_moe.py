"""Operations and bytes of WHOLE decode steps of a decoder with
delta-rule layers between gated attention layers over a held share of
routed experts (``solar_open2``), computed from shapes and from what
the program routed.

A step reads every layer's OWN matrices once (an attention layer's q, k,
v, o and output gate; a delta-rule layer's q, k, v, o, its two low-rank
pairs and beta; every layer's router and shared expert) and the held
slice of the output head once, whatever its rows. Of the held experts it
reads those that RECEIVED a row (``experts_touched``, the program's own
count on the device), three matrices each. Each row multiplies by all
the layers' own matrices and by the head, each ASSIGNMENT computed here
by one expert's three; a row reads and writes its matrix states
(``kda_decode_update``), reads the keys and values its attention layers
need (``decode_attention_by_gqa_layers``) and its embedding row. The
norms' weights, the filters, the per-head scalars and the new token's
own key, value and ring writes are left out (under 1 MB a step against
9 GB), as the other step costs leave them out.

``cost(steps, contexts, cfg, assignments, experts_touched)``: ``steps``
decode steps that between them decoded one row at each of ``contexts``
(the positions the row attends over before its own), computed
``assignments`` (row, expert) pairs and touched ``experts_touched``
experts, summed over layers and steps.
"""

from typing import Any, Dict, Iterable, Tuple

from chipbench.kernel_costs import (decode_attention,
                                    decode_attention_by_gqa_layers,
                                    kda_decode_update, moe_experts)

ITEMSIZE = decode_attention.ITEMSIZE


def own_weights(cfg: Dict[str, Any]) -> int:
    """Elements of all layers' own matrices (outside the routed experts)."""
    d, fe = int(cfg["hidden_size"]), int(cfg["moe_intermediate_size"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dh = int(cfg["head_dim"])
    la = cfg["linear_attn_config"]
    inner, rank = int(la["num_heads"]) * int(la["head_dim"]), \
        int(la["head_dim"])
    routed = int(cfg["n_routed_experts"]) \
        * int(cfg.get("expert_share_chips", 1))
    every = d * routed + int(cfg["n_shared_experts"]) * 3 * d * fe
    attention = 3 * d * hq * dh + 2 * d * hkv * dh      # q, gate, o; k, v
    delta = 4 * d * inner + 2 * (d * rank + rank * inner) \
        + d * int(la["num_heads"])
    n_attn = decode_attention_by_gqa_layers.attention_layers(cfg)
    n_delta = kda_decode_update.delta_layers(cfg)
    return n_attn * (attention + every) + n_delta * (delta + every)


def step_cost(cfg: Dict[str, Any]) -> Tuple[float, float]:
    """What one step costs whatever its rows and its routing: no
    operations, and the bytes of the weights it always walks."""
    size = ITEMSIZE[cfg.get("torch_dtype") or "bfloat16"]
    head = int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    return 0.0, (own_weights(cfg) + head) * float(size)


def row_cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    """One row of one step outside the routed experts: its
    multiplications by every own matrix and by the head, its attention
    over ``context`` positions, its state updates, and the bytes of its
    states, of the keys and values it reads and of its embedding row."""
    size = ITEMSIZE[cfg.get("torch_dtype") or "bfloat16"]
    d = int(cfg["hidden_size"])
    a_ops, a_bytes = decode_attention_by_gqa_layers.cost(context, cfg)
    s_ops, s_bytes = kda_decode_update.cost(context, cfg)
    ops = 2.0 * (own_weights(cfg) + d * int(cfg["vocab_size"])) \
        + a_ops + s_ops
    return ops, a_bytes + s_bytes + d * float(size)


def cost(steps: int, contexts: Iterable[int], cfg: Dict[str, Any],
         assignments: float = 0.0, experts_touched: float = 0.0
         ) -> Tuple[float, float]:
    ops, bytes_ = moe_experts.cost(assignments, experts_touched, cfg)
    bytes_ += steps * step_cost(cfg)[1]
    for n in contexts:
        o, b = row_cost(n, cfg)
        ops += o
        bytes_ += b
    return ops, bytes_
