"""Operations and bytes of WHOLE decode steps of a decoder with
sliding-window attention layers between full attention layers, dense
feed-forwards in its leading layers and a held share of routed experts
after them (``afmoe``), computed from shapes and from what the program
routed.

A step reads every layer's OWN matrices once (q, k, v, o and the output
gate; a dense layer's three feed-forward matrices; a sparse layer's
router and shared expert) and the held slice of the output head once,
whatever its rows. Of the held experts it reads those that RECEIVED a
row (``experts_touched``, the program's own count on the device), three
matrices each. Each row multiplies by all the layers' own matrices and
by the head, each ASSIGNMENT computed here by one expert's three; a row
reads the keys and values its layers need (``decode_attention_by_window``:
a full layer its whole context, a window layer its window's pages) and
its embedding row. The norms' weights, the selection bias and the new
token's own key and value writes are left out (under 1 MB a step against
10 GB), as the other step costs leave them out.

``cost(steps, contexts, cfg, assignments, experts_touched)``: ``steps``
decode steps that between them decoded one row at each of ``contexts``
(the positions the row attends over before its own), computed
``assignments`` (row, expert) pairs and touched ``experts_touched``
experts, summed over layers and steps.
"""

from typing import Any, Dict, Iterable, Tuple

from chipbench.kernel_costs import decode_attention_by_window, moe_experts

ITEMSIZE = decode_attention_by_window.ITEMSIZE


def own_weights(cfg: Dict[str, Any]) -> int:
    """Elements of all layers' own matrices (outside the routed experts)."""
    d, f = int(cfg["hidden_size"]), int(cfg["intermediate_size"])
    fe = int(cfg["moe_intermediate_size"])
    hq, hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    dh = int(cfg["head_dim"])
    n = int(cfg["num_hidden_layers"])
    n_dense = min(int(cfg.get("num_dense_layers", 0)), n)
    routed = int(cfg["num_experts"]) * int(cfg.get("expert_share_chips", 1))
    attention = 3 * d * hq * dh + 2 * d * hkv * dh      # q, gate, o; k, v
    sparse = d * routed + int(cfg.get("num_shared_experts", 1)) * 3 * d * fe
    return n * attention + n_dense * 3 * d * f + (n - n_dense) * sparse


def step_cost(cfg: Dict[str, Any]) -> Tuple[float, float]:
    """What one step costs whatever its rows and its routing: no
    operations, and the bytes of the weights it always walks."""
    size = ITEMSIZE[cfg.get("torch_dtype") or "bfloat16"]
    head = int(cfg["hidden_size"]) * int(cfg["vocab_size"])
    return 0.0, (own_weights(cfg) + head) * float(size)


def row_cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    """One row of one step outside the routed experts: its
    multiplications by every own matrix and by the head, its attention
    over ``context`` positions, and the bytes of the keys and values it
    reads and of its embedding row."""
    size = ITEMSIZE[cfg.get("torch_dtype") or "bfloat16"]
    d = int(cfg["hidden_size"])
    a_ops, a_bytes = decode_attention_by_window.cost(context, cfg)
    ops = 2.0 * (own_weights(cfg) + d * int(cfg["vocab_size"])) + a_ops
    return ops, a_bytes + d * float(size)


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
