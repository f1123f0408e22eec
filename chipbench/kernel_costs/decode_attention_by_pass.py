"""``decode_attention.cost`` for a looped model: the layer stack runs
``total_ut_steps`` times a token and EVERY pass keeps and reads its own
keys and values, so a token's decode attention is that of
``num_hidden_layers x total_ut_steps`` layer-slots. A configuration
without the key runs its layers once and costs what ``decode_attention``
says.
"""

from typing import Any, Dict, Tuple

from chipbench.kernel_costs import decode_attention


def layer_slots(cfg: Dict[str, Any]) -> int:
    return int(cfg["num_hidden_layers"]) * int(cfg.get("total_ut_steps", 1))


def cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    return decode_attention.cost(
        context, {**cfg, "num_hidden_layers": layer_slots(cfg)})
