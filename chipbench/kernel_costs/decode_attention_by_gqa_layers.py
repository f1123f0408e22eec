"""``decode_attention.cost`` for a model in which only the layers listed
under ``gqa_layers`` attend (``solar_open2``): they keep keys and values
and read them in decode, the others (delta-rule layers, which keep a
matrix state) read none. Of the published list the entries below
``num_hidden_layers`` count: the file as run keeps the list whole and
cuts the depth.
"""

from typing import Any, Dict, Tuple

from chipbench.kernel_costs import decode_attention


def attention_layers(cfg: Dict[str, Any]) -> int:
    n = int(cfg["num_hidden_layers"])
    return sum(int(i) < n for i in cfg["gqa_layers"])


def cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    return decode_attention.cost(
        context, {**cfg, "num_hidden_layers": attention_layers(cfg)})
