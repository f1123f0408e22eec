"""``decode_attention.cost`` for a model in which only some layers
attend: the layers whose ``layer_types`` entry is ``full_attention``
keep keys and values and read them in decode, the others (a gated short
convolution here) read none. A configuration without ``layer_types``
attends in every layer and costs what ``decode_attention`` says.
"""

from typing import Any, Dict, Tuple

from chipbench.kernel_costs import decode_attention


def attention_layers(cfg: Dict[str, Any]) -> int:
    types = cfg.get("layer_types")
    if types is None:
        return int(cfg["num_hidden_layers"])
    return sum(t == "full_attention" for t in types)


def cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    return decode_attention.cost(
        context, {**cfg, "num_hidden_layers": attention_layers(cfg)})
