"""Operations and bytes that decode attention needs for ONE new token of
one sequence in a model whose ``layer_types`` mix sliding-window and full
attention layers (``afmoe``), all layers, computed from shapes.

The token's query (Hq heads of Dh) attends over ``n`` cached positions a
layer: a ``full_attention`` layer over the whole context and itself; a
``sliding_attention`` layer over the last ``sliding_window`` of them,
ROUNDED TO THE PAGES the kernel's walk touches (``page_size`` positions a
page, 128 where the configuration gives none: the page the oldest
position lies in is read whole, so a window of 2,048 that starts inside
a page reads 17 pages' positions and not 16's; never more than the
context holds). Each of the Hkv key-value heads is read ONCE for the Hq /
Hkv query heads that share it.

ops:   per layer 2*n*Hq*Dh for Q.K^T and the same for P.V, over the
       positions the window KEEPS (a position the mask drops costs a
       byte and no useful operation).
bytes: per layer n positions of K and of V, Hkv*Dh elements each, in the
       served type; plus the query in and the output out (Hq*Dh each).
       The new token's own K/V write belongs to the writer kernel.

Of the published ``layer_types`` the entries below ``num_hidden_layers``
count.
"""

from typing import Any, Dict, Tuple

ITEMSIZE = {"bfloat16": 2, "float16": 2, "float32": 4}
PAGE = 128


def layers(cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(sliding-window layers, full layers) of the model as run."""
    kinds = list(cfg["layer_types"])[:int(cfg["num_hidden_layers"])]
    return (kinds.count("sliding_attention"), kinds.count("full_attention"))


def window_positions(context: int, cfg: Dict[str, Any]) -> Tuple[int, int]:
    """(positions a window layer keeps, positions the walk reads) for a
    query at position ``context``."""
    w, ps = int(cfg["sliding_window"]), int(cfg.get("page_size") or PAGE)
    n = context + 1
    kept = min(n, w)
    first = (n - kept) // ps * ps           # the oldest kept one's page
    return kept, n - first


def cost(context: int, cfg: Dict[str, Any]) -> Tuple[float, float]:
    hq = int(cfg["num_attention_heads"])
    hkv = int(cfg.get("num_key_value_heads") or hq)
    dh = int(cfg.get("head_dim") or cfg["hidden_size"] // hq)
    size = ITEMSIZE[cfg.get("torch_dtype") or "bfloat16"]
    n_win, n_full = layers(cfg)
    kept, read = window_positions(context, cfg)
    n = context + 1
    flops = 4.0 * hq * dh * (n_win * kept + n_full * n)
    bytes_ = (2.0 * hkv * dh * (n_win * read + n_full * n)
              + (n_win + n_full) * 2.0 * hq * dh) * size
    return flops, bytes_
