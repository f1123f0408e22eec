"""Weights of a dense grouped-query decoder (``MistralForCausalLM`` and
its kin), shared by every configuration of that family: each one's
``weights.py``, beside its ``config.json``, binds this module to it.

Leaves carry the names of the published checkpoints (``q_proj`` ...
``down_proj``, stored [in, out]); every layer is of one kind, and
``program_tree`` stacks them by layer, which is the layout
``models/transformer.py`` scans over.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from chipbench.weights import (norm_weight, root_key, scaled_normal,
                               served_dtype)


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    hq = int(cfg["num_attention_heads"])
    d = int(cfg["hidden_size"])
    return {"D": d, "F": int(cfg["intermediate_size"]), "Hq": hq,
            "Hkv": int(cfg.get("num_key_value_heads") or hq),
            "Dh": int(cfg.get("head_dim") or d // hq),
            "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"])}


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    return ["layer"] * dims(cfg)["L"]


def layer_params(cfg: Dict[str, Any], key: jax.Array, layer, kind: str
                 ) -> Dict[str, jax.Array]:
    """One decoder layer's weights, as stored (traceable in ``layer``)."""
    m, dt = dims(cfg), served_dtype(cfg)
    k = jax.random.split(jax.random.fold_in(key, 1000 + layer), 9)
    D, F, Hq, Hkv, Dh = m["D"], m["F"], m["Hq"], m["Hkv"], m["Dh"]
    return {
        "input_norm": norm_weight(k[0], (D,), dt),
        "q_proj": scaled_normal(k[1], (D, Hq * Dh), D, dt),
        "k_proj": scaled_normal(k[2], (D, Hkv * Dh), D, dt),
        "v_proj": scaled_normal(k[3], (D, Hkv * Dh), D, dt),
        "o_proj": scaled_normal(k[4], (Hq * Dh, D), Hq * Dh, dt),
        "post_norm": norm_weight(k[5], (D,), dt),
        "gate_proj": scaled_normal(k[6], (D, F), D, dt),
        "up_proj": scaled_normal(k[7], (D, F), D, dt),
        "down_proj": scaled_normal(k[8], (F, D), F, dt),
    }


def head_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, jax.Array]:
    """Embedding, final norm and output head, as stored."""
    m, dt = dims(cfg), served_dtype(cfg)
    k = jax.random.split(jax.random.fold_in(key, 7), 3)
    return {"embed": scaled_normal(k[0], (m["V"], m["D"]), m["D"], dt),
            "final_norm": norm_weight(k[1], (m["D"],), dt),
            "lm_head": scaled_normal(k[2], (m["D"], m["V"]), m["D"], dt)}


def program_tree(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Every weight, in ONE jitted call, born on the device in the served
    type and stacked by layer. ``lax.map`` makes the layers one after
    another, so the float32 draw of only one layer is ever alive."""
    if cfg.get("tie_word_embeddings"):
        raise ValueError("tied embeddings: this generator makes a head")
    L = dims(cfg)["L"]

    def make(key):
        layers = jax.lax.map(lambda i: layer_params(cfg, key, i, "layer"),
                             jnp.arange(L, dtype=jnp.int32))
        return {**head_params(cfg, key), "layers": layers}

    return jax.jit(make)(root_key(seed))
