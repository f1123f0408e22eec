"""Weights from the seed of a looped dense decoder (``model_type``
``ouro``): ONE stack of ``num_hidden_layers`` layers that the model runs
``total_ut_steps`` times, the final norm after every pass, and an exit
gate (hidden -> 1, with a bias).

Leaves are stored [in, out]; the four norms carry the published
checkpoint's names (``input_layernorm``, ``input_layernorm_2``,
``post_attention_layernorm``, ``post_attention_layernorm_2``).

``chipbench/check.py`` walks ``layer_kinds`` and makes layer ``i`` by
``layer_params``. For it the model is ``P x L`` layers: layer ``i`` draws
the leaves of ``i mod L`` (pass 2's layer 0 IS pass 1's), and the last
layer of every pass but the final is of kind ``pass_end``: it also
carries ``final_norm``, drawn exactly as ``head_params`` draws it, and the
reference ends that layer with it. ``program_tree`` hands the program the
``L`` layers once, stacked, under the program's names.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from chipbench.weights import (norm_weight, root_key, scaled_normal,
                               served_dtype)

# stored leaf -> the program's name (models/transformer.py: the four-norm
# block's post_norm is the norm on the attention's OUTPUT)
_PROGRAM_NAMES = {
    "input_layernorm": "input_norm", "input_layernorm_2": "post_norm",
    "post_attention_layernorm": "pre_ff_norm",
    "post_attention_layernorm_2": "post_ff_norm"}


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    hq = int(cfg["num_attention_heads"])
    d = int(cfg["hidden_size"])
    return {"D": d, "F": int(cfg["intermediate_size"]), "Hq": hq,
            "Hkv": int(cfg.get("num_key_value_heads") or hq),
            "Dh": int(cfg.get("head_dim") or d // hq),
            "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"]),
            "P": int(cfg["total_ut_steps"])}


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    m = dims(cfg)
    one = ["layer"] * (m["L"] - 1)
    return (one + ["pass_end"]) * (m["P"] - 1) + one + ["layer"]


def _head_keys(key: jax.Array):
    return jax.random.split(jax.random.fold_in(key, 7), 5)


def _final_norm(cfg: Dict[str, Any], key: jax.Array) -> jax.Array:
    return norm_weight(_head_keys(key)[1], (dims(cfg)["D"],),
                       served_dtype(cfg))


def layer_params(cfg: Dict[str, Any], key: jax.Array, layer, kind: str
                 ) -> Dict[str, jax.Array]:
    """Layer ``layer`` of the ``P x L``, as stored (traceable in
    ``layer``): the leaves of ``layer mod L``."""
    m, dt = dims(cfg), served_dtype(cfg)
    k = jax.random.split(jax.random.fold_in(key, 1000 + layer % m["L"]), 11)
    D, F, Hq, Hkv, Dh = m["D"], m["F"], m["Hq"], m["Hkv"], m["Dh"]
    lp = {
        "input_layernorm": norm_weight(k[0], (D,), dt),
        "q_proj": scaled_normal(k[1], (D, Hq * Dh), D, dt),
        "k_proj": scaled_normal(k[2], (D, Hkv * Dh), D, dt),
        "v_proj": scaled_normal(k[3], (D, Hkv * Dh), D, dt),
        "o_proj": scaled_normal(k[4], (Hq * Dh, D), Hq * Dh, dt),
        "input_layernorm_2": norm_weight(k[5], (D,), dt),
        "post_attention_layernorm": norm_weight(k[6], (D,), dt),
        "gate_proj": scaled_normal(k[7], (D, F), D, dt),
        "up_proj": scaled_normal(k[8], (D, F), D, dt),
        "down_proj": scaled_normal(k[9], (F, D), F, dt),
        "post_attention_layernorm_2": norm_weight(k[10], (D,), dt),
    }
    if kind == "pass_end":
        lp["final_norm"] = _final_norm(cfg, key)
    elif kind != "layer":
        raise ValueError(f"no layer of kind {kind!r}")
    return lp


def head_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, jax.Array]:
    """Embedding, final norm, output head and the exit gate, as stored.
    The gate's bias is drawn too (not zero): a bias that is dropped must
    change the exit probabilities."""
    m, dt = dims(cfg), served_dtype(cfg)
    k = _head_keys(key)
    return {"embed": scaled_normal(k[0], (m["V"], m["D"]), m["D"], dt),
            "final_norm": _final_norm(cfg, key),
            "lm_head": scaled_normal(k[2], (m["D"], m["V"]), m["D"], dt),
            "exit_gate_w": scaled_normal(k[3], (m["D"],), m["D"], dt),
            "exit_gate_b": (0.5 * jax.random.normal(k[4], (), jnp.float32)
                            ).astype(dt)}


def program_tree(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Every weight, in ONE jitted call, born on the device in the served
    type: the ``L`` layers stacked once (``lax.map``: one layer's float32
    draw alive at a time), whatever the pass count."""
    if cfg.get("tie_word_embeddings"):
        raise ValueError("tied embeddings: this generator makes a head")
    L = dims(cfg)["L"]

    def make(key):
        layers = jax.lax.map(
            lambda i: {_PROGRAM_NAMES.get(n, n): v for n, v in
                       layer_params(cfg, key, i, "layer").items()},
            jnp.arange(L, dtype=jnp.int32))
        head = head_params(cfg, key)
        return {"embed": head["embed"], "final_norm": head["final_norm"],
                "lm_head": head["lm_head"], "layers": layers,
                "exit_gate": {"w": head["exit_gate_w"],
                              "b": head["exit_gate_b"]}}

    return jax.jit(make)(root_key(seed))
