"""The benchmark's own weights, made on the device from ``--seed``.

The program is handed these (it makes none of its own), and the plain
reference regenerates the very same values layer by layer after the
window, so neither side takes anything the other has made. Values are
drawn in float32 and stored in the type the configuration serves
(bfloat16); the reference reads those stored values up into float32.

Leaves carry the names of the published checkpoints (``q_proj`` ...
``down_proj``, stored [in, out]); ``program_tree`` stacks them by layer,
which is the layout ``models/transformer.py`` scans over.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def root_key(seed: int) -> jax.Array:
    """A key from any whole number up to well past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    hq = int(cfg["num_attention_heads"])
    d = int(cfg["hidden_size"])
    return {"D": d, "F": int(cfg["intermediate_size"]), "Hq": hq,
            "Hkv": int(cfg.get("num_key_value_heads") or hq),
            "Dh": int(cfg.get("head_dim") or d // hq),
            "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"])}


def _dtype(cfg: Dict[str, Any]):
    return jnp.dtype(cfg.get("torch_dtype") or "bfloat16")


def _w(key, shape, fan_in, dtype):
    # The scale is the power of two nearest 1/sqrt(fan_in): scaling by a
    # power of two commutes with rounding, so the value is the same bit
    # for bit however the compiler folds it into the draw (a scale of
    # 1/sqrt(14336) was folded differently under ``lax.map`` and alone,
    # and one weight in some thousands then rounded the other way).
    scale = 2.0 ** round(math.log2(1.0 / math.sqrt(fan_in)))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def _norm(key, shape, dtype):
    # Not all ones: a norm weight that is dropped or applied twice must
    # change the answer.
    return (1.0 + 0.125 * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)


def layer_params(cfg: Dict[str, Any], key: jax.Array, layer
                 ) -> Dict[str, jax.Array]:
    """One decoder layer's weights, as stored (traceable in ``layer``)."""
    m, dt = dims(cfg), _dtype(cfg)
    k = jax.random.split(jax.random.fold_in(key, 1000 + layer), 9)
    D, F, Hq, Hkv, Dh = m["D"], m["F"], m["Hq"], m["Hkv"], m["Dh"]
    return {
        "input_norm": _norm(k[0], (D,), dt),
        "q_proj": _w(k[1], (D, Hq * Dh), D, dt),
        "k_proj": _w(k[2], (D, Hkv * Dh), D, dt),
        "v_proj": _w(k[3], (D, Hkv * Dh), D, dt),
        "o_proj": _w(k[4], (Hq * Dh, D), Hq * Dh, dt),
        "post_norm": _norm(k[5], (D,), dt),
        "gate_proj": _w(k[6], (D, F), D, dt),
        "up_proj": _w(k[7], (D, F), D, dt),
        "down_proj": _w(k[8], (F, D), F, dt),
    }


def head_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, jax.Array]:
    """Embedding, final norm and output head, as stored."""
    m, dt = dims(cfg), _dtype(cfg)
    k = jax.random.split(jax.random.fold_in(key, 7), 3)
    return {"embed": _w(k[0], (m["V"], m["D"]), m["D"], dt),
            "final_norm": _norm(k[1], (m["D"],), dt),
            "lm_head": _w(k[2], (m["D"], m["V"]), m["D"], dt)}


def program_tree(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Every weight, in ONE jitted call, born on the device in the served
    type and stacked by layer. ``lax.map`` makes the layers one after
    another, so the float32 draw of only one layer is ever alive."""
    if cfg.get("tie_word_embeddings"):
        raise ValueError("tied embeddings: this generator makes a head")
    L = dims(cfg)["L"]

    def make(key):
        layers = jax.lax.map(lambda i: layer_params(cfg, key, i),
                             jnp.arange(L, dtype=jnp.int32))
        return {**head_params(cfg, key), "layers": layers}

    return jax.jit(make)(root_key(seed))
