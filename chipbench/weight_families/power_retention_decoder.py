"""Weights from the seed for the power-retention decoder (``model_type``
``brumby``): Qwen3's block with a retention layer's decay beside the
attention's projections.

Leaves carry the checkpoint's names (Qwen3's; ``self_attn.g_proj`` for
the decay: assumed, ``meta.json``) and are stored [in, out].
``program_tree`` hands the program what its loader (``runtime/
checkpoint.py`` ``_load_ret_checkpoint``) makes of such a checkpoint: one
stack of the one kind ``ret+dense`` under the program's names.

**The decay.** ``gamma = sigmoid(h W_g)`` is a bias-free linear of a
NORMED input: drawn like any other leaf its logit has mean zero over
tokens, half the tokens would halve the state, and nothing older than a
few positions would reach a query: a wrong decay, a snapshot restored
from the wrong page or a state advanced twice would move no served
token. A trained model holds such a gate open through a direction its
residual stream keeps (the massive activations of every large decoder).
So the seeded model is given one: every row of the embedding is its
random part (unit spread) plus ONE fixed vector ``e0`` of signs (unit
spread too), and a head's column of ``W_g`` is ``a / D`` times ``e0``
plus a small random part, ``a`` uniform in (6, 11). ``e0``'s share of a
layer's normed input falls from 0.71 at layer 0 to about a half after
four layers' outputs are added (they carry none of it), so a head's mean
logit lies between about 3 and 7.8: ``gamma`` from about 0.95 to 0.9996,
a memory of twenty to a few thousand positions, and the random part
moves a token's logit by a standard deviation of 0.5. The same vector
reaches q, k, v and the head like any direction of the stream.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from chipbench.weights import (norm_weight, root_key, scaled_normal,
                               served_dtype)

KIND = "ret+dense"
GATE_MEAN = (6.0, 11.0)     # a head's `a`: its mean logit is a x e0's share
GATE_SPREAD = 0.5           # a token's logit about that mean


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    return {"D": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
            "Hq": int(cfg["num_attention_heads"]),
            "Hkv": int(cfg["num_key_value_heads"]),
            "Dh": int(cfg["head_dim"]), "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"])}


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    return [KIND] * dims(cfg)["L"]


def stream_direction(cfg: Dict[str, Any], key: jax.Array) -> jax.Array:
    """``e0`` [D]: the fixed vector of signs every embedding row carries
    and every decay reads, float32."""
    return jnp.where(jax.random.bernoulli(
        jax.random.fold_in(key, 11), 0.5, (dims(cfg)["D"],)), 1.0, -1.0)


def layer_params(cfg: Dict[str, Any], key: jax.Array, layer, kind: str
                 ) -> Dict[str, jax.Array]:
    """One layer's weights, as stored (traceable in ``layer``)."""
    if kind != KIND:
        raise ValueError(f"no layer of kind {kind!r} in this family")
    m, dt = dims(cfg), served_dtype(cfg)
    D, F, Hq, Hkv, Dh = m["D"], m["F"], m["Hq"], m["Hkv"], m["Dh"]
    k = jax.random.split(jax.random.fold_in(key, 1000 + layer), 14)
    a = jax.random.uniform(k[11], (Hkv,), jnp.float32, *GATE_MEAN)
    g_proj = stream_direction(cfg, key)[:, None] * (a / D)[None, :] \
        + GATE_SPREAD * D ** -0.5 * jax.random.normal(
            k[12], (D, Hkv), jnp.float32)
    return {
        "input_layernorm": norm_weight(k[0], (D,), dt),
        "self_attn.q_proj": scaled_normal(k[1], (D, Hq * Dh), D, dt),
        "self_attn.k_proj": scaled_normal(k[2], (D, Hkv * Dh), D, dt),
        "self_attn.v_proj": scaled_normal(k[3], (D, Hkv * Dh), D, dt),
        "self_attn.o_proj": scaled_normal(k[4], (Hq * Dh, D), Hq * Dh, dt),
        "self_attn.q_norm": norm_weight(k[5], (Dh,), dt),
        "self_attn.k_norm": norm_weight(k[6], (Dh,), dt),
        "self_attn.g_proj": g_proj.astype(dt),
        "post_attention_layernorm": norm_weight(k[7], (D,), dt),
        "mlp.gate_proj": scaled_normal(k[8], (D, F), D, dt),
        "mlp.up_proj": scaled_normal(k[9], (D, F), D, dt),
        "mlp.down_proj": scaled_normal(k[10], (F, D), F, dt),
    }


def head_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, jax.Array]:
    """Embedding (each row its random part plus ``e0``), final norm and
    the (untied) output head, as stored."""
    if cfg.get("tie_word_embeddings"):
        raise ValueError("this generator makes a head of its own")
    m, dt = dims(cfg), served_dtype(cfg)
    k = jax.random.split(jax.random.fold_in(key, 7), 3)
    embed = jax.random.normal(k[0], (m["V"], m["D"]), jnp.float32) \
        + stream_direction(cfg, key)[None, :]
    return {"embed": embed.astype(dt),
            "final_norm": norm_weight(k[1], (m["D"],), dt),
            "lm_head": scaled_normal(k[2], (m["D"], m["V"]), m["D"], dt)}


_PROGRAM_NAMES = {
    "input_layernorm": "input_norm",
    "post_attention_layernorm": "post_norm",
    "self_attn.q_proj": "q_proj", "self_attn.k_proj": "k_proj",
    "self_attn.v_proj": "v_proj", "self_attn.o_proj": "o_proj",
    "self_attn.q_norm": "q_norm", "self_attn.k_norm": "k_norm",
    "self_attn.g_proj": "ret_gate",
    "mlp.gate_proj": "gate_proj", "mlp.up_proj": "up_proj",
    "mlp.down_proj": "down_proj"}


def program_layer(cfg: Dict[str, Any], lp: Dict[str, jax.Array]
                  ) -> Dict[str, jax.Array]:
    """A layer's leaves under the program's names."""
    return {_PROGRAM_NAMES[n]: v for n, v in lp.items()}


def program_tree(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Every weight, in ONE jitted call, born on the device in the served
    type: the one stack under ``stacks``. ``lax.map`` makes the layers one
    after another, so that one layer's float32 draws are alive at a
    time."""
    L = dims(cfg)["L"]

    def make(key):
        stack = jax.lax.map(
            lambda i: program_layer(cfg, layer_params(cfg, key, i, KIND)),
            jnp.arange(L, dtype=jnp.int32))
        return {**head_params(cfg, key), "stacks": {KIND: stack}}

    return jax.jit(make)(root_key(seed))
