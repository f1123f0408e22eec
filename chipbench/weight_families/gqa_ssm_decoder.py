"""Weights from the seed for the family with a Mamba-2 mixer beside
attention in every layer (``model_type`` ``falcon_h1``).

Leaves carry the published checkpoint's names and are stored [in, out]
(``mamba.in_proj`` the ONE matrix [hidden, z | xBC | dt];
``mamba.conv1d.weight`` as [taps, channels]). ``program_tree`` hands the
program what its loader (``runtime/checkpoint.py``
``_load_kinds_checkpoint``) makes of such a checkpoint: one stack of the
one kind ``mix+dense`` under the program's names, the projection's three
column blocks apart.

**The draws and the multipliers.** The family scales a dozen products by
published scalars (0.0078 on the logits, 0.011 on the keys, ...). Drawn
at 1/sqrt(fan_in) like every other family's, the scaled products would
vanish: attention uniform, logits flat, and a dropped or doubled
multiplier invisible. So a leaf whose product a multiplier ``m`` scales
is drawn at 1/(sqrt(fan_in) m), to the nearest power of two
(``chipbench/weights.py``): the scaled product has the spread an
unscaled family's has, as trained weights under such a parametrisation
have, and a multiplier applied wrongly moves the answer by its whole
factor. The mixer's own parameters are drawn where a trained mixer's
lie: ``A_log`` = log of uniform(1, 16), ``dt_bias`` uniform(-4.5, -1.5)
(a step of 0.01-0.2 after the softplus: heads that forget within a few
tokens beside heads that hold hundreds), ``D`` and the filter's bias not
zero.
"""

from __future__ import annotations

from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from chipbench.weights import (norm_weight, root_key, scaled_normal,
                               served_dtype)

KIND = "mix+dense"


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    G, N = int(cfg["mamba_n_groups"]), int(cfg["mamba_d_state"])
    I = int(cfg["mamba_d_ssm"])
    return {"D": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
            "Hq": int(cfg["num_attention_heads"]),
            "Hkv": int(cfg["num_key_value_heads"]),
            "Dh": int(cfg["head_dim"]), "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"]), "K": int(cfg["mamba_d_conv"]),
            "Hs": int(cfg["mamba_n_heads"]), "I": I, "GN": G * N,
            "C": I + 2 * G * N}


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    return [KIND] * dims(cfg)["L"]


def _scaled(key, shape, fan_in, multiplier, dt):
    """A leaf whose product ``multiplier`` scales: drawn so that the
    SCALED product has the spread 1/sqrt(fan_in) gives an unscaled one."""
    return scaled_normal(key, shape, fan_in * float(multiplier) ** 2, dt)


def layer_params(cfg: Dict[str, Any], key: jax.Array, layer, kind: str
                 ) -> Dict[str, jax.Array]:
    """One layer's weights, as stored (traceable in ``layer``)."""
    if kind != KIND:
        raise ValueError(f"no layer of kind {kind!r} in this family")
    m, dt = dims(cfg), served_dtype(cfg)
    D, F, Hq, Hkv, Dh = m["D"], m["F"], m["Hq"], m["Hkv"], m["Dh"]
    I, GN, Hs, K, C = m["I"], m["GN"], m["Hs"], m["K"], m["C"]
    k = jax.random.split(jax.random.fold_in(key, 1000 + layer), 24)
    a_in = float(cfg["attention_in_multiplier"])
    s_in = float(cfg["ssm_in_multiplier"])
    sm = [float(x) for x in cfg["ssm_multipliers"]]
    gate_m, down_m = (float(x) for x in cfg["mlp_multipliers"])
    in_proj = jnp.concatenate([
        _scaled(k[6 + j], (D, w), D, s_in * sm[j], dt)
        for j, w in enumerate((I, I, GN, GN, Hs))], axis=1)
    u = jax.random.uniform
    return {
        "input_layernorm": norm_weight(k[0], (D,), dt),
        "self_attn.q_proj": _scaled(k[1], (D, Hq * Dh), D, a_in, dt),
        "self_attn.k_proj": _scaled(
            k[2], (D, Hkv * Dh), D, a_in * float(cfg["key_multiplier"]), dt),
        "self_attn.v_proj": _scaled(k[3], (D, Hkv * Dh), D, a_in, dt),
        "self_attn.o_proj": _scaled(
            k[4], (Hq * Dh, D), Hq * Dh,
            float(cfg["attention_out_multiplier"]), dt),
        "mamba.in_proj": in_proj,
        "mamba.conv1d.weight": scaled_normal(k[11], (K, C), K, dt),
        "mamba.conv1d.bias":
            (0.125 * jax.random.normal(k[12], (C,), jnp.float32)).astype(dt),
        "mamba.dt_bias": u(k[13], (Hs,), jnp.float32, -4.5, -1.5),
        "mamba.A_log": jnp.log(u(k[14], (Hs,), jnp.float32, 1.0, 16.0)),
        "mamba.D": 1.0 + 0.125 * jax.random.normal(k[15], (Hs,), jnp.float32),
        "mamba.norm": norm_weight(k[16], (I,), dt),
        "mamba.out_proj": _scaled(
            k[17], (I, D), I, float(cfg["ssm_out_multiplier"]), dt),
        "pre_ff_layernorm": norm_weight(k[18], (D,), dt),
        "feed_forward.gate_proj": _scaled(k[19], (D, F), D, gate_m, dt),
        "feed_forward.up_proj": scaled_normal(k[20], (D, F), D, dt),
        "feed_forward.down_proj": _scaled(k[21], (F, D), F, down_m, dt),
    }


def head_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, jax.Array]:
    """Embedding, final norm and the (untied) output head, as stored."""
    if cfg.get("tie_word_embeddings"):
        raise ValueError("this generator makes a head of its own")
    m, dt = dims(cfg), served_dtype(cfg)
    k = jax.random.split(jax.random.fold_in(key, 7), 3)
    return {"embed": _scaled(k[0], (m["V"], m["D"]), m["D"],
                             float(cfg["embedding_multiplier"]), dt),
            "final_norm": norm_weight(k[1], (m["D"],), dt),
            "lm_head": _scaled(k[2], (m["D"], m["V"]), m["D"],
                               float(cfg["lm_head_multiplier"]), dt)}


_PROGRAM_NAMES = {
    "input_layernorm": "input_norm", "pre_ff_layernorm": "post_norm",
    "self_attn.q_proj": "q_proj", "self_attn.k_proj": "k_proj",
    "self_attn.v_proj": "v_proj", "self_attn.o_proj": "o_proj",
    "mamba.conv1d.weight": "ssm_conv_w", "mamba.conv1d.bias": "ssm_conv_b",
    "mamba.dt_bias": "ssm_dt_bias", "mamba.A_log": "ssm_a_log",
    "mamba.D": "ssm_d", "mamba.norm": "ssm_norm",
    "mamba.out_proj": "ssm_out",
    "feed_forward.gate_proj": "gate_proj", "feed_forward.up_proj": "up_proj",
    "feed_forward.down_proj": "down_proj"}


def program_layer(cfg: Dict[str, Any], lp: Dict[str, jax.Array]
                  ) -> Dict[str, jax.Array]:
    """A layer's leaves under the program's names, the input projection
    in its three column blocks z | xBC | dt."""
    m = dims(cfg)
    out = {_PROGRAM_NAMES[n]: v for n, v in lp.items()
           if n != "mamba.in_proj"}
    w = lp["mamba.in_proj"]
    out.update(ssm_in_z=w[:, :m["I"]], ssm_in_xbc=w[:, m["I"]:m["I"] + m["C"]],
               ssm_in_dt=w[:, m["I"] + m["C"]:])
    return out


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
