"""Weights from the seed for the family with delta-rule linear-attention
layers between gated attention layers (``model_type`` ``solar_open2``):
``gqa_layers`` attend (grouped-query, no rotation, an output gate), every
other layer is a Kimi Delta Attention layer, and every layer routes over
``n_routed_experts x expert_share_chips`` experts of which it HOLDS
``n_routed_experts``, beside one shared expert.

Leaves carry the names the family's published code gives them (the
``fla`` layer ``KimiDeltaAttention`` under ``self_attn.``, DeepSeek-V3's
under ``mlp.``; no checkpoint is in the repository to hold them against)
and are stored [in, out]; a depthwise filter as [taps, channels], tap j
on the input ``taps - 1 - j`` positions back; ``mlp.experts.*`` with a
leading axis of the HELD experts, ``mlp.gate`` as wide as all the routed
ones. ``program_tree`` hands the program one stack a kind under its own
names, q | k | v and their three filters side by side in one matrix each
(``kda_qkv``, ``kda_conv_w``).

What is drawn how, and why:

- ``A_log = log uniform(0.05, 1)`` a head and ``dt_bias = uniform(-4,
  -0.5)`` a channel: with the decay's low-rank pair giving about a unit
  normal, a step's decay ``alpha = exp(-exp(A_log) softplus(. +
  dt_bias))`` runs from 0.999 (a slow channel of a slow head) to 0.3 and
  below (a fast channel at a large input), so a chunk of the prefill
  scan holds channels whose log-decay passes -30 and channels that
  hardly move;
- ``b_proj`` at 1/sqrt(hidden): beta = 2 sigmoid(.) spreads over about
  0.5-1.5 and is not pinned at 1;
- the selection bias 0.02 x normal, float32, not zero (a bias that is
  dropped, or that leaks into the weights, must change the answer; the
  latent family's reasoning), and router weights alike for every expert:
  the held ones get an eighth of the assignments in the mean.
"""

from __future__ import annotations

import concurrent.futures
import json
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from chipbench.weights import (norm_weight, root_key, scaled_normal,
                               served_dtype)


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    la = cfg["linear_attn_config"]
    held = int(cfg["n_routed_experts"])
    return {"D": int(cfg["hidden_size"]),
            "Fe": int(cfg["moe_intermediate_size"]),
            "H": int(cfg["num_attention_heads"]),
            "Hkv": int(cfg["num_key_value_heads"]),
            "Dh": int(cfg["head_dim"]),
            "Hk": int(la["num_heads"]), "Dk": int(la["head_dim"]),
            "K": int(la["short_conv_kernel_size"]),
            "E": held, "Er": held * int(cfg.get("expert_share_chips", 1)),
            "S": int(cfg["n_shared_experts"]), "V": int(cfg["vocab_size"])}


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    gqa = set(cfg["gqa_layers"])
    return [("attn" if i in gqa else "kda") + "+moe"
            for i in range(int(cfg["num_hidden_layers"]))]


def layer_params(cfg: Dict[str, Any], key: jax.Array, layer, kind: str
                 ) -> Dict[str, jax.Array]:
    """One layer's weights, as stored (traceable in ``layer``)."""
    m, dt = dims(cfg), served_dtype(cfg)
    D, Fe, E, Er = m["D"], m["Fe"], m["E"], m["Er"]
    k = jax.random.split(jax.random.fold_in(key, 1000 + layer), 32)
    lp = {"input_layernorm": norm_weight(k[0], (D,), dt),
          "post_attention_layernorm": norm_weight(k[1], (D,), dt)}
    if kind == "kda+moe":
        Hk, Dk, K = m["Hk"], m["Dk"], m["K"]
        I, R = Hk * Dk, Dk
        u = jax.random.uniform
        lp.update({
            "self_attn.q_proj": scaled_normal(k[2], (D, I), D, dt),
            "self_attn.k_proj": scaled_normal(k[3], (D, I), D, dt),
            "self_attn.v_proj": scaled_normal(k[4], (D, I), D, dt),
            "self_attn.q_conv1d": scaled_normal(k[5], (K, I), K, dt),
            "self_attn.k_conv1d": scaled_normal(k[6], (K, I), K, dt),
            "self_attn.v_conv1d": scaled_normal(k[7], (K, I), K, dt),
            "self_attn.f_a_proj": scaled_normal(k[8], (D, R), D, dt),
            "self_attn.f_b_proj": scaled_normal(k[9], (R, I), R, dt),
            "self_attn.dt_bias": u(k[10], (I,), jnp.float32, -4.0, -0.5),
            "self_attn.A_log": jnp.log(
                u(k[11], (Hk,), jnp.float32, 0.05, 1.0)),
            "self_attn.b_proj": scaled_normal(k[12], (D, Hk), D, dt),
            "self_attn.g_a_proj": scaled_normal(k[13], (D, R), D, dt),
            "self_attn.g_b_proj": scaled_normal(k[14], (R, I), R, dt),
            "self_attn.o_norm": norm_weight(k[15], (Dk,), dt),
            "self_attn.o_proj": scaled_normal(k[16], (I, D), I, dt)})
    elif kind == "attn+moe":
        H, Hkv, Dh = m["H"], m["Hkv"], m["Dh"]
        lp.update({
            "self_attn.q_proj": scaled_normal(k[2], (D, H * Dh), D, dt),
            "self_attn.k_proj": scaled_normal(k[3], (D, Hkv * Dh), D, dt),
            "self_attn.v_proj": scaled_normal(k[4], (D, Hkv * Dh), D, dt),
            "self_attn.g_proj": scaled_normal(k[5], (D, H * Dh), D, dt),
            "self_attn.o_proj":
                scaled_normal(k[6], (H * Dh, D), H * Dh, dt)})
    else:
        raise ValueError(f"no layer of kind {kind!r} in this family")
    Fs = m["S"] * Fe
    lp.update({
        "mlp.gate": scaled_normal(k[20], (D, Er), D, dt),
        "mlp.gate.e_score_correction_bias":
            0.02 * jax.random.normal(k[21], (Er,), jnp.float32),
        "mlp.experts.gate_proj": scaled_normal(k[22], (E, D, Fe), D, dt),
        "mlp.experts.up_proj": scaled_normal(k[23], (E, D, Fe), D, dt),
        "mlp.experts.down_proj": scaled_normal(k[24], (E, Fe, D), Fe, dt),
        "mlp.shared_experts.gate_proj":
            scaled_normal(k[25], (D, Fs), D, dt),
        "mlp.shared_experts.up_proj": scaled_normal(k[26], (D, Fs), D, dt),
        "mlp.shared_experts.down_proj":
            scaled_normal(k[27], (Fs, D), Fs, dt)})
    return lp


# Compiled makers of the embedding and head that ``program_tree`` has
# built in this process, by configuration (the latent family's finding:
# the reference check asks for the same leaves again after the window).
_HEAD_MAKERS: Dict[str, Any] = {}


def _head(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, jax.Array]:
    m, dt = dims(cfg), served_dtype(cfg)
    k = jax.random.split(jax.random.fold_in(key, 7), 3)
    return {"embed": scaled_normal(k[0], (m["V"], m["D"]), m["D"], dt),
            "final_norm": norm_weight(k[1], (m["D"],), dt),
            "lm_head": scaled_normal(k[2], (m["D"], m["V"]), m["D"], dt)}


def head_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, jax.Array]:
    """Embedding, final norm and the untied output head."""
    if cfg.get("tie_word_embeddings"):
        raise ValueError("this generator's head is not the embedding")
    made = _HEAD_MAKERS.get(json.dumps(cfg, sort_keys=True))
    return made(key) if made is not None \
        and not isinstance(key, jax.core.Tracer) else _head(cfg, key)


_PROGRAM_NAMES = {
    "input_layernorm": "input_norm", "post_attention_layernorm": "post_norm",
    "self_attn.f_a_proj": "kda_f_down", "self_attn.f_b_proj": "kda_f_up",
    "self_attn.dt_bias": "kda_dt_bias", "self_attn.A_log": "kda_a_log",
    "self_attn.b_proj": "kda_beta",
    "self_attn.g_a_proj": "kda_g_down", "self_attn.g_b_proj": "kda_g_up",
    "self_attn.o_norm": "kda_norm",
    "self_attn.g_proj": "attn_gate",
    "mlp.gate": "router",
    "mlp.gate.e_score_correction_bias": "router_bias",
    "mlp.experts.gate_proj": "gate_proj", "mlp.experts.up_proj": "up_proj",
    "mlp.experts.down_proj": "down_proj",
    "mlp.shared_experts.gate_proj": "shared_gate",
    "mlp.shared_experts.up_proj": "shared_up",
    "mlp.shared_experts.down_proj": "shared_down"}


def program_layer(lp: Dict[str, jax.Array], kind: str
                  ) -> Dict[str, jax.Array]:
    """A stored layer under the program's names: a delta-rule layer's q,
    k, v projections side by side in ``kda_qkv`` and their filters in
    ``kda_conv_w`` (the values are the published matrices')."""
    lp = dict(lp)
    out = {}
    if kind == "kda+moe":
        out["kda_qkv"] = jnp.concatenate(
            [lp.pop(f"self_attn.{n}_proj") for n in "qkv"], axis=1)
        out["kda_conv_w"] = jnp.concatenate(
            [lp.pop(f"self_attn.{n}_conv1d") for n in "qkv"], axis=1)
        out["kda_out"] = lp.pop("self_attn.o_proj")
    else:
        for n in "qkvo":
            out[f"{n}_proj"] = lp.pop(f"self_attn.{n}_proj")
    out.update({_PROGRAM_NAMES[n]: v for n, v in lp.items()})
    return out


def program_tree(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Every weight, born on the device in the served type: one stack a
    kind under ``stacks``, each in layer order."""
    kinds = layer_kinds(cfg)

    def stack(kind):
        # One jitted call a stack, so that only one layer's float32
        # draws are alive beside what is already made.
        at = jnp.asarray([i for i, k in enumerate(kinds) if k == kind],
                         jnp.int32)
        return jax.jit(lambda key: jax.lax.map(
            lambda i: program_layer(layer_params(cfg, key, i, kind), kind),
            at))

    key = root_key(seed)
    names = sorted(set(kinds))
    makers = [jax.jit(lambda key: _head(cfg, key)).lower(key)] + [
        stack(kind).lower(key) for kind in names]
    with concurrent.futures.ThreadPoolExecutor(len(makers)) as pool:
        head, *stacks = pool.map(lambda lo: lo.compile(), makers)
    _HEAD_MAKERS[json.dumps(cfg, sort_keys=True)] = head
    return {**head(key),
            "stacks": {kind: make(key) for kind, make in zip(names, stacks)}}
