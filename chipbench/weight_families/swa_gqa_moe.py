"""Weights from the seed for the family with sliding-window attention
layers between full attention layers (``model_type`` ``afmoe``): every
layer attends (grouped-query, q and k normed a head, an output gate)
under four norms, the first ``num_dense_layers`` layers keep a dense
SwiGLU and every later one routes over ``num_experts x
expert_share_chips`` experts of which it HOLDS ``num_experts``, beside
one shared expert.

Leaves carry the names the family's published code gives them
(``AfmoeAttention``'s ``q_proj`` ... ``o_proj``, ``gate_proj``,
``q_norm``, ``k_norm`` under ``self_attn.``; ``router.gate``,
``expert_bias``, ``experts.*`` and ``shared_experts.*`` under ``mlp.``;
the four ``*_layernorm``; no checkpoint is in the repository to hold
them against) and are stored [in, out]; ``mlp.experts.*`` with a leading
axis of the HELD experts, ``mlp.router.gate`` as wide as all the routed
ones. ``program_tree`` hands the program one stack a kind under its own
names.

What is drawn how, and why: the selection bias 0.02 x normal, float32,
not zero (a bias that is dropped, or that leaks into the weights, must
change the answer), and router weights alike for every expert, so that
the held ones get an eighth of the assignments in the mean
(``kda_gqa_moe.py``'s reasoning); the q and k norms a head as every norm
(not all ones).
"""

from __future__ import annotations

import concurrent.futures
import json
from typing import Any, Dict, List

import jax
import jax.numpy as jnp

from chipbench.weights import (norm_weight, root_key, scaled_normal,
                               served_dtype)

KINDS = {"sliding_attention": "swa", "full_attention": "attn"}


def dims(cfg: Dict[str, Any]) -> Dict[str, int]:
    held = int(cfg["num_experts"])
    return {"D": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
            "Fe": int(cfg["moe_intermediate_size"]),
            "H": int(cfg["num_attention_heads"]),
            "Hkv": int(cfg["num_key_value_heads"]),
            "Dh": int(cfg["head_dim"]),
            "E": held, "Er": held * int(cfg.get("expert_share_chips", 1)),
            "S": int(cfg.get("num_shared_experts", 1)),
            "V": int(cfg["vocab_size"])}


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    n_dense = int(cfg.get("num_dense_layers", 0))
    return [KINDS[t] + ("+dense" if i < n_dense else "+moe")
            for i, t in enumerate(cfg["layer_types"])
            ][:int(cfg["num_hidden_layers"])]


def layer_params(cfg: Dict[str, Any], key: jax.Array, layer, kind: str
                 ) -> Dict[str, jax.Array]:
    """One layer's weights, as stored (traceable in ``layer``)."""
    m, dt = dims(cfg), served_dtype(cfg)
    D, H, Hkv, Dh = m["D"], m["H"], m["Hkv"], m["Dh"]
    op, ffn = kind.split("+")
    if op not in ("swa", "attn") or ffn not in ("dense", "moe"):
        raise ValueError(f"no layer of kind {kind!r} in this family")
    k = jax.random.split(jax.random.fold_in(key, 1000 + layer), 32)
    lp = {"input_layernorm": norm_weight(k[0], (D,), dt),
          "post_attention_layernorm": norm_weight(k[1], (D,), dt),
          "pre_mlp_layernorm": norm_weight(k[2], (D,), dt),
          "post_mlp_layernorm": norm_weight(k[3], (D,), dt),
          "self_attn.q_proj": scaled_normal(k[4], (D, H * Dh), D, dt),
          "self_attn.k_proj": scaled_normal(k[5], (D, Hkv * Dh), D, dt),
          "self_attn.v_proj": scaled_normal(k[6], (D, Hkv * Dh), D, dt),
          "self_attn.gate_proj": scaled_normal(k[7], (D, H * Dh), D, dt),
          "self_attn.o_proj": scaled_normal(k[8], (H * Dh, D), H * Dh, dt),
          "self_attn.q_norm": norm_weight(k[9], (Dh,), dt),
          "self_attn.k_norm": norm_weight(k[10], (Dh,), dt)}
    if ffn == "dense":
        F = m["F"]
        lp.update({
            "mlp.gate_proj": scaled_normal(k[20], (D, F), D, dt),
            "mlp.up_proj": scaled_normal(k[21], (D, F), D, dt),
            "mlp.down_proj": scaled_normal(k[22], (F, D), F, dt)})
        return lp
    Fe, E, Er, Fs = m["Fe"], m["E"], m["Er"], m["S"] * m["Fe"]
    lp.update({
        "mlp.router.gate": scaled_normal(k[20], (D, Er), D, dt),
        "mlp.expert_bias":
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
    "input_layernorm": "input_norm",
    "post_attention_layernorm": "post_attn_norm",
    "pre_mlp_layernorm": "post_norm",
    "post_mlp_layernorm": "post_mlp_norm",
    "self_attn.q_proj": "q_proj", "self_attn.k_proj": "k_proj",
    "self_attn.v_proj": "v_proj", "self_attn.o_proj": "o_proj",
    "self_attn.gate_proj": "attn_gate",
    "self_attn.q_norm": "q_norm", "self_attn.k_norm": "k_norm",
    "mlp.gate_proj": "gate_proj", "mlp.up_proj": "up_proj",
    "mlp.down_proj": "down_proj",
    "mlp.router.gate": "router", "mlp.expert_bias": "router_bias",
    "mlp.experts.gate_proj": "gate_proj", "mlp.experts.up_proj": "up_proj",
    "mlp.experts.down_proj": "down_proj",
    "mlp.shared_experts.gate_proj": "shared_gate",
    "mlp.shared_experts.up_proj": "shared_up",
    "mlp.shared_experts.down_proj": "shared_down"}


def program_layer(lp: Dict[str, jax.Array], kind: str
                  ) -> Dict[str, jax.Array]:
    """A stored layer under the program's names (``post_norm`` is the
    norm in front of the feed-forward in every body of the loop over
    kinds: this family's ``pre_mlp_layernorm``)."""
    return {_PROGRAM_NAMES[n]: v for n, v in lp.items()}


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
