"""Weights from the seed for the family whose layers differ in kind
(``model_type`` ``lfm2_moe``): a gated short convolution or grouped-query
attention as a layer's operator (``layer_types``), a SwiGLU feed-forward
in the first ``num_dense_layers`` layers and sigmoid-routed experts in
the rest.

Leaves carry the published checkpoint's names and are stored [in, out]
(``experts.*`` with a leading expert axis; the depthwise filter
``conv.conv`` as [taps, channels], tap j on the gated input ``taps - 1 -
j`` positions back). A layer's kind is ``<operator>+<ffn>``: ``conv`` or
``attn``, ``dense`` or ``moe``. ``program_tree`` hands the program what
its loader (``runtime/checkpoint.py``, ``_load_kinds_checkpoint``) makes
of such a checkpoint: one stack a kind, in layer order, under the
program's names; the head is the embedding (``tie_word_embeddings``).
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
    H = int(cfg["num_attention_heads"])
    return {"D": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
            "Fe": int(cfg["moe_intermediate_size"]), "H": H,
            "Hkv": int(cfg["num_key_value_heads"]),
            "Dh": int(cfg["hidden_size"]) // H,
            "E": int(cfg["num_experts"]), "K": int(cfg["conv_L_cache"]),
            "V": int(cfg["vocab_size"])}


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    ops = {"conv": "conv", "full_attention": "attn"}
    return [ops[t] + ("+dense" if i < int(cfg["num_dense_layers"])
                      else "+moe")
            for i, t in enumerate(cfg["layer_types"])]


def layer_params(cfg: Dict[str, Any], key: jax.Array, layer, kind: str
                 ) -> Dict[str, jax.Array]:
    """One layer's weights, as stored (traceable in ``layer``)."""
    m, dt = dims(cfg), served_dtype(cfg)
    D, H, Hkv, Dh = m["D"], m["H"], m["Hkv"], m["Dh"]
    k = jax.random.split(jax.random.fold_in(key, 1000 + layer), 16)
    op, ffn = kind.split("+")
    lp = {"operator_norm": norm_weight(k[0], (D,), dt),
          "ffn_norm": norm_weight(k[1], (D,), dt)}
    if op == "conv":
        lp.update({
            "conv.in_proj": scaled_normal(k[2], (D, 3 * D), D, dt),
            "conv.conv": scaled_normal(k[3], (m["K"], D), m["K"], dt),
            "conv.out_proj": scaled_normal(k[4], (D, D), D, dt)})
    elif op == "attn":
        lp.update({
            "self_attn.q_proj": scaled_normal(k[2], (D, H * Dh), D, dt),
            "self_attn.k_proj": scaled_normal(k[3], (D, Hkv * Dh), D, dt),
            "self_attn.v_proj": scaled_normal(k[4], (D, Hkv * Dh), D, dt),
            "self_attn.out_proj":
                scaled_normal(k[5], (H * Dh, D), H * Dh, dt),
            "self_attn.q_layernorm": norm_weight(k[6], (Dh,), dt),
            "self_attn.k_layernorm": norm_weight(k[7], (Dh,), dt)})
    else:
        raise ValueError(f"no operator {op!r} in this family")
    if ffn == "dense":
        F = m["F"]
        lp.update({"feed_forward.w1": scaled_normal(k[8], (D, F), D, dt),
                   "feed_forward.w3": scaled_normal(k[9], (D, F), D, dt),
                   "feed_forward.w2": scaled_normal(k[10], (F, D), F, dt)})
    elif ffn == "moe":
        E, Fe = m["E"], m["Fe"]
        lp.update({
            "feed_forward.gate": scaled_normal(k[8], (D, E), D, dt),
            # The selection bias is no weight of a linear layer: float32,
            # nonzero (a bias that is dropped, or that leaks into the
            # weights, must change the answer), and an eighth of the
            # scores' own spread, so that the load stays as even as a
            # trained bias keeps it (the latent family's reasoning,
            # chipbench/weight_families/latent_moe.py).
            "feed_forward.expert_bias":
                0.02 * jax.random.normal(k[9], (E,), jnp.float32),
            "feed_forward.experts.w1":
                scaled_normal(k[10], (E, D, Fe), D, dt),
            "feed_forward.experts.w3":
                scaled_normal(k[11], (E, D, Fe), D, dt),
            "feed_forward.experts.w2":
                scaled_normal(k[12], (E, Fe, D), Fe, dt)})
    else:
        raise ValueError(f"no feed-forward {ffn!r} in this family")
    return lp


# Compiled makers of the embedding that ``program_tree`` has built in
# this process, by configuration: the reference check asks for the same
# leaves again after the window, outside any jit (the latent family's
# finding: drawing a [vocabulary, hidden] normal operation by operation
# compiles for many seconds what is compiled already).
_HEAD_MAKERS: Dict[str, Any] = {}


def _head(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, jax.Array]:
    m, dt = dims(cfg), served_dtype(cfg)
    k = jax.random.split(jax.random.fold_in(key, 7), 2)
    return {"embed": scaled_normal(k[0], (m["V"], m["D"]), m["D"], dt),
            "final_norm": norm_weight(k[1], (m["D"],), dt)}


def head_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, jax.Array]:
    """Embedding, the norm after the last layer (``embedding_norm``) and
    the output head, which IS the embedding (tied)."""
    if not cfg.get("tie_word_embeddings", True):
        raise ValueError("this generator ties the head to the embedding")
    made = _HEAD_MAKERS.get(json.dumps(cfg, sort_keys=True))
    head = made(key) if made is not None \
        and not isinstance(key, jax.core.Tracer) else _head(cfg, key)
    return {**head, "lm_head": head["embed"].T}


_PROGRAM_NAMES = {
    "operator_norm": "input_norm", "ffn_norm": "post_norm",
    "conv.in_proj": "conv_in", "conv.conv": "conv_w",
    "conv.out_proj": "conv_out",
    "self_attn.q_proj": "q_proj", "self_attn.k_proj": "k_proj",
    "self_attn.v_proj": "v_proj", "self_attn.out_proj": "o_proj",
    "self_attn.q_layernorm": "q_norm", "self_attn.k_layernorm": "k_norm",
    "feed_forward.w1": "gate_proj", "feed_forward.w3": "up_proj",
    "feed_forward.w2": "down_proj",
    "feed_forward.gate": "router", "feed_forward.expert_bias": "router_bias",
    "feed_forward.experts.w1": "gate_proj",
    "feed_forward.experts.w3": "up_proj",
    "feed_forward.experts.w2": "down_proj"}


def program_tree(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Every weight, born on the device in the served type: one stack a
    kind under ``stacks``, each in layer order."""
    kinds = layer_kinds(cfg)

    def stack(kind):
        # One jitted call a stack, so that only one layer's float32
        # draws are alive beside what is already made: a sparse layer is
        # 1.2 GB in the served type at 64 experts of 2048 x 1536.
        at = jnp.asarray([i for i, k in enumerate(kinds) if k == kind],
                         jnp.int32)
        return jax.jit(lambda key: jax.lax.map(
            lambda i: {_PROGRAM_NAMES[n]: v for n, v in
                       layer_params(cfg, key, i, kind).items()}, at))

    # Compiled side by side, run one after another (the latent family's
    # arrangement: a checkout's first run compiles them, every later run
    # finds them in the persistent cache).
    key = root_key(seed)
    names = sorted(set(kinds))
    makers = [jax.jit(lambda key: _head(cfg, key)).lower(key)] + [
        stack(kind).lower(key) for kind in names]
    with concurrent.futures.ThreadPoolExecutor(len(makers)) as pool:
        head, *stacks = pool.map(lambda lo: lo.compile(), makers)
    _HEAD_MAKERS[json.dumps(cfg, sort_keys=True)] = head
    return {**head(key),
            "stacks": {kind: make(key) for kind, make in zip(names, stacks)}}
