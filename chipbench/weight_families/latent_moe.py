"""Weights from the seed for the latent-attention, sparse-expert family
(``model_type`` ``deepseek_v3`` and its kin, ``joyai_llm_flash`` among
them): multi-head latent attention in every layer, a SwiGLU feed-forward
in the first ``first_k_dense_replace`` layers and sigmoid-routed experts
plus shared ones in the rest.

Leaves carry the published checkpoint's names and are stored [in, out]
(``experts.*`` with a leading expert axis). Two kinds of layer: ``dense``
and ``sparse``. ``program_tree`` hands the program what its loader
(``runtime/checkpoint.py``, ``_load_mla_checkpoint``) makes of such a
checkpoint: two stacks, the program's names, ``kv_b_proj`` split into
the absorbed halves. A module a checkpoint carries beside its layers
(``num_nextn_predict_layers``) is made by nobody here: the model's
next-token logits do not depend on it.
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
    return {"D": int(cfg["hidden_size"]), "F": int(cfg["intermediate_size"]),
            "Fe": int(cfg["moe_intermediate_size"]),
            "H": int(cfg["num_attention_heads"]),
            "ql": int(cfg["q_lora_rank"]), "r": int(cfg["kv_lora_rank"]),
            "nope": int(cfg["qk_nope_head_dim"]),
            "rope": int(cfg["qk_rope_head_dim"]),
            "vd": int(cfg["v_head_dim"]), "E": int(cfg["n_routed_experts"]),
            "S": int(cfg["n_shared_experts"]), "V": int(cfg["vocab_size"]),
            "L": int(cfg["num_hidden_layers"]),
            "k_dense": int(cfg["first_k_dense_replace"])}


def layer_kinds(cfg: Dict[str, Any]) -> List[str]:
    m = dims(cfg)
    return ["dense"] * m["k_dense"] + ["sparse"] * (m["L"] - m["k_dense"])


def layer_params(cfg: Dict[str, Any], key: jax.Array, layer, kind: str
                 ) -> Dict[str, jax.Array]:
    """One layer's weights, as stored (traceable in ``layer``)."""
    m, dt = dims(cfg), served_dtype(cfg)
    D, H, ql, r = m["D"], m["H"], m["ql"], m["r"]
    nope, rope, vd = m["nope"], m["rope"], m["vd"]
    k = jax.random.split(jax.random.fold_in(key, 1000 + layer), 20)
    lp = {
        "input_layernorm": norm_weight(k[0], (D,), dt),
        "q_a_proj": scaled_normal(k[1], (D, ql), D, dt),
        "q_a_layernorm": norm_weight(k[2], (ql,), dt),
        "q_b_proj": scaled_normal(k[3], (ql, H * (nope + rope)), ql, dt),
        "kv_a_proj_with_mqa": scaled_normal(k[4], (D, r + rope), D, dt),
        "kv_a_layernorm": norm_weight(k[5], (r,), dt),
        "kv_b_proj": scaled_normal(k[6], (r, H * (nope + vd)), r, dt),
        "o_proj": scaled_normal(k[7], (H * vd, D), H * vd, dt),
        "post_attention_layernorm": norm_weight(k[8], (D,), dt),
    }
    if kind == "dense":
        F = m["F"]
        lp.update({"gate_proj": scaled_normal(k[9], (D, F), D, dt),
                   "up_proj": scaled_normal(k[10], (D, F), D, dt),
                   "down_proj": scaled_normal(k[11], (F, D), F, dt)})
    elif kind == "sparse":
        E, Fe, Fs = m["E"], m["Fe"], m["Fe"] * m["S"]
        lp.update({
            "gate": scaled_normal(k[9], (D, E), D, dt),
            # The selection bias is no weight of a linear layer: float32,
            # and an eighth of the scores' own spread (sigmoid of logits
            # of deviation 0.7: 0.16), so that dropping it changes which
            # experts most tokens take while the load stays as even as a
            # trained bias keeps it: 32 rows taking 8 of 256 touch 61% of
            # a layer's experts under it, 63% without it, and 37% under a
            # deviation of 0.1, which sends every row to the same few
            # (31% measured in the cell: PERF.md, PR 36).
            "e_score_correction_bias":
                0.02 * jax.random.normal(k[10], (E,), jnp.float32),
            "experts.gate_proj": scaled_normal(k[11], (E, D, Fe), D, dt),
            "experts.up_proj": scaled_normal(k[12], (E, D, Fe), D, dt),
            "experts.down_proj": scaled_normal(k[13], (E, Fe, D), Fe, dt),
            "shared_experts.gate_proj":
                scaled_normal(k[14], (D, Fs), D, dt),
            "shared_experts.up_proj": scaled_normal(k[15], (D, Fs), D, dt),
            "shared_experts.down_proj":
                scaled_normal(k[16], (Fs, D), Fs, dt)})
    else:
        raise ValueError(f"no kind of layer {kind!r} in this family")
    return lp


# Compiled makers of embedding and head that ``program_tree`` has built in
# this process, by configuration: the reference check asks for the same
# leaves again after the window, outside any jit, and drawing two
# [vocabulary, hidden] normals operation by operation compiled for 15 s
# (my chip run, PR 36) what is compiled already.
_HEAD_MAKERS: Dict[str, Any] = {}


def head_params(cfg: Dict[str, Any], key: jax.Array) -> Dict[str, jax.Array]:
    """Embedding, final norm and output head, as stored."""
    made = _HEAD_MAKERS.get(json.dumps(cfg, sort_keys=True))
    if made is not None and not isinstance(key, jax.core.Tracer):
        return made(key)
    m, dt = dims(cfg), served_dtype(cfg)
    k = jax.random.split(jax.random.fold_in(key, 7), 3)
    return {"embed": scaled_normal(k[0], (m["V"], m["D"]), m["D"], dt),
            "final_norm": norm_weight(k[1], (m["D"],), dt),
            "lm_head": scaled_normal(k[2], (m["D"], m["V"]), m["D"], dt)}


_PROGRAM_NAMES = {
    "input_layernorm": "input_norm", "q_a_proj": "q_a",
    "q_a_layernorm": "q_a_norm", "q_b_proj": "q_b",
    "kv_a_proj_with_mqa": "kv_a", "kv_a_layernorm": "kv_a_norm",
    "o_proj": "o_proj", "post_attention_layernorm": "post_norm",
    "gate_proj": "gate_proj", "up_proj": "up_proj", "down_proj": "down_proj",
    "gate": "router", "e_score_correction_bias": "router_bias",
    "experts.gate_proj": "gate_proj", "experts.up_proj": "up_proj",
    "experts.down_proj": "down_proj",
    "shared_experts.gate_proj": "shared_gate",
    "shared_experts.up_proj": "shared_up",
    "shared_experts.down_proj": "shared_down"}


def _as_the_program_loads(cfg: Dict[str, Any], lp: Dict[str, jax.Array]
                          ) -> Dict[str, jax.Array]:
    m = dims(cfg)
    lp = dict(lp)
    # [r, H * (nope + v)] -> per head [nope + v, r], keys then values
    w = lp.pop("kv_b_proj").reshape(m["r"], m["H"], m["nope"] + m["vd"])
    w = jnp.transpose(w, (1, 2, 0))
    out = {_PROGRAM_NAMES[k]: v for k, v in lp.items()}
    out["kv_b_k"], out["kv_b_v"] = w[:, :m["nope"]], w[:, m["nope"]:]
    return out


def program_tree(cfg: Dict[str, Any], seed: int) -> Dict[str, Any]:
    """Every weight, born on the device in the served type: the dense
    stack under ``layers``, the sparse one under ``layers_moe``."""
    if cfg.get("tie_word_embeddings"):
        raise ValueError("tied embeddings: this generator makes a head")
    m = dims(cfg)
    if not 0 < m["k_dense"] < m["L"]:
        raise ValueError("this generator makes one stack of each kind")

    def stack(kind, lo, hi):
        # One jitted call a stack, so that only one stack's float32
        # draws are alive beside what is already made: a sparse layer is
        # 2.5 GB in the served type at 256 experts of 2048 x 768.
        return jax.jit(lambda key: jax.lax.map(
            lambda i: _as_the_program_loads(
                cfg, layer_params(cfg, key, i, kind)),
            jnp.arange(lo, hi, dtype=jnp.int32)))

    # The three makers are compiled side by side (a checkout's first run
    # compiles them, ~35 s one after another on a v5e's host; every later
    # run finds them in the persistent cache) and run one after another.
    key = root_key(seed)
    makers = [jax.jit(lambda key: head_params(cfg, key)).lower(key),
              stack("dense", 0, m["k_dense"]).lower(key),
              stack("sparse", m["k_dense"], m["L"]).lower(key)]
    with concurrent.futures.ThreadPoolExecutor(len(makers)) as pool:
        head, dense, sparse = pool.map(lambda lo: lo.compile(), makers)
    _HEAD_MAKERS[json.dumps(cfg, sort_keys=True)] = head
    return {**head(key), "layers": dense(key), "layers_moe": sparse(key)}
