"""PartitionSpec rules for model parameters, KV cache, and activations.

Megatron-style tensor parallelism expressed purely as GSPMD sharding
annotations: column-parallel QKV/gate/up (output feature axis over ``tp``),
row-parallel O/down (input feature axis over ``tp``) — XLA then places
exactly one all-reduce after attention-out and one after MLP-down per layer,
the same collective schedule a hand-written Megatron implements with NCCL.
Experts shard over ``ep``: the dense-MoE einsums in the model contract over
the expert axis, which GSPMD turns into compute-local-experts + psum — an
expert-parallel schedule with no explicit all-to-all code.

Rules are path-keyed so new parameters fail loudly rather than silently
replicating.
"""

from __future__ import annotations

from typing import Any, Dict

import jax
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from xllm_service_tpu.config import ModelConfig
from xllm_service_tpu.parallel.mesh import AXIS_DP, AXIS_EP, AXIS_SP, AXIS_TP

# Per-leaf rules; layer weights carry a leading stacked-L axis (always
# unsharded — scan iterates over it).
_LAYER_RULES: Dict[str, P] = {
    "input_norm": P(None, None),
    "post_norm": P(None, None),
    "q_proj": P(None, None, AXIS_TP),
    "k_proj": P(None, None, AXIS_TP),
    "v_proj": P(None, None, AXIS_TP),
    "q_bias": P(None, AXIS_TP),
    "k_bias": P(None, AXIS_TP),
    "v_bias": P(None, AXIS_TP),
    "o_proj": P(None, AXIS_TP, None),
    # Dense MLP.
    "gate_proj": P(None, None, AXIS_TP),
    "up_proj": P(None, None, AXIS_TP),
    "down_proj": P(None, AXIS_TP, None),
    # MoE (4-D expert-stacked shapes override the dense rules below).
    "router": P(None, None, AXIS_EP),
}
_MOE_LAYER_RULES: Dict[str, P] = {
    "gate_proj": P(None, AXIS_EP, None, AXIS_TP),
    "up_proj": P(None, AXIS_EP, None, AXIS_TP),
    "down_proj": P(None, AXIS_EP, AXIS_TP, None),
}


def param_pspecs(cfg: ModelConfig) -> Dict[str, Any]:
    """PartitionSpec pytree matching ``init_params``' structure."""
    layer_rules = dict(_LAYER_RULES)
    if cfg.is_moe:
        layer_rules.update(_MOE_LAYER_RULES)
    keys = ["input_norm", "post_norm", "q_proj", "k_proj", "v_proj",
            "o_proj", "gate_proj", "up_proj", "down_proj"]
    if cfg.attention_bias:
        keys += ["q_bias", "k_bias", "v_bias"]
    if cfg.is_moe:
        keys += ["router"]
    layers = {k: layer_rules[k] for k in keys}
    specs: Dict[str, Any] = {
        # Vocab-sharded embedding: the gather broadcasts only D per token,
        # and the (tied) lm_head matmul contracts locally then psums.
        "embed": P(AXIS_TP, None),
        "layers": layers,
        "final_norm": P(None),
    }
    if not cfg.tie_word_embeddings:
        specs["lm_head"] = P(None, AXIS_TP)
    return specs


def kv_cache_pspec(cfg: ModelConfig, tp_size: int = 1) -> P:
    """KV pages [L, pages, page_size, Hkv, Dh]: KV heads over tp, co-located
    with the q heads that read them — pure-local attention, zero collectives
    in the decode hot loop. When Hkv doesn't divide tp (MQA / small models on
    wide meshes) the cache is replicated instead, mirroring how GQA KV heads
    are duplicated across tp subgroups."""
    if tp_size > 1 and cfg.kv_cache_heads % tp_size == 0:
        # (MLA's single latent "head" never divides tp>1 → replicated.)
        return P(None, None, None, AXIS_TP, None)
    return P(None, None, None, None, None)


def batch_pspec() -> P:
    """Activations/tokens [B, ...]: batch over dp."""
    return P(AXIS_DP)


def seq_pspec() -> P:
    """Long-context activations [B, T, ...]: batch over dp, seq over sp."""
    return P(AXIS_DP, AXIS_SP)


def param_shardings(params: Dict[str, Any], mesh: Mesh,
                    cfg: ModelConfig) -> Dict[str, Any]:
    """NamedSharding for every leaf of ``params`` (arrays, or the
    ShapeDtypeStructs of ``jax.eval_shape`` — only names and ranks are
    read). Specs are derived from the ACTUAL tree structure: rule tables
    by leaf name (picking the rule whose rank matches — MoE expert
    stacks vs dense MLPs share names), replicated default for everything
    unlisted (per-head norms, gemma's extra block norms, the MLA
    q_a/q_b/kv_a/kv_b_*/shared_* tree). MLA leaves whose name AND rank
    match a llama rule (q_proj, o_proj — both column/row-parallel on
    their feature axis) take that rule, which is dimensionally sound for
    them too."""

    def spec_for(path, leaf) -> P:
        name = next((p.key for p in reversed(path)
                     if hasattr(p, "key")), "")
        if name == "embed":
            return P(AXIS_TP, None)
        if name == "lm_head":
            return P(None, AXIS_TP)
        for rules in ((_MOE_LAYER_RULES, _LAYER_RULES) if cfg.is_moe
                      else (_LAYER_RULES,)):
            spec = rules.get(name)
            if spec is not None and len(spec) == leaf.ndim:
                return spec
        return P(*([None] * leaf.ndim))

    return jax.tree_util.tree_map_with_path(
        lambda path, x: NamedSharding(mesh, spec_for(path, x)), params)


def shard_params(params: Dict[str, Any], mesh: Mesh,
                 cfg: ModelConfig) -> Dict[str, Any]:
    """device_put every leaf with its ``param_shardings`` placement."""
    return jax.tree_util.tree_map(
        jax.device_put, params, param_shardings(params, mesh, cfg))


def kv_cache_sharding(mesh: Mesh, cfg: ModelConfig) -> NamedSharding:
    return NamedSharding(mesh, kv_cache_pspec(cfg, mesh.shape[AXIS_TP]))


def shard_kv_cache(kv, mesh: Mesh, cfg: ModelConfig):
    s = kv_cache_sharding(mesh, cfg)
    return tuple(jax.device_put(x, s) for x in kv)
