"""Functional decoder-only transformer over a paged KV cache.

One scanned layer body serves every family in ``docs/MODELS.md`` —
Llama-2/3.x, Qwen2/2.5/3 (+Qwen3-MoE), Phi-3, Mistral (v0.1 sliding
window and v0.2+), Gemma-2/3 (four-norm blocks, soft-caps, per-layer
windows and rope bases as traced scan xs), Mixtral, GPT-OSS (attention
sinks, clamped-GLU experts), the Qwen2/2.5-VL mrope text stacks, Ouro
(that scan inside a loop over passes: "Layer passes") — plus
a dedicated multi-head-latent-attention path (DeepSeek-V2/V3/R1) that
serves a latent pool through the same paged machinery. Design choices
are TPU-first (SURVEY.md §7.1):

- **Stacked layers + ``lax.scan``**: every per-layer weight carries a leading
  ``[L, ...]`` axis and the layer body is traced once, so compile time and
  program size are depth-independent and XLA pipelines HBM prefetch of layer
  l+1's weights behind layer l's compute.
- **Plain pytree params** (no framework modules): the sharding layer
  (``parallel/sharding.py``) attaches ``NamedSharding`` per leaf path; pjit
  then partitions the same function over any mesh.
- **Paged KV cache** threaded through scan as per-layer xs/ys (see
  ``ops/attention.py`` for the page pool layout).
- **bfloat16 weights/activations, float32 softmax/norm/rope/logits** — the
  MXU-native mix.

The reference repo has no model code at all (its engine is out-of-repo,
SURVEY.md §2 intro); this file is the net-new compute path it assumes.
"""

from __future__ import annotations

import math
from typing import Any, Dict, Optional, Tuple

import jax
import jax.numpy as jnp

from xllm_service_tpu.config import ModelConfig
from xllm_service_tpu.ops.norm import rms_norm
from xllm_service_tpu.ops.rope import (apply_rope,
                                       apply_rope_dynamic,
                                       rope_for)
from xllm_service_tpu.ops.attention import (
    FULL_WINDOW,
    mha_prefill,
    mha_prefill_auto,
    paged_decode_attention,
    paged_decode_attention_auto,
    paged_decode_attention_current,
    paged_decode_attention_current_auto,
    gather_pages,
    overlay_fresh_kv,
    write_prefill_kv_all_layers,
    write_prefill_kv_layer,
    write_decode_kv_all_layers,
    write_decode_kv_layer,
    write_prefill_kv_all_layers_xla,
    write_prefill_kv_layer_xla,
    write_decode_kv_all_layers_xla,
    write_decode_kv_layer_xla,
)
from xllm_service_tpu.ops.plan import KernelPlan

Params = Dict[str, Any]
# k_pages, v_pages: [L, P, ps, Hkv, Dh], L a slot a layer a PASS
# (ModelConfig.kv_cache_layers); under latent attention the one latent
# pool alone (init_kv_cache).
KVCache = Tuple[jnp.ndarray, ...]


# ---------------------------------------------------------------------------
# Initialization
# ---------------------------------------------------------------------------

def init_params(cfg: ModelConfig, key: jax.Array,
                dtype: Optional[jnp.dtype] = None) -> Params:
    """Random-init a parameter pytree with the stacked-layer layout."""
    if cfg.mla:
        return _init_mla_params(cfg, key, dtype)
    if cfg.layer_kinds is not None:
        return _init_kinds_params(cfg, key, dtype)
    dtype = dtype or jnp.dtype(cfg.dtype)
    L, D, F = cfg.num_layers, cfg.hidden_size, cfg.intermediate_size
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    keys = iter(jax.random.split(key, 32))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dtype)

    layers: Dict[str, jnp.ndarray] = {
        "input_norm": jnp.ones((L, D), dtype),
        "q_proj": w((L, D, Hq * Dh), D),
        "k_proj": w((L, D, Hkv * Dh), D),
        "v_proj": w((L, D, Hkv * Dh), D),
        "o_proj": w((L, Hq * Dh, D), Hq * Dh),
        "post_norm": jnp.ones((L, D), dtype),
    }
    if cfg.attention_bias:
        layers["q_bias"] = jnp.zeros((L, Hq * Dh), dtype)
        layers["k_bias"] = jnp.zeros((L, Hkv * Dh), dtype)
        layers["v_bias"] = jnp.zeros((L, Hkv * Dh), dtype)
    if cfg.four_norm_block:
        layers["pre_ff_norm"] = jnp.ones((L, D), dtype)
        layers["post_ff_norm"] = jnp.ones((L, D), dtype)
    if cfg.gptoss:
        layers["sinks"] = jnp.zeros((L, Hq), jnp.float32)
        layers["o_bias"] = jnp.zeros((L, D), dtype)
    if cfg.qk_norm:
        layers["q_norm"] = jnp.ones((L, Dh), dtype)
        layers["k_norm"] = jnp.ones((L, Dh), dtype)
    if cfg.is_moe:
        E = cfg.num_experts
        Fe = cfg.moe_intermediate_size or F
        layers["router"] = w((L, D, E), D)
        layers["gate_proj"] = w((L, E, D, Fe), D)
        layers["up_proj"] = w((L, E, D, Fe), D)
        layers["down_proj"] = w((L, E, Fe, D), Fe)
        if cfg.gptoss:
            layers["router_bias"] = jnp.zeros((L, E), jnp.float32)
            layers["gate_bias"] = jnp.zeros((L, E, Fe), dtype)
            layers["up_bias"] = jnp.zeros((L, E, Fe), dtype)
            layers["down_bias"] = jnp.zeros((L, E, D), dtype)
    else:
        layers["gate_proj"] = w((L, D, F), D)
        layers["up_proj"] = w((L, D, F), D)
        layers["down_proj"] = w((L, F, D), F)

    params: Params = {
        "embed": w((cfg.vocab_size, D), D),
        "layers": layers,
        "final_norm": jnp.ones((D,), dtype),
    }
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((D, cfg.vocab_size), D)
    if cfg.looped:
        # The exit gate: hidden -> 1 with a bias, read off each pass's
        # normed state (``_exit_gate``).
        params["exit_gate"] = {"w": w((D,), D), "b": jnp.zeros((), dtype)}
    return params


def num_params(params: Params) -> int:
    return sum(int(x.size) for x in jax.tree_util.tree_leaves(params))


def init_kv_cache(cfg: ModelConfig, num_pages: int, page_size: int,
                  dtype: Optional[jnp.dtype] = None,
                  state_slots: int = 0) -> KVCache:
    """The pools. ``state_slots``: slots of the fourth pool, which a model
    with a mixer beside attention keeps (``cfg.num_ssm_layers``) and no
    other does."""
    dtype = dtype or jnp.dtype(cfg.dtype)
    if cfg.layer_kinds is not None:
        # Keys and values of the ATTENTION layers alone, and a third
        # pool of convolution tails: one row a page a convolution layer,
        # the layer's state as of the last token written into that page
        # ("Layers that differ in kind", below). Flat rows, so that no
        # short axis is padded to a tile.
        pack = _kv_pack(cfg)
        shape = (max(cfg.num_attn_layers, 1), num_pages, page_size,
                 cfg.num_kv_heads // pack, pack * cfg.head_dim)
        tails = (max(cfg.num_conv_layers, 1), num_pages,
                 cfg.conv_tail_width)
        pools = (jnp.zeros(shape, dtype), jnp.zeros(shape, dtype),
                 jnp.zeros(tails, dtype))
        if cfg.num_ssm_layers:
            # A fourth pool, addressed by SLOT and not by page: a layer's
            # state of one sequence is heads x head width x state values
            # in float32 (4.2 MB at 32 x 128 x 256), sixteen times the
            # layer's keys and values of a page. Slot 0 is the null slot
            # (padding rows), as page 0 is the null page; who owns which
            # slot is the host's business (runtime/kv_cache.py). A
            # head's matrix is stored [state, head width]: the head
            # width rides the lanes, as the row's x and y do, so the
            # decode kernel broadcasts its operands along an axis they
            # already lack (ops/pallas/ssm_update.py).
            pools += (jnp.zeros(
                (cfg.num_ssm_layers, max(state_slots, 2), cfg.ssm_heads,
                 cfg.ssm_state, cfg.ssm_head_dim), jnp.float32),)
        return pools
    # One slot a layer a PASS (``ModelConfig.kv_cache_layers``): pass p
    # of a looped model keeps layer l's keys and values at p * L + l.
    shape = (cfg.kv_cache_layers, num_pages, page_size, cfg.kv_cache_heads,
             cfg.kv_cache_dim)
    if cfg.mla:
        # ONE pool: a token's cached row under latent attention is one
        # latent "head" of width kv_lora_rank + qk_rope_head_dim
        # (cfg.kv_cache_{heads,dim}) that serves as key and value both.
        return (jnp.zeros(shape, dtype),)
    return jnp.zeros(shape, dtype), jnp.zeros(shape, dtype)


# ---------------------------------------------------------------------------
# Layer body (shared by prefill and decode via an `is_prefill` closure switch
# — two separate compiled programs, one source of truth)
# ---------------------------------------------------------------------------

# Sentinel window for full-attention layers when windows ride the layer
# scan as traced per-layer values (Gemma-2 alternation): larger than any
# context, so the window mask is a no-op. Shared with the Pallas kernels
# (whose int32 window arithmetic bounds it at 2^30 — see ops/attention).
_FULL_WINDOW = FULL_WINDOW


def _scatter_topk(vals: jnp.ndarray, idx: jnp.ndarray,
                  num_classes: int) -> jnp.ndarray:
    """Scatter per-token top-k ``vals`` [.., k] at expert ids ``idx``
    [.., k] into a dense [.., E] map (k is tiny/static). The one shared
    idiom behind every router's dense weight map."""
    out = jnp.zeros(vals.shape[:-1] + (num_classes,), vals.dtype)
    for j in range(vals.shape[-1]):
        out = out + vals[..., j:j + 1] * jax.nn.one_hot(
            idx[..., j], num_classes, dtype=vals.dtype)
    return out


def _attn_extras(cfg: ModelConfig) -> Dict[str, Any]:
    """Per-model attention kwargs beyond the tensors: Gemma-2's logit
    soft-cap and query_pre_attn_scalar**-0.5 scale override."""
    out: Dict[str, Any] = {"logits_soft_cap": cfg.attn_logit_softcapping}
    if cfg.query_pre_attn_scalar is not None:
        out["scale"] = cfg.query_pre_attn_scalar ** -0.5
    return out


def _layer_windows(cfg: ModelConfig) -> Optional[jnp.ndarray]:
    """[L] int32 per-layer window xs when the model alternates
    local/global layers; None for uniform models (static window)."""
    if cfg.layer_sliding is None:
        return None
    return jnp.asarray(
        [cfg.sliding_window if s else _FULL_WINDOW
         for s in cfg.layer_sliding], jnp.int32)


def _layer_rope(cfg: ModelConfig) -> Optional[jnp.ndarray]:
    """[L, 2] (theta, linear factor) per layer when rope bases differ by
    layer type (Gemma-3): sliding layers use rope_local_base_freq
    unscaled; full layers use rope_theta with the linear factor."""
    if cfg.rope_local_base_freq is None:
        return None
    factor = (cfg.rope_scaling[1]
              if cfg.rope_scaling is not None
              and cfg.rope_scaling[0] == "linear" else 1.0)
    pattern = cfg.layer_sliding
    if pattern is None:
        # Uniform models: an all-sliding pattern collapses to
        # layer_sliding None + sliding_window set at config load — every
        # layer is then LOCAL; no window at all means every layer is
        # global.
        pattern = (cfg.sliding_window is not None,) * cfg.num_layers
    rows = [(cfg.rope_local_base_freq, 1.0) if s
            else (cfg.rope_theta, factor) for s in pattern]
    return jnp.asarray(rows, jnp.float32)


def _scale_embed(cfg: ModelConfig, x: jnp.ndarray) -> jnp.ndarray:
    """Gemma scales token embeddings by sqrt(hidden) (cast to the
    activation dtype first, as HF does)."""
    if not cfg.gemma:
        return x
    return x * jnp.asarray(math.sqrt(cfg.hidden_size), x.dtype)


def _head_logits(cfg: ModelConfig, x: jnp.ndarray,
                 head: jnp.ndarray) -> jnp.ndarray:
    """lm_head matmul in fp32, with Gemma-2's final tanh soft-cap."""
    logits = (x @ head).astype(jnp.float32)
    cap = cfg.final_logit_softcapping
    if cap > 0.0:
        logits = cap * jnp.tanh(logits / cap)
    return logits


def _pin(values):
    """The identity on values, and NOT a no-op: ``values`` exist, as
    they are shaped here, before anything consumes them. A projection
    that feeds a reshape to heads is pinned twice, flat and in heads.

    FLAT (``[B, T, N]``, as the feed-forward's results are). Left to
    itself the TPU compiler folds the reshape to heads into the product
    and gives the folded product's WEIGHT operand the layout ``{1,2,0}``.
    A parameter's layout is fixed, so the program pays for the other one
    in every step: a layer's matrix sliced out of the stack into a
    temporary and copied transposed (13% of the Mistral cell's decode
    step), or the whole stack copied once a step, 1.2 GB of temporaries,
    and sliced 192 times (a fifth of the looped cell's; PERF.md, PR 44).
    How the weights are stored does not cure it (stored transposed the
    slice stays; one head-grouped weight brings both back): what does is
    a product whose result is consumed flat first. Then it reads its
    weight where it lies.

    IN HEADS (``[B, T, H, Dh]``). Pinned flat alone, the layout a
    consumer wants reaches back through the reshape and is settled
    somewhere worse: the hybrid family's prefill, whose values go
    straight into a pool tiled (4, 128), relaid the whole carried pool
    out and back around every attention layer (9 ms of a 29 ms program;
    PERF.md, PR 44). Pinned in heads too, the reshape is a small copy of
    an activation, as it was before, and no pool moves.

    To see either: ``tools/aot_copy_census.py`` counts the weights'
    slices and copies (``census_weight_relayouts``) and the pools'
    (``census_pool_copies``) in a compiled program's text;
    ``tests/test_copy_census.py`` holds the step programs at zero at the
    benchmark's widths and finds both again with this patched out."""
    return jax.lax.optimization_barrier(values)


def _qkv(lp: Dict[str, jnp.ndarray], cfg: ModelConfig, x: jnp.ndarray):
    """x: [B, T, D] → q [B, T, Hq, Dh], k/v [B, T, Hkv, Dh]."""
    B, T, _ = x.shape
    q = x @ lp["q_proj"]
    k = x @ lp["k_proj"]
    v = x @ lp["v_proj"]
    if "q_bias" in lp:
        q = q + lp["q_bias"]
        k = k + lp["k_bias"]
        v = v + lp["v_bias"]
    # Pinned flat AFTER the bias, not before: the bias is flat too, and
    # on this side of the pin its add rides the product's own fusion
    # (before it, three more fusions a layer; the weights lie still
    # either way).
    q, k, v = _pin((q, k, v))
    q = q.reshape(B, T, cfg.num_heads, cfg.head_dim)
    k = k.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    v = v.reshape(B, T, cfg.num_kv_heads, cfg.head_dim)
    q, k, v = _pin((q, k, v))
    if "q_norm" in lp:
        # Qwen3: per-head RMSNorm on q/k before rope.
        q = rms_norm(q, lp["q_norm"], cfg.rms_norm_eps)
        k = rms_norm(k, lp["k_norm"], cfg.rms_norm_eps)
    return q, k, v


def _mlp(lp: Dict[str, jnp.ndarray], cfg: ModelConfig,
         x: jnp.ndarray, valid: Optional[jnp.ndarray] = None
         ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """SwiGLU MLP; MoE routes each token through its top-k experts.
    ``valid`` [B, T] bool marks real tokens — padding / inactive lanes are
    kept out of sparse-MoE routing so they can't consume expert capacity
    (a real token's output must not depend on batch composition).

    Returns ``(out, moe_dropped)`` — the int32 count of (token, expert)
    assignments lost to capacity (always 0 for dense / oracle paths), so
    the serving layer can surface drop pressure instead of degrading
    silently."""
    zero = jnp.zeros((), jnp.int32)
    if cfg.gptoss:
        # GPT-OSS router: top-k over BIASED LOGITS, softmax over just
        # the selected k logits → dense weight map (sums to 1 on the
        # chosen experts); clamped-GLU experts with biases.
        logits = (x @ lp["router"]).astype(jnp.float32) + lp["router_bias"]
        k = cfg.num_experts_per_tok
        topv, topi = jax.lax.top_k(logits, k)
        weights = _scatter_topk(jax.nn.softmax(topv, axis=-1), topi,
                                logits.shape[-1])
        if cfg.moe_capacity_factor > 0:
            from xllm_service_tpu.parallel.expert import moe_mlp
            return moe_mlp(
                x, lp["router"], lp["gate_proj"], lp["up_proj"],
                lp["down_proj"], k, cfg.moe_capacity_factor,
                valid=valid, group_size=cfg.moe_group_size,
                norm_topk=False, gates=weights, expert_style="gptoss",
                gate_b=lp["gate_bias"], up_b=lp["up_bias"],
                down_b=lp["down_bias"])
        # Dense oracle: every expert on every token, weighted.
        hg = jnp.einsum("btd,edf->btef", x, lp["gate_proj"]) \
            + lp["gate_bias"][None, None]
        hu = jnp.einsum("btd,edf->btef", x, lp["up_proj"]) \
            + lp["up_bias"][None, None]
        hg = jnp.clip(hg, None, 7.0)
        hu = jnp.clip(hu, -7.0, 7.0)
        h = (hu + 1.0) * (hg * jax.nn.sigmoid(1.702 * hg))
        out = jnp.einsum("btef,efd->bted", h, lp["down_proj"]) \
            + lp["down_bias"][None, None]
        return jnp.einsum("bted,bte->btd", out,
                          weights.astype(x.dtype)), zero
    if not cfg.is_moe:
        gate = x @ lp["gate_proj"]
        # Gemma gates with tanh-GELU (gelu_pytorch_tanh); llama-family
        # with SiLU.
        act = jax.nn.gelu(gate, approximate=True) if cfg.gemma \
            else jax.nn.silu(gate)
        return (act * (x @ lp["up_proj"])) @ lp["down_proj"], zero
    if cfg.moe_capacity_factor > 0:
        # Sparse top-k dispatch into capacity buckets: per-token FLOPs are
        # k×(expert MLP), independent of E; GSPMD partitions the expert
        # axis over 'ep' from the weight shardings (parallel/expert.py).
        from xllm_service_tpu.parallel.expert import moe_mlp
        return moe_mlp(x, lp["router"], lp["gate_proj"], lp["up_proj"],
                       lp["down_proj"], cfg.num_experts_per_tok,
                       cfg.moe_capacity_factor, valid=valid,
                       group_size=cfg.moe_group_size,
                       norm_topk=cfg.norm_topk_prob)
    # Dense oracle (moe_capacity_factor == 0): every expert on every token,
    # mixed by routing weight — the test reference for the sparse path.
    gates = jax.nn.softmax((x @ lp["router"]).astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(gates, cfg.num_experts_per_tok)   # [B,T,K]
    if cfg.norm_topk_prob:
        topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    weights = _scatter_topk(topv, topi, gates.shape[-1])         # [B,T,E]
    h = jax.nn.silu(jnp.einsum("btd,edf->btef", x, lp["gate_proj"])) \
        * jnp.einsum("btd,edf->btef", x, lp["up_proj"])
    out = jnp.einsum("btef,efd->bted", h, lp["down_proj"])
    return jnp.einsum("bted,bte->btd", out,
                      weights.astype(x.dtype)), zero


# ---------------------------------------------------------------------------
# Layer passes (a looped model: ``ModelConfig.total_ut_steps`` > 1)
#
# The dense families' layer scan runs inside a loop over passes: the same
# stacked weights every pass, the final norm after every pass (the normed
# state is what the next pass starts from and what the exit gate reads),
# pool slot ``pass * num_layers + layer``. The loop is a scan, traced
# once; at a pass count of 1 there is no loop and no gate, and the
# program is what it was before there were passes.
# ---------------------------------------------------------------------------

def _exit_gate(params: Params, x: jnp.ndarray) -> jnp.ndarray:
    """lambda = sigmoid(w_g . x + b_g) of normed states x [..., D];
    float32 [...]."""
    g = params["exit_gate"]
    return jax.nn.sigmoid(
        jnp.dot(x, g["w"], preferred_element_type=jnp.float32)
        + g["b"].astype(jnp.float32))


def exit_pdf(lam: jnp.ndarray) -> jnp.ndarray:
    """Exit probabilities q [P, ...] from the gate's lambda [P, ...]:
    q_p = lambda_p * prod_{j<p}(1 - lambda_j), the last pass takes what
    is left (so they sum to 1)."""
    survive = jnp.cumprod(1.0 - lam, axis=0)
    before = jnp.concatenate([jnp.ones_like(lam[:1]), survive[:-1]], axis=0)
    return jnp.concatenate([(lam * before)[:-1], before[-1:]], axis=0)


def _run_passes(cfg: ModelConfig, params: Params, scan_layers, end_pass,
                carry, gate_rows):
    """``scan_layers(carry, li_arr) -> (carry, ys)`` (the layer scan over
    pool slots ``li_arr``) once, or in a scan over
    ``cfg.total_ut_steps`` passes, each ended by ``end_pass(carry)`` (the
    final norm). Returns ``(carry, ys, loop)``: ``ys`` with the passes
    folded into the layers' axis; ``loop`` is ``(passes the loop counted,
    lambda [P, B])``, with ``gate_rows(x)`` [B, D] the rows the gate reads
    of a pass's normed state. Without a loop, ``loop`` is None and the
    one pass comes back NOT ended: its caller ends it where it did before
    there were passes (after the pools' scatter), so that such a model's
    program is the text it was (tests/test_step_program_pins.py)."""
    li = jnp.arange(cfg.num_layers, dtype=jnp.int32)
    if not cfg.looped:
        carry, ys = scan_layers(carry, li)
        return carry, ys, None

    def body(c, p):
        carry, n = c
        carry, ys = scan_layers(carry, p * cfg.num_layers + li)
        carry = end_pass(carry)
        x = carry[0] if isinstance(carry, tuple) else carry
        return (carry, n + 1), (ys, _exit_gate(params, gate_rows(x)))

    (carry, n), (ys, lam) = jax.lax.scan(
        body, (carry, jnp.zeros((), jnp.int32)),
        jnp.arange(cfg.total_ut_steps, dtype=jnp.int32))
    ys = jax.tree_util.tree_map(
        lambda a: a.reshape((-1,) + a.shape[2:]), ys)
    return carry, ys, (n, lam)


def _dense_stats(dropped: jnp.ndarray, loop, decode_rows: jnp.ndarray
                 ) -> Dict[str, jnp.ndarray]:
    """The ``return_stats`` dict of the dense forwards. A looped model
    adds ``exit_pdf`` [B, P] (each row's exit probabilities) and
    ``loop``, the float32 vector that rides back in the place of the
    dropped scalar (``step_moe_stats``): [dropped, passes run, decode
    rows, then for each pass but the last the sum over the decode rows
    of the cumulative exit probability after it]. ``decode_rows`` [B]
    bool: the rows that count (none in a prefill)."""
    if loop is None:
        return {"moe_dropped": dropped}
    n, lam = loop
    q = exit_pdf(lam)                                            # [P, B]
    rows = decode_rows.astype(jnp.float32)
    cdf = jnp.cumsum(q, axis=0)[:-1] * rows[None, :]
    vec = jnp.concatenate([
        jnp.stack([dropped.astype(jnp.float32), n.astype(jnp.float32),
                   jnp.sum(rows)]), jnp.sum(cdf, axis=1)])
    return {"moe_dropped": dropped, "loop": vec, "exit_pdf": q.T}


# ---------------------------------------------------------------------------
# Prefill
# ---------------------------------------------------------------------------

def forward_prefill(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                    start_pos: jnp.ndarray, lengths: jnp.ndarray,
                    kv: KVCache, page_table: jnp.ndarray,
                    return_all_logits: bool = False,
                    mm_embeds: Optional[jnp.ndarray] = None,
                    mm_positions: Optional[jnp.ndarray] = None,
                    prompt_lp_targets: Optional[jnp.ndarray] = None,
                    return_stats: bool = False,
                    rope_pos: Optional[jnp.ndarray] = None,
                    plan: KernelPlan = KernelPlan(),
                    state_cols: Optional[jnp.ndarray] = None,
                    ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray], KVCache]:
    """Prefill ``tokens`` [B, T] (padded; true new-token counts in
    ``lengths``; nonzero ``start_pos`` = prefix-cache hit, those tokens are
    already resident in the cache).

    ``return_stats`` (static) appends a stats dict (``moe_dropped``:
    int32 capacity-dropped assignments summed over layers) as the final
    element — the serving engine's drop accounting; default off keeps the
    3-tuple contract for existing callers.

    ``mm_embeds`` [B, M, D] + ``mm_positions`` [B, M] splice multimodal
    (vision-encoder) embeddings over the token embeddings at the given
    window-relative positions (EPD prefill stage; pad positions ≥ T are
    dropped).

    ``rope_pos`` [B, 3, T] — explicit 3-D rope positions for mrope
    models (Qwen2-VL: image tokens rotate by (t, h, w) grid ids,
    decoupled from KV storage positions). None → streams broadcast from
    the storage positions (pure-text requests; equals standard rope).

    MLA models (DeepSeek-V2) take a dedicated path over the latent
    pool (``_mla_forward_prefill``); multimodal splice is not defined
    for them.

    ``plan`` (static; ops/plan.py ``KernelPlan``) is every choice the
    layer body makes, resolved once per engine; the default is the XLA
    reference, attend-then-scatter.

    ``plan.write_then_attend``: the round-5 "known residue" fix — the
    pool rides the layer scan as a CARRY and each layer writes its
    fresh window into the pool FIRST (aliased Pallas writer = the
    pool's first consumer), then attention reads everything — cached
    prefix AND the current window — from the pool. Kills the jit-call-
    boundary pool copies XLA inserts when an opaque attention call
    reads a buffer the post-scan writer aliases (~10-15 GB per prefill
    call at the bench shape).

    ``plan.ragged_rows``: the batch is a RAGGED MIX — rows may be
    prefill windows (lengths > 1) or single decode continuations
    (lengths = 1, start_pos = context − 1), assembled by the engine's
    one-dispatch interleaved step (``plan.mixed_program()``, which also
    sets write-then-attend — every row's new K/V must land in the pool
    before attention — and clears ``page_aligned``: decode rows start
    mid-page). Attention is the ragged Pallas kernel
    (ops/pallas/ragged_attention.py) where ``plan.decode_attn``;
    otherwise the pool-gather XLA reference below already handles
    arbitrary (start, length) rows.

    Returns (last_logits [B, V] fp32, all_logits [B, T, V] fp32 or None,
    kv'). ``return_all_logits`` (static) gates the full-prompt lm_head: at
    serving shapes a [B, T, V] fp32 tensor is gigabytes of HBM and a T×
    larger matmul, so by default only the last valid hidden state per
    sequence hits the head — all_logits exists for prompt-logprob requests.
    """
    if cfg.mla:
        assert mm_embeds is None, "MLA models have no multimodal splice"
        assert not plan.ragged_rows, \
            "MLA models have no ragged mixed-batch path"
        return _mla_forward_prefill(
            params, cfg, tokens, start_pos, lengths, kv, page_table,
            return_all_logits=return_all_logits,
            prompt_lp_targets=prompt_lp_targets,
            return_stats=return_stats, plan=plan)
    if cfg.layer_kinds is not None:
        assert mm_embeds is None and not plan.ragged_rows, \
            "a layer_kinds model has neither a multimodal splice nor a " \
            "ragged mixed-batch path"
        return _kinds_forward_prefill(
            params, cfg, tokens, start_pos, lengths, kv, page_table,
            return_all_logits=return_all_logits,
            prompt_lp_targets=prompt_lp_targets,
            return_stats=return_stats, plan=plan, state_cols=state_cols)
    k_pages, v_pages = kv
    write_then_attend = plan.write_then_attend
    x = _scale_embed(cfg, params["embed"][tokens]
                     .astype(jnp.dtype(cfg.dtype)))              # [B, T, D]
    if mm_embeds is not None:
        x = jax.vmap(
            lambda xb, eb, pb: xb.at[pb].set(
                eb.astype(xb.dtype), mode="drop"))(
            x, mm_embeds, mm_positions)
    positions = start_pos[:, None] + jnp.arange(tokens.shape[1],
                                                dtype=jnp.int32)[None, :]
    kv_lengths = start_pos + lengths                             # [B]
    tok_valid = (jnp.arange(tokens.shape[1], dtype=jnp.int32)[None, :]
                 < lengths[:, None])                             # [B, T]
    extras = _attn_extras(cfg)
    win_arr = _layer_windows(cfg)
    rope_arr = _layer_rope(cfg)

    def layer(carry, xs):
        if write_then_attend:
            x, kp_c, vp_c = carry
        else:
            x = carry
            kp_c, vp_c = k_pages, v_pages
        ro = None
        if win_arr is not None and rope_arr is not None:
            lp, li, w_l, ro = xs
        elif win_arr is not None:
            lp, li, w_l = xs
        elif rope_arr is not None:
            lp, li, ro = xs
            w_l = cfg.sliding_window or 0
        else:
            lp, li = xs
            w_l = cfg.sliding_window or 0
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(lp, cfg, h)
        if ro is not None:
            q = apply_rope_dynamic(q, positions, ro[0], ro[1])
            k = apply_rope_dynamic(k, positions, ro[0], ro[1])
        else:
            q = rope_for(cfg.rope_scaling, q, positions, cfg.rope_theta,
                         positions3=rope_pos)
            k = rope_for(cfg.rope_scaling, k, positions, cfg.rope_theta,
                         positions3=rope_pos)
        B, T = tokens.shape
        # The flash-prefill kernel needs the window to tile exactly into
        # pool pages (engine buckets are pow2 multiples of the page size
        # at serving shapes; odd test shapes take the XLA path).
        prefill_kernel = plan.prefill_attn and T % kp_c.shape[2] == 0
        if write_then_attend:
            # Write-then-attend: the window's fresh K/V lands in the
            # pool FIRST (the aliased writer is the pool's first
            # consumer inside the scan carry — no defensive copy), then
            # attention reads everything — cached prefix AND current
            # window — from the pool. No dual cached/fresh source, no
            # overlay.
            kp_c, vp_c = write_prefill_kv_layer(
                kp_c, vp_c, k, v, page_table, start_pos, lengths, li,
                plan)
            if plan.ragged_rows and plan.decode_attn:
                # No window/page alignment requirement: the ragged
                # layout reads everything through the page table.
                from xllm_service_tpu.ops.pallas import (
                    ragged_paged_attention_pallas)
                attn = ragged_paged_attention_pallas(
                    q, kp_c, vp_c, page_table, start_pos, lengths,
                    sliding_window=w_l, sinks=lp.get("sinks"),
                    logits_soft_cap=cfg.attn_logit_softcapping,
                    scale=extras.get("scale"), layer=li,
                    interpret=plan.interpret)
            elif prefill_kernel:
                from xllm_service_tpu.ops.pallas import (
                    paged_prefill_attention_pallas)
                attn = paged_prefill_attention_pallas(
                    q, None, None, kp_c, vp_c, page_table, start_pos,
                    lengths, sliding_window=w_l, sinks=lp.get("sinks"),
                    logits_soft_cap=cfg.attn_logit_softcapping,
                    scale=extras.get("scale"), layer=li, from_pool=True,
                    interpret=plan.interpret)
            else:
                kp = jax.lax.dynamic_index_in_dim(kp_c, li, axis=0,
                                                  keepdims=False)
                vp = jax.lax.dynamic_index_in_dim(vp_c, li, axis=0,
                                                  keepdims=False)
                # Pool already holds the window — gather, no overlay.
                attn = mha_prefill_auto(
                    q, gather_pages(kp, page_table),
                    gather_pages(vp, page_table), kv_lengths, start_pos,
                    sliding_window=w_l, sinks=lp.get("sinks"), **extras)
        elif prefill_kernel:
            # Attend against cache (prefix-cache hits) + this step's
            # fresh K/V; the pool itself is NOT written here: emitting
            # updated pools as scan ys would rewrite the whole pool per
            # call — the fresh rows come out as small ys instead and
            # land in one scatter after the scan. The gated Pallas
            # kernel streams pool pages + fresh blocks from the FULL 5D
            # pools (the traced layer index joins the page in its DMA
            # indices — a per-layer slice feeding a custom call is
            # MATERIALIZED, the round-5 conviction). The kernel
            # implements the full model-delta surface — windows (static
            # or traced per-layer), Gemma soft-cap and scale, GPT-OSS
            # sinks — so SWA families are no longer trace-time-bypassed
            # to the gather path (round-4 verdict).
            from xllm_service_tpu.ops.pallas import (
                paged_prefill_attention_pallas)
            attn = paged_prefill_attention_pallas(
                q, k, v, kp_c, vp_c, page_table, start_pos,
                lengths, sliding_window=w_l, sinks=lp.get("sinks"),
                logits_soft_cap=cfg.attn_logit_softcapping,
                scale=extras.get("scale"), layer=li,
                interpret=plan.interpret)
        else:
            # The XLA reference slices locally (its gather fuses) then
            # overlays the not-yet-written fresh window.
            kp = jax.lax.dynamic_index_in_dim(kp_c, li, axis=0,
                                              keepdims=False)
            vp = jax.lax.dynamic_index_in_dim(vp_c, li, axis=0,
                                              keepdims=False)
            k_all = overlay_fresh_kv(gather_pages(kp, page_table), k,
                                     start_pos)
            v_all = overlay_fresh_kv(gather_pages(vp, page_table), v,
                                     start_pos)
            attn = mha_prefill_auto(q, k_all, v_all, kv_lengths, start_pos,
                                    sliding_window=w_l,
                                    sinks=lp.get("sinks"), **extras)
        a = attn.reshape(B, T, -1) @ lp["o_proj"]
        if "o_bias" in lp:
            a = a + lp["o_bias"]
        if cfg.four_norm_block:
            # The four-norm block: post-norms apply to the SUBLAYER
            # OUTPUT before the residual add.
            x = x + rms_norm(a, lp["post_norm"], cfg.rms_norm_eps)
            h = rms_norm(x, lp["pre_ff_norm"], cfg.rms_norm_eps)
            m, dropped = _mlp(lp, cfg, h, valid=tok_valid)
            x = x + rms_norm(m, lp["post_ff_norm"], cfg.rms_norm_eps)
        else:
            x = x + a
            h = rms_norm(x, lp["post_norm"], cfg.rms_norm_eps)
            m, dropped = _mlp(lp, cfg, h, valid=tok_valid)
            x = x + m
        if write_then_attend:
            return (x, kp_c, vp_c), dropped
        return x, (k, v, dropped)

    def last_rows(x):
        last_idx = jnp.maximum(lengths - 1, 0)
        return jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]

    def scan_layers(carry, li_arr):
        if win_arr is not None and rope_arr is not None:
            xs = (params["layers"], li_arr, win_arr, rope_arr)
        elif win_arr is not None:
            xs = (params["layers"], li_arr, win_arr)
        elif rope_arr is not None:
            xs = (params["layers"], li_arr, rope_arr)
        else:
            xs = (params["layers"], li_arr)
        return jax.lax.scan(layer, carry, xs)

    def final_norm(x):
        return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)

    if write_then_attend:
        (x, k_pages, v_pages), dropped_l, loop = _run_passes(
            cfg, params, scan_layers,
            lambda c: (final_norm(c[0]),) + c[1:],
            (x, k_pages, v_pages), last_rows)
    else:
        x, (k_new, v_new, dropped_l), loop = _run_passes(
            cfg, params, scan_layers, final_norm, x, last_rows)
        k_pages, v_pages = write_prefill_kv_all_layers(
            k_pages, v_pages, k_new, v_new, page_table, start_pos,
            lengths, plan)
    if loop is None:
        x = final_norm(x)                   # the one pass ends here
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    last_logits = _head_logits(cfg, last_rows(x), head)          # [B, V]
    all_logits = _head_logits(cfg, x, head) if return_all_logits else None
    outs = [last_logits, all_logits, (k_pages, v_pages)]
    if prompt_lp_targets is not None:
        # 4th element ONLY on the echo+logprobs path: existing callers
        # (and the driver's entry contract) unpack three.
        outs.append(_prompt_logprobs(x, head, prompt_lp_targets,
                                     cap=cfg.final_logit_softcapping))
    if return_stats:
        outs.append(_dense_stats(jnp.sum(dropped_l), loop,
                                 jnp.zeros(lengths.shape, bool)))
    return tuple(outs)


def _prompt_logprobs(x: jnp.ndarray, head: jnp.ndarray,
                     targets: jnp.ndarray,
                     chunk: int = 128, cap: float = 0.0) -> jnp.ndarray:
    """logprob of ``targets[b, t]`` under the distribution predicted at
    position ``t`` — the completion API's ``echo`` + ``logprobs`` prompt
    scoring. Chunked over T so the [B, c, V] logits block (not the full
    [B, T, V]) is the peak intermediate."""
    B, T, D = x.shape
    c = math.gcd(T, min(chunk, T))
    xc = x.reshape(B, T // c, c, D).transpose(1, 0, 2, 3)     # [nc,B,c,D]
    tc = targets.reshape(B, T // c, c).transpose(1, 0, 2)     # [nc,B,c]

    def one(args):
        xb, tb = args                                  # [B, c, D], [B, c]
        logits = (xb @ head).astype(jnp.float32)       # [B, c, V]
        if cap > 0.0:
            logits = cap * jnp.tanh(logits / cap)
        lse = jax.scipy.special.logsumexp(logits, axis=-1)
        tgt = jnp.take_along_axis(
            logits, tb[..., None].astype(jnp.int32), axis=-1)[..., 0]
        return tgt - lse                               # [B, c]

    out = jax.lax.map(one, (xc, tc))                   # [nc, B, c]
    return out.transpose(1, 0, 2).reshape(B, T)


def forward_prefill_ring(params: Params, cfg: ModelConfig,
                         tokens: jnp.ndarray, lengths: jnp.ndarray,
                         kv: KVCache, page_table: jnp.ndarray, mesh,
                         axis_name: str = "sp",
                         return_stats: bool = False,
                         ) -> Tuple[jnp.ndarray, Optional[jnp.ndarray],
                                    KVCache]:
    """Sequence-parallel long-context prefill: exact causal attention with
    the sequence axis sharded over the mesh's ``sp`` axis via ring attention
    (parallel/ring.py — KV blocks rotate over ``ppermute``, flash-style
    accumulator, O(T/sp) attention memory per device).

    Restrictions vs ``forward_prefill`` (the engine falls back to chunked
    windows otherwise): no cached prefix (start_pos == 0 — the sequence is
    entirely fresh), no multimodal splice, and T must divide by the sp size.
    The serving engine dispatches here when a prompt exceeds the largest
    single-chip bucket and the whole prompt fits one ring window
    (runtime/engine.py _run_prefill; round-1 left ring attention
    unintegrated, round-1 verdict, weak #3).
    """
    from xllm_service_tpu.parallel.mesh import AXIS_TP
    from xllm_service_tpu.parallel.ring import ring_attention_sharded

    if cfg.sliding_window or cfg.four_norm_block or cfg.mla or cfg.gptoss \
            or cfg.layer_kinds is not None or cfg.looped:
        # Ring rotation assumes full causal reach and the plain llama
        # layer body run once; SWA/four-norm/MLA/GPT-OSS/looped long
        # prompts take the chunked-window path (whose flash fold skips
        # out-of-window chunks, so the work is O(T·W) there anyway).
        raise NotImplementedError(
            "ring prefill implements neither sliding-window masks, the "
            "four-norm layer body, latent attention, attention sinks "
            "nor layer passes")

    k_pages, v_pages = kv
    B, T = tokens.shape
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))     # [B, T, D]
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None],
                                 (B, T))

    # Heads shard over tp only when BOTH head counts divide it (the GQA
    # head grouping inside the ring block must stay aligned); otherwise
    # heads are replicated inside the shard_map island, mirroring
    # kv_cache_pspec's replication rule.
    tp = mesh.shape.get(AXIS_TP, 1)
    head_axis = (AXIS_TP if tp > 1 and cfg.num_heads % tp == 0
                 and cfg.num_kv_heads % tp == 0 else None)
    _ring = ring_attention_sharded(mesh, axis_name, head_axis)

    tok_valid = (jnp.arange(T, dtype=jnp.int32)[None, :]
                 < lengths[:, None])                             # [B, T]

    def layer(x, lp):
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(lp, cfg, h)
        q = apply_rope(q, positions, cfg.rope_theta, cfg.rope_scaling)
        k = apply_rope(k, positions, cfg.rope_theta, cfg.rope_scaling)
        attn = _ring(q, k, v, lengths)
        x = x + attn.reshape(B, T, -1) @ lp["o_proj"]
        h = rms_norm(x, lp["post_norm"], cfg.rms_norm_eps)
        m, dropped = _mlp(lp, cfg, h, valid=tok_valid)
        x = x + m
        return x, (k, v, dropped)

    x, (k_new, v_new, dropped_l) = jax.lax.scan(layer, x, params["layers"])
    # A ring program exists only on a mesh, where every plan is the XLA
    # reference: the scatter, never the in-place writer.
    k_pages, v_pages = write_prefill_kv_all_layers_xla(
        k_pages, v_pages, k_new, v_new, page_table,
        jnp.zeros((B,), jnp.int32), lengths)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    last_idx = jnp.maximum(lengths - 1, 0)
    last_x = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
    last_logits = (last_x @ head).astype(jnp.float32)
    if return_stats:
        return last_logits, None, (k_pages, v_pages), \
            {"moe_dropped": jnp.sum(dropped_l)}
    return last_logits, None, (k_pages, v_pages)


# ---------------------------------------------------------------------------
# Embeddings (net-new capability: the reference's /v1/embeddings returns
# "not support", http_service/service.cpp:492)
# ---------------------------------------------------------------------------

def forward_embedding(params: Params, cfg: ModelConfig,
                      tokens: jnp.ndarray, lengths: jnp.ndarray
                      ) -> jnp.ndarray:
    """Sequence embeddings: causal forward (no KV cache), masked mean-pool
    of the final hidden states, L2-normalized. tokens [B, T] padded,
    lengths [B] → [B, hidden] float32."""
    if cfg.mla or cfg.layer_kinds is not None or cfg.looped:
        raise NotImplementedError(
            "/v1/embeddings is not implemented for MLA models, for "
            "models whose layers differ in kind, nor for a layer loop")
    B, T = tokens.shape
    x = _scale_embed(cfg, params["embed"][tokens]
                     .astype(jnp.dtype(cfg.dtype)))
    positions = jnp.broadcast_to(jnp.arange(T, dtype=jnp.int32)[None], (B, T))

    tok_valid = (jnp.arange(T, dtype=jnp.int32)[None, :]
                 < lengths[:, None])                             # [B, T]
    extras = _attn_extras(cfg)
    win_arr = _layer_windows(cfg)
    rope_arr = _layer_rope(cfg)

    def layer(x, xs):
        ro = None
        if win_arr is not None and rope_arr is not None:
            lp, w_l, ro = xs
        elif win_arr is not None:
            lp, w_l = xs
        elif rope_arr is not None:
            lp, ro = xs
            w_l = cfg.sliding_window or 0
        else:
            lp = xs
            w_l = cfg.sliding_window or 0
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(lp, cfg, h)
        if ro is not None:
            q = apply_rope_dynamic(q, positions, ro[0], ro[1])
            k = apply_rope_dynamic(k, positions, ro[0], ro[1])
        else:
            q = rope_for(cfg.rope_scaling, q, positions, cfg.rope_theta)
            k = rope_for(cfg.rope_scaling, k, positions, cfg.rope_theta)
        attn = mha_prefill(q, k, v, lengths,
                           jnp.zeros((B,), jnp.int32),
                           sliding_window=w_l,
                           sinks=lp.get("sinks"), **extras)
        a = attn.reshape(B, T, -1) @ lp["o_proj"]
        if "o_bias" in lp:
            a = a + lp["o_bias"]
        if cfg.four_norm_block:
            x = x + rms_norm(a, lp["post_norm"], cfg.rms_norm_eps)
            h = rms_norm(x, lp["pre_ff_norm"], cfg.rms_norm_eps)
            x = x + rms_norm(_mlp(lp, cfg, h, valid=tok_valid)[0],
                             lp["post_ff_norm"], cfg.rms_norm_eps)
        else:
            x = x + a
            h = rms_norm(x, lp["post_norm"], cfg.rms_norm_eps)
            x = x + _mlp(lp, cfg, h, valid=tok_valid)[0]
        return x, None

    if win_arr is not None and rope_arr is not None:
        xs = (params["layers"], win_arr, rope_arr)
    elif win_arr is not None:
        xs = (params["layers"], win_arr)
    elif rope_arr is not None:
        xs = (params["layers"], rope_arr)
    else:
        xs = params["layers"]
    x, _ = jax.lax.scan(layer, x, xs)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps).astype(
        jnp.float32)
    mask = (jnp.arange(T, dtype=jnp.int32)[None] <
            lengths[:, None]).astype(jnp.float32)
    pooled = jnp.sum(x * mask[..., None], axis=1) / \
        jnp.maximum(jnp.sum(mask, axis=1, keepdims=True), 1.0)
    return pooled / jnp.maximum(
        jnp.linalg.norm(pooled, axis=-1, keepdims=True), 1e-12)


# ---------------------------------------------------------------------------
# Decode
# ---------------------------------------------------------------------------

def forward_decode(params: Params, cfg: ModelConfig, tokens: jnp.ndarray,
                   positions: jnp.ndarray, active: jnp.ndarray,
                   kv: KVCache, page_table: jnp.ndarray,
                   return_stats: bool = False,
                   rope_delta: Optional[jnp.ndarray] = None,
                   plan: KernelPlan = KernelPlan(),
                   state_rows: Optional[jnp.ndarray] = None,
                   ) -> Tuple[jnp.ndarray, KVCache]:
    """One decode step for ``tokens`` [B] at ``positions`` [B]
    (``active`` [B] bool masks empty batch slots). Returns
    (logits [B, V] fp32, kv'); with ``return_stats`` (static) a trailing
    stats dict (``moe_dropped``) is appended.

    ``rope_delta`` [B] — mrope models only: per-sequence offset between
    the rope position of a generated token and its KV storage position
    (images compress T·H·W patch tokens into a max(t,h,w)-sized rope
    span, so post-image rope positions trail storage positions).

    ``plan`` (static; ops/plan.py ``KernelPlan``). With
    ``plan.write_then_attend`` the pool rides the layer scan as a
    carry; each layer writes the current token's K/V in place (aliased
    Pallas writer) BEFORE attending, and attention reads the pool alone
    — the ``k_cur``/``v_cur`` plumbing disappears, and so do the
    jit-call-boundary pool copies around the post-scan scatter."""
    if cfg.mla:
        return _mla_forward_decode(params, cfg, tokens, positions,
                                   active, kv, page_table,
                                   return_stats=return_stats, plan=plan)
    if cfg.layer_kinds is not None:
        return _kinds_forward_decode(params, cfg, tokens, positions,
                                     active, kv, page_table,
                                     return_stats=return_stats, plan=plan,
                                     state_rows=state_rows)
    k_pages, v_pages = kv
    write_then_attend = plan.write_then_attend
    x = _scale_embed(cfg, params["embed"][tokens[:, None]]
                     .astype(jnp.dtype(cfg.dtype)))              # [B,1,D]
    cache_lens = jnp.where(active, positions, 0)   # tokens already written
    extras = _attn_extras(cfg)
    win_arr = _layer_windows(cfg)
    rope_arr = _layer_rope(cfg)

    # The attention dispatch gets the FULL 5D pools + a traced layer
    # scalar: where plan.decode_attn the kernel's page DMAs index
    # [L, P, ps, Hkv, D] directly (round-5: a per-layer pool slice
    # feeding a custom call is MATERIALIZED — 134 MB x 2 pools x layers
    # per step); the XLA gather fallback slices per layer, which fuses.
    def layer(carry, xs):
        if write_then_attend:
            x, kp_c, vp_c = carry
        else:
            x = carry
            kp_c, vp_c = k_pages, v_pages
        ro = None
        if win_arr is not None and rope_arr is not None:
            lp, li, w_l, ro = xs
        elif win_arr is not None:
            lp, li, w_l = xs
        elif rope_arr is not None:
            lp, li, ro = xs
            w_l = cfg.sliding_window or 0
        else:
            lp, li = xs
            w_l = cfg.sliding_window or 0
        h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
        q, k, v = _qkv(lp, cfg, h)                               # [B,1,H,Dh]
        pos2 = positions[:, None]
        if ro is not None:
            q = apply_rope_dynamic(q, pos2, ro[0], ro[1])
            k = apply_rope_dynamic(k, pos2, ro[0], ro[1])
        else:
            rp3 = None
            if rope_delta is not None:
                rp3 = jnp.broadcast_to(
                    (positions + rope_delta)[:, None, None],
                    (positions.shape[0], 3, 1))
            q = rope_for(cfg.rope_scaling, q, pos2, cfg.rope_theta,
                         positions3=rp3)
            k = rope_for(cfg.rope_scaling, k, pos2, cfg.rope_theta,
                         positions3=rp3)
        if write_then_attend:
            # Write-then-attend: the current token's K/V goes into the
            # pool FIRST (per-layer aliased write; the writer is the
            # carried pool's first consumer), then attention reads the
            # pool alone with context INCLUDING the current token — no
            # k_cur/v_cur plumbing.
            kp_c, vp_c = write_decode_kv_layer(
                kp_c, vp_c, k[:, 0], v[:, 0], page_table, positions,
                active, li, plan)
            attn = paged_decode_attention_auto(
                q[:, 0], kp_c, vp_c, page_table,
                jnp.where(active, positions + 1, 0), plan,
                sliding_window=w_l, sinks=lp.get("sinks"),
                layer=li, **extras)                              # [B,Hq,Dh]
        else:
            # The current token's K/V stays in-registers for attention;
            # the pool write happens once for all layers after the scan
            # (carrying the pool as scan ys would rewrite the whole pool
            # per step).
            attn = paged_decode_attention_current_auto(
                q[:, 0], kp_c, vp_c, page_table, cache_lens,
                k[:, 0], v[:, 0], plan,
                sliding_window=w_l, sinks=lp.get("sinks"),
                layer=li, **extras)                              # [B,Hq,Dh]
        B = tokens.shape[0]
        a = attn.reshape(B, 1, -1) @ lp["o_proj"]
        if "o_bias" in lp:
            a = a + lp["o_bias"]
        if cfg.four_norm_block:
            x = x + rms_norm(a, lp["post_norm"], cfg.rms_norm_eps)
            h = rms_norm(x, lp["pre_ff_norm"], cfg.rms_norm_eps)
            m, dropped = _mlp(lp, cfg, h, valid=active[:, None])
            x = x + rms_norm(m, lp["post_ff_norm"], cfg.rms_norm_eps)
        else:
            x = x + a
            h = rms_norm(x, lp["post_norm"], cfg.rms_norm_eps)
            m, dropped = _mlp(lp, cfg, h, valid=active[:, None])
            x = x + m
        if write_then_attend:
            return (x, kp_c, vp_c), dropped
        return x, (k[:, 0], v[:, 0], dropped)

    def scan_layers(carry, li_arr):
        if win_arr is not None and rope_arr is not None:
            xs = (params["layers"], li_arr, win_arr, rope_arr)
        elif win_arr is not None:
            xs = (params["layers"], li_arr, win_arr)
        elif rope_arr is not None:
            xs = (params["layers"], li_arr, rope_arr)
        else:
            xs = (params["layers"], li_arr)
        return jax.lax.scan(layer, carry, xs)

    def final_norm(x):
        return rms_norm(x, params["final_norm"], cfg.rms_norm_eps)

    if write_then_attend:
        (x, k_pages, v_pages), dropped_l, loop = _run_passes(
            cfg, params, scan_layers,
            lambda c: (final_norm(c[0]),) + c[1:],
            (x, k_pages, v_pages), lambda x: x[:, 0])
    else:
        x, (k_new, v_new, dropped_l), loop = _run_passes(
            cfg, params, scan_layers, final_norm, x, lambda x: x[:, 0])
        k_pages, v_pages = write_decode_kv_all_layers(
            k_pages, v_pages, k_new, v_new, page_table, positions, active,
            plan)
    if loop is None:
        x = final_norm(x)                   # the one pass ends here
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = _head_logits(cfg, x[:, 0], head)                    # [B, V]
    if return_stats:
        return logits, (k_pages, v_pages), \
            _dense_stats(jnp.sum(dropped_l), loop, active)
    return logits, (k_pages, v_pages)


# ---------------------------------------------------------------------------
# DeepSeek-V2 multi-head latent attention (MLA)
#
# The cache stores one LATENT row per token — [kv_lora_rank (post
# kv_a_layernorm) ‖ rotated k_pe] — in the standard paged pool with a
# single KV "head" (cfg.kv_cache_{heads,dim}), so every page-table,
# migration, and trimming mechanism applies unchanged. The kv_b
# up-projections are ABSORBED: scores = (W_bk^T q_nope)·c + q_pe·k_pe and
# out_h = W_bv (Σ p·c), which is exactly HF's per-head math by
# associativity but reads r+rope bytes per token instead of
# Hq·(qk_head+v_head). DeepSeek's rope sub-head uses the adjacent-pair
# (complex) rotation — ops/rope.apply_rope_interleaved.
# (HF oracle: transformers deepseek_v2 — DeepseekV2Attention,
# DeepseekV2MoEGate greedy/group_limited_greedy, shared experts.)
# ---------------------------------------------------------------------------

def _init_mla_params(cfg: ModelConfig, key: jax.Array,
                     dtype: Optional[jnp.dtype]) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    D, Hq = cfg.hidden_size, cfg.num_heads
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope, vd = cfg.qk_nope_head_dim, cfg.v_head_dim
    keys = iter(jax.random.split(key, 64))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dtype)

    def attn_block(L):
        blk = {
            "input_norm": jnp.ones((L, D), dtype),
            "kv_a": w((L, D, r + rope), D),
            "kv_a_norm": jnp.ones((L, r), dtype),
            "kv_b_k": w((L, Hq, nope, r), r),
            "kv_b_v": w((L, Hq, vd, r), r),
            "o_proj": w((L, Hq * vd, D), Hq * vd),
            "post_norm": jnp.ones((L, D), dtype),
        }
        if cfg.q_lora_rank:
            blk["q_a"] = w((L, D, cfg.q_lora_rank), D)
            blk["q_a_norm"] = jnp.ones((L, cfg.q_lora_rank), dtype)
            blk["q_b"] = w((L, cfg.q_lora_rank, Hq * cfg.qk_head_dim),
                           cfg.q_lora_rank)
        else:
            blk["q_proj"] = w((L, D, Hq * cfg.qk_head_dim), D)
        return blk

    k_dense = cfg.first_k_dense_replace if cfg.is_moe else cfg.num_layers
    n_moe = cfg.num_layers - k_dense
    dense = attn_block(k_dense)
    dense["gate_proj"] = w((k_dense, D, cfg.intermediate_size), D)
    dense["up_proj"] = w((k_dense, D, cfg.intermediate_size), D)
    dense["down_proj"] = w((k_dense, cfg.intermediate_size, D),
                           cfg.intermediate_size)
    params: Params = {
        "embed": w((cfg.vocab_size, D), D),
        "layers": dense,
        "final_norm": jnp.ones((D,), dtype),
    }
    if n_moe:
        Fe = cfg.moe_intermediate_size or cfg.intermediate_size
        E = cfg.num_experts
        moe = attn_block(n_moe)
        moe["router"] = w((n_moe, D, E), D)
        if cfg.moe_scoring == "sigmoid":
            moe["router_bias"] = jnp.zeros((n_moe, E), jnp.float32)
        moe["gate_proj"] = w((n_moe, E, D, Fe), D)
        moe["up_proj"] = w((n_moe, E, D, Fe), D)
        moe["down_proj"] = w((n_moe, E, Fe, D), Fe)
        if cfg.n_shared_experts:
            Fs = Fe * cfg.n_shared_experts
            moe["shared_gate"] = w((n_moe, D, Fs), D)
            moe["shared_up"] = w((n_moe, D, Fs), D)
            moe["shared_down"] = w((n_moe, Fs, D), Fs)
        params["layers_moe"] = moe
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((D, cfg.vocab_size), D)
    return params


def _deepseek_gate(cfg: ModelConfig, x: jnp.ndarray,
                   router_w: jnp.ndarray,
                   bias: Optional[jnp.ndarray] = None
                   ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """The experts each token takes and their weights AFTER DeepSeek's
    selection rules: ``(topi [.., k] int32, topw [.., k] float32)``. The
    one place the choice is made.

    V2 (softmax scoring): softmax over fp32 logits; group-limited
    routing zeroes every expert outside the top ``topk_group`` of
    ``n_group`` groups (group score = max member score); top-k selected
    weights scale by routed_scaling_factor, no normalization.

    V3 (sigmoid scoring): sigmoid scores; SELECTION uses scores + the
    learned per-expert ``e_score_correction_bias`` with top-2-SUM group
    scores, but the combine WEIGHTS are the raw sigmoid scores of the
    chosen experts, optionally normalized (norm_topk_prob), then scaled.
    (HF DeepseekV2MoEGate / DeepseekV3TopkRouter.)"""
    logits = (x @ router_w).astype(jnp.float32)
    E = logits.shape[-1]
    sigmoid = cfg.moe_scoring == "sigmoid"
    scores = jax.nn.sigmoid(logits) if sigmoid \
        else jax.nn.softmax(logits, axis=-1)
    choice = scores + bias if (sigmoid and bias is not None) else scores
    if cfg.topk_method == "group_limited_greedy":
        G = cfg.n_group
        grouped = choice.reshape(*choice.shape[:-1], G, E // G)
        if sigmoid:
            g2, _ = jax.lax.top_k(grouped, 2)
            gs = jnp.sum(g2, axis=-1)                        # top-2 sum
        else:
            gs = grouped.max(axis=-1)
        _, gidx = jax.lax.top_k(gs, cfg.topk_group)          # [.., tg]
        gmask = jnp.sum(jax.nn.one_hot(gidx, G, dtype=choice.dtype),
                        axis=-2)                             # [.., G]
        choice = jnp.where(jnp.repeat(gmask, E // G, axis=-1) > 0,
                           choice, 0.0)
    _, topi = jax.lax.top_k(choice, cfg.num_experts_per_tok)
    # V3 combines with the RAW sigmoid scores (bias shapes choice only);
    # V2 combines with the masked selection values themselves.
    topw = jnp.take_along_axis(scores if sigmoid else choice, topi,
                               axis=-1)
    if sigmoid and cfg.norm_topk_prob:
        topw = topw / (jnp.sum(topw, axis=-1, keepdims=True)
                       + cfg.moe_gate_eps)
    return topi.astype(jnp.int32), topw * cfg.routed_scaling_factor


def moe_stats_shape(cfg: ModelConfig) -> Tuple[int, ...]:
    """Shape of what a step's sparse layers count: the dropless layers
    count what they routed (``expert.MOE_STATS``, summed over layers;
    element 0 is the dropped count every family reports), every other
    model has the one scalar."""
    from xllm_service_tpu.parallel.expert import MOE_STATS
    if cfg.looped:
        return (cfg.total_ut_steps + 2,)        # ``_dense_stats``' vector
    return (len(MOE_STATS),) if cfg.dropless_experts else ()


def step_stats_zeros(cfg: ModelConfig) -> jnp.ndarray:
    """What a burst of steps starts its sum of ``step_moe_stats`` from:
    int32 counts, but a looped model's float32 vector."""
    return jnp.zeros(moe_stats_shape(cfg),
                     jnp.float32 if cfg.looped else jnp.int32)


def _moe_stats_dict(moe_stats: jnp.ndarray) -> Dict[str, jnp.ndarray]:
    """The ``return_stats`` dict of the latent forwards: ``moe_dropped``
    as every family gives it, and the whole vector under ``moe``."""
    if moe_stats.ndim == 0:
        return {"moe_dropped": moe_stats}
    return {"moe_dropped": moe_stats[0], "moe": moe_stats}


def step_moe_stats(stats: Dict[str, jnp.ndarray]) -> jnp.ndarray:
    """What a step program hands the engine of ``return_stats``: the
    ``expert.MOE_STATS`` vector where the model counts it, a looped
    model's vector of passes and exit probabilities (``_dense_stats``),
    else the dropped scalar (``moe_stats_shape``)."""
    return stats.get("loop", stats.get("moe", stats["moe_dropped"]))


# The routed experts' weights: the layer scan hands them on WHOLE (a
# closure, with the layer's index) and never as its per-layer slice.
_EXPERT_LEAVES = ("gate_proj", "up_proj", "down_proj")


def _split_experts(stack: Dict[str, jnp.ndarray]):
    """A sparse stack as (what the scan slices, the experts' stacks)."""
    return ({k: v for k, v in stack.items() if k not in _EXPERT_LEAVES},
            {k: stack[k] for k in _EXPERT_LEAVES})


def _dropless_moe_mlp(cfg: ModelConfig, lp: Dict[str, jnp.ndarray],
                      experts: Dict[str, jnp.ndarray], layer: jnp.ndarray,
                      x: jnp.ndarray, valid: Optional[jnp.ndarray] = None,
                      plan: KernelPlan = KernelPlan()
                      ) -> Tuple[jnp.ndarray, jnp.ndarray]:
    """Routed experts + the always-on shared experts of sparse layer
    ``layer`` (its index in the ``experts`` stacks); returns
    ``(out [B, T, D], stats)``. The DeepSeek gate chooses; the dropless
    layer (parallel/expert.py) computes exactly what it chose, whatever
    ``moe_capacity_factor`` says: no buckets on this path, nothing
    dropped, and ``stats`` counts it."""
    from xllm_service_tpu.parallel.expert import dropless_moe
    B, T, D = x.shape
    topi, topw = _deepseek_gate(cfg, x, lp["router"],
                                lp.get("router_bias"))       # [B, T, k]
    k = topi.shape[-1]
    vf = (jnp.ones((B * T,), bool) if valid is None
          else jnp.broadcast_to(valid, (B, T)).reshape(B * T))
    routed, stats = dropless_moe(
        x.reshape(B * T, D), topi.reshape(B * T, k),
        topw.reshape(B * T, k), vf, experts["gate_proj"],
        experts["up_proj"], experts["down_proj"], layer=layer,
        kernel=plan.expert_gmm, interpret=plan.interpret)
    shared = (jax.nn.silu(x @ lp["shared_gate"]) * (x @ lp["shared_up"])) \
        @ lp["shared_down"] if "shared_gate" in lp else 0.0
    return routed.reshape(B, T, D) + shared, stats


def _mla_qkv(cfg: ModelConfig, lp, h, positions):
    """Absorbed-query and latent-row computation for one layer.

    Returns (q_tilde [B, T, Hq, r+rope], latent [B, T, 1, r+rope]):
    q_tilde = [W_bk^T q_nope ‖ rope(q_pe)], latent = [c_hat ‖ rope(k_pe)].
    """
    from xllm_service_tpu.ops.rope import (apply_rope,
                                           apply_rope_interleaved)

    rope_fn = apply_rope_interleaved if cfg.rope_interleave else apply_rope
    B, T, _ = h.shape
    Hq = cfg.num_heads
    r, rope = cfg.kv_lora_rank, cfg.qk_rope_head_dim
    nope = cfg.qk_nope_head_dim
    if cfg.q_lora_rank:
        q = rms_norm(h @ lp["q_a"], lp["q_a_norm"], cfg.rms_norm_eps) \
            @ lp["q_b"]
    else:
        q = h @ lp["q_proj"]
    # q_b's slice and transposed copy a layer: the census found the fold
    # here too (PERF.md, PR 44).
    q = _pin(_pin(q).reshape(B, T, Hq, cfg.qk_head_dim))
    q_nope, q_pe = q[..., :nope], q[..., nope:]
    q_pe = rope_fn(q_pe, positions, cfg.rope_theta, cfg.rope_scaling)
    # Absorb the key up-projection into the query side.
    q_eff = jnp.einsum("bthn,hnr->bthr", q_nope, lp["kv_b_k"])
    q_tilde = jnp.concatenate([q_eff, q_pe], axis=-1)        # [B,T,Hq,r+rope]

    ckv = h @ lp["kv_a"]                                     # [B,T,r+rope]
    c_hat = rms_norm(ckv[..., :r], lp["kv_a_norm"], cfg.rms_norm_eps)
    k_pe = rope_fn(ckv[..., r:], positions, cfg.rope_theta,
                   cfg.rope_scaling)
    latent = jnp.concatenate([c_hat, k_pe], axis=-1)[:, :, None, :]
    return q_tilde, latent


def _mla_out(cfg: ModelConfig, lp, attn: jnp.ndarray) -> jnp.ndarray:
    """attn [..., Hq, r+rope] → absorbed value up-projection → o_proj."""
    o_lat = attn[..., :cfg.kv_lora_rank]                     # [...,Hq,r]
    o = jnp.einsum("...hr,hvr->...hv", o_lat, lp["kv_b_v"])
    return o.reshape(*o.shape[:-2], -1) @ lp["o_proj"]


def _mla_scale(cfg: ModelConfig) -> float:
    scale = cfg.qk_head_dim ** -0.5
    rs = cfg.rope_scaling
    if cfg.mla_yarn_mscale and rs is not None and rs[0] == "yarn":
        # DeepSeek folds yarn's mscale into the softmax scale (squared
        # — query and key sides), on top of the rope module's cos/sin
        # attention factor, whenever the checkpoint ships a nonzero
        # mscale_all_dim (real V2 and V3 both do; HF's in-tree V2 port
        # omits the factor — config.py keys the flag on the checkpoint).
        factor, msa = rs[1], rs[7] if len(rs) > 7 else 0.0
        if msa and factor > 1.0:
            m = 0.1 * msa * math.log(factor) + 1.0
            scale = scale * m * m
    return scale


def _mla_forward_prefill(params: Params, cfg: ModelConfig,
                         tokens: jnp.ndarray, start_pos: jnp.ndarray,
                         lengths: jnp.ndarray, kv: KVCache,
                         page_table: jnp.ndarray,
                         return_all_logits: bool = False,
                         prompt_lp_targets: Optional[jnp.ndarray] = None,
                         return_stats: bool = False,
                         plan: KernelPlan = KernelPlan()):
    k_pages, = kv
    write_then_attend = plan.write_then_attend
    L_dense = params["layers"]["input_norm"].shape[0]
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    positions = start_pos[:, None] + jnp.arange(tokens.shape[1],
                                                dtype=jnp.int32)[None, :]
    kv_lengths = start_pos + lengths
    B, T = tokens.shape
    tok_valid = (jnp.arange(T, dtype=jnp.int32)[None, :]
                 < lengths[:, None])                             # [B, T]

    def body(moe: bool):
        def layer(carry, xs):
            if write_then_attend:
                x, kp_full = carry
                lp, li = xs
                h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
                q_t, latent = _mla_qkv(cfg, lp, h, positions)
                # Write the latent window first (the one pool: its row
                # is key and value both), then attend from the pool: no
                # overlay.
                kp_full, = write_prefill_kv_layer_xla(
                    kp_full, None, latent, None,
                    page_table, start_pos, lengths, li)
                kp = jax.lax.dynamic_index_in_dim(
                    kp_full, li, axis=0, keepdims=False)
                lat_all = gather_pages(kp, page_table)
                attn = mha_prefill_auto(q_t, lat_all, lat_all,
                                        kv_lengths, start_pos,
                                        scale=_mla_scale(cfg))
            else:
                x, = carry
                lp, li, kp = xs
                h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
                q_t, latent = _mla_qkv(cfg, lp, h, positions)
                lat_all = overlay_fresh_kv(
                    gather_pages(kp, page_table), latent, start_pos)
                attn = mha_prefill_auto(q_t, lat_all, lat_all,
                                        kv_lengths, start_pos,
                                        scale=_mla_scale(cfg))
            x = x + _mla_out(cfg, lp, attn)
            h = rms_norm(x, lp["post_norm"], cfg.rms_norm_eps)
            stats = None
            if moe:
                y, stats = _dropless_moe_mlp(
                    cfg, lp, experts, li - L_dense, h, valid=tok_valid,
                    plan=plan)
                x = x + y
            else:
                x = x + (jax.nn.silu(h @ lp["gate_proj"])
                         * (h @ lp["up_proj"])) @ lp["down_proj"]
            if write_then_attend:
                return (x, kp_full), stats
            return (x,), (latent, stats)
        return layer

    moe_stats = jnp.zeros(moe_stats_shape(cfg), jnp.int32)
    li_d = jnp.arange(L_dense, dtype=jnp.int32)
    if "layers_moe" in params:
        sparse, experts = _split_experts(params["layers_moe"])
        li_m = L_dense + jnp.arange(sparse["input_norm"].shape[0],
                                    dtype=jnp.int32)
    if write_then_attend:
        (x, k_pages), _ = jax.lax.scan(
            body(False), (x, k_pages), (params["layers"], li_d))
        if "layers_moe" in params:
            (x, k_pages), moe_l = jax.lax.scan(
                body(True), (x, k_pages), (sparse, li_m))
            moe_stats = jnp.sum(moe_l, axis=0)
    else:
        (x,), (k_new, _) = jax.lax.scan(
            body(False), (x,), (params["layers"], li_d, k_pages[:L_dense]))
        if "layers_moe" in params:
            (x,), (k_m, moe_l) = jax.lax.scan(
                body(True), (x,), (sparse, li_m, k_pages[L_dense:]))
            moe_stats = jnp.sum(moe_l, axis=0)
            k_new = jnp.concatenate([k_new, k_m], axis=0)
        k_pages, = write_prefill_kv_all_layers_xla(
            k_pages, None, k_new, None, page_table, start_pos, lengths)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    last_idx = jnp.maximum(lengths - 1, 0)
    last_x = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
    outs = [_head_logits(cfg, last_x, head),
            _head_logits(cfg, x, head) if return_all_logits else None,
            (k_pages,)]
    if prompt_lp_targets is not None:
        outs.append(_prompt_logprobs(x, head, prompt_lp_targets))
    if return_stats:
        outs.append(_moe_stats_dict(moe_stats))
    return tuple(outs)


def _mla_forward_decode(params: Params, cfg: ModelConfig,
                        tokens: jnp.ndarray, positions: jnp.ndarray,
                        active: jnp.ndarray, kv: KVCache,
                        page_table: jnp.ndarray,
                        return_stats: bool = False,
                        plan: KernelPlan = KernelPlan()):
    k_pages, = kv
    write_then_attend = plan.write_then_attend
    L_dense = params["layers"]["input_norm"].shape[0]
    x = params["embed"][tokens[:, None]].astype(jnp.dtype(cfg.dtype))
    cache_lens = jnp.where(active, positions, 0)
    B = tokens.shape[0]

    # Under plan.latent_decode (write-then-attend with the kernels on)
    # a step carries the pool as [L, P, ps, D], the shape the latent
    # kernels take (ops/pallas/latent.py); under the engine's pin the
    # reshape moves nothing. Every other write of a latent pool is the
    # XLA scatter: the paged writers' blocks [.., ps, 1, D] would have
    # the pool copied into a row-major layout of 2.2 times its bytes.
    flat = write_then_attend and plan.latent_decode
    if flat:
        from xllm_service_tpu.ops.pallas.latent import (
            latent_decode_attention, latent_kv_update_layer)

    def body(moe: bool):
        def layer(carry, xs):
            if write_then_attend:
                x, kp_full = carry
                lp, li = xs
                h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
                q_t, latent = _mla_qkv(cfg, lp, h, positions[:, None])
                # Latent row into the pool first (aliased write), then
                # attend from the pool with context INCLUDING the
                # current token — no k_cur/v_cur plumbing: the latent
                # kernels, else the XLA scatter and gather reference.
                ctx = jnp.where(active, positions + 1, 0)
                if flat:
                    kp_full = latent_kv_update_layer(
                        kp_full, latent[:, 0, 0], page_table, positions,
                        active, li, interpret=plan.interpret)
                    attn = latent_decode_attention(
                        q_t[:, 0], kp_full, page_table, ctx, li,
                        scale=_mla_scale(cfg), interpret=plan.interpret)
                else:
                    kp_full, = write_decode_kv_layer_xla(
                        kp_full, None, latent[:, 0], None,
                        page_table, positions, active, li)
                    kp = jax.lax.dynamic_index_in_dim(
                        kp_full, li, axis=0, keepdims=False)
                    attn = paged_decode_attention(
                        q_t[:, 0], kp, kp, page_table, ctx,
                        scale=_mla_scale(cfg))
            else:
                x, = carry
                lp, li, kp = xs
                h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
                q_t, latent = _mla_qkv(cfg, lp, h, positions[:, None])
                # Both "k" and "v" reads come from the ONE latent pool
                # (kp twice — XLA CSEs the duplicate gather into one HBM
                # read), the current row folded in.
                attn = paged_decode_attention_current(
                    q_t[:, 0], kp, kp, page_table, cache_lens,
                    latent[:, 0], latent[:, 0], scale=_mla_scale(cfg))
            x = x + _mla_out(cfg, lp, attn)[:, None, :]
            h = rms_norm(x, lp["post_norm"], cfg.rms_norm_eps)
            stats = None
            if moe:
                y, stats = _dropless_moe_mlp(
                    cfg, lp, experts, li - L_dense, h,
                    valid=active[:, None], plan=plan)
                x = x + y
            else:
                x = x + (jax.nn.silu(h @ lp["gate_proj"])
                         * (h @ lp["up_proj"])) @ lp["down_proj"]
            if write_then_attend:
                return (x, kp_full), stats
            return (x,), (latent[:, 0], stats)
        return layer

    moe_stats = jnp.zeros(moe_stats_shape(cfg), jnp.int32)
    li_d = jnp.arange(L_dense, dtype=jnp.int32)
    if "layers_moe" in params:
        sparse, experts = _split_experts(params["layers_moe"])
        li_m = L_dense + jnp.arange(sparse["input_norm"].shape[0],
                                    dtype=jnp.int32)
    if write_then_attend:
        pool_shape = k_pages.shape
        if flat:
            k_pages = k_pages.reshape(pool_shape[:3] + pool_shape[4:])
        (x, k_pages), _ = jax.lax.scan(
            body(False), (x, k_pages), (params["layers"], li_d))
        if "layers_moe" in params:
            (x, k_pages), moe_l = jax.lax.scan(
                body(True), (x, k_pages), (sparse, li_m))
            moe_stats = jnp.sum(moe_l, axis=0)
        k_pages = k_pages.reshape(pool_shape)
    else:
        (x,), (k_new, _) = jax.lax.scan(
            body(False), (x,), (params["layers"], li_d, k_pages[:L_dense]))
        if "layers_moe" in params:
            (x,), (k_m, moe_l) = jax.lax.scan(
                body(True), (x,), (sparse, li_m, k_pages[L_dense:]))
            moe_stats = jnp.sum(moe_l, axis=0)
            k_new = jnp.concatenate([k_new, k_m], axis=0)
        k_pages, = write_decode_kv_all_layers_xla(
            k_pages, None, k_new, None, page_table, positions, active)
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    head = params.get("lm_head")
    if head is None:
        head = params["embed"].T
    logits = _head_logits(cfg, x[:, 0], head)
    if return_stats:
        return logits, (k_pages,), _moe_stats_dict(moe_stats)
    return logits, (k_pages,)


# ---------------------------------------------------------------------------
# Layers that differ in kind (ModelConfig.layer_kinds; LFM2-MoE)
#
# A layer is "<operator>+<ffn>": the operator a gated short convolution
# ("conv") or grouped-query attention ("attn"), the FFN a dense SwiGLU
# ("dense") or routed experts ("moe": the DeepSeek gate and the dropless
# layer, as the latent family's). Weights are stacked PER KIND, in layer
# order (params["stacks"][kind]); the loop walks the pattern as leading
# layers, a ``lax.scan`` over the repeating period and a remainder, and
# inside each of those a run of layers alike is a scan of its own, so a
# program traces a kind's body once for each of the three places it
# appears in, whatever the depth. Prefill and decode share the loop and
# each kind's body; they differ in the two operators they hand it.
#
# The cache is three pools. Keys and values belong to the ATTENTION
# layers alone ([L_attn, P, ps, Hkv, Dh]) and always ride the loop as a
# carry, written before they are read (write-then-attend, whatever the
# plan says of the other families' ordering). A convolution layer's
# state is the last ``conv_kernel - 1`` gated inputs z: ONE ROW A PAGE,
# tails[c, p] = the layer's state as of the last token written into page
# p. A token at position t reads the row of the page that holds t - 1
# and writes the row of the page that holds t; a prefill window writes
# the row of every page it touches. A full page's row is therefore the
# state a prefix hit continues from, a partial page belongs to one
# sequence, freeing a page frees its row, and the host keeps no state
# of its own: the prefix index still hashes pages and nothing else.
# ---------------------------------------------------------------------------

def _init_kinds_params(cfg: ModelConfig, key: jax.Array,
                       dtype: Optional[jnp.dtype]) -> Params:
    dtype = dtype or jnp.dtype(cfg.dtype)
    D, F = cfg.hidden_size, cfg.intermediate_size
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    E, Fe = cfg.num_experts, cfg.moe_intermediate_size or F
    K = cfg.conv_kernel
    keys = iter(jax.random.split(key, 64))

    def w(shape, fan_in):
        return (jax.random.normal(next(keys), shape, jnp.float32)
                * (1.0 / math.sqrt(fan_in))).astype(dtype)

    stacks: Dict[str, Dict[str, jnp.ndarray]] = {}
    for kind in sorted(set(cfg.layer_kinds)):
        n = cfg.layer_kinds.count(kind)
        op, ffn = kind.split("+")
        st = {"input_norm": jnp.ones((n, D), dtype),
              "post_norm": jnp.ones((n, D), dtype)}
        if op == "conv":
            st.update(conv_in=w((n, D, 3 * D), D), conv_w=w((n, K, D), K),
                      conv_out=w((n, D, D), D))
        if op == "mix":
            Hs, I, C = cfg.ssm_heads, cfg.ssm_inner, cfg.ssm_conv_dim
            # The published input projection's three column blocks, kept
            # apart (z | xBC | dt), so that each product reads its own
            # weight where it lies.
            st.update(ssm_in_z=w((n, D, I), D), ssm_in_xbc=w((n, D, C), D),
                      ssm_in_dt=w((n, D, Hs), D),
                      ssm_conv_w=w((n, K, C), K),
                      ssm_conv_b=jnp.zeros((n, C), dtype),
                      ssm_dt_bias=jnp.zeros((n, Hs), jnp.float32),
                      ssm_a_log=jnp.zeros((n, Hs), jnp.float32),
                      ssm_d=jnp.ones((n, Hs), jnp.float32),
                      ssm_norm=jnp.ones((n, I), dtype),
                      ssm_out=w((n, I, D), I))
        if op != "conv":
            st.update(q_proj=w((n, D, Hq * Dh), D),
                      k_proj=w((n, D, Hkv * Dh), D),
                      v_proj=w((n, D, Hkv * Dh), D),
                      o_proj=w((n, Hq * Dh, D), Hq * Dh))
            if cfg.qk_norm:
                st.update(q_norm=jnp.ones((n, Dh), dtype),
                          k_norm=jnp.ones((n, Dh), dtype))
        if ffn == "moe":
            st.update(router=w((n, D, E), D),
                      router_bias=jnp.zeros((n, E), jnp.float32),
                      gate_proj=w((n, E, D, Fe), D),
                      up_proj=w((n, E, D, Fe), D),
                      down_proj=w((n, E, Fe, D), Fe))
        else:
            st.update(gate_proj=w((n, D, F), D), up_proj=w((n, D, F), D),
                      down_proj=w((n, F, D), F))
        stacks[kind] = st
    params: Params = {"embed": w((cfg.vocab_size, D), D), "stacks": stacks,
                      "final_norm": jnp.ones((D,), dtype)}
    if not cfg.tie_word_embeddings:
        params["lm_head"] = w((D, cfg.vocab_size), D)
    return params


def _runs(kinds) -> Tuple[Tuple[str, int], ...]:
    """``kinds`` as runs of layers alike: ((kind, count), ...)."""
    out: list = []
    for k in kinds:
        if out and out[-1][0] == k:
            out[-1][1] += 1
        else:
            out.append([k, 1])
    return tuple((k, n) for k, n in out)


def kinds_pattern(kinds: Tuple[str, ...]) -> Tuple[int, int, int]:
    """``(lead, period, repeats)``: ``kinds[:lead]`` lead, then ``repeats``
    times the ``period`` kinds that follow them, then a remainder. Of
    all such readings the one that leaves the fewest layers outside the
    scan (the published 40 layers: 2 leading, 9 periods of 4, 2 left
    over); ``(len, 0, 0)`` where nothing repeats."""
    L = len(kinds)
    best = (L, L, 0, 0)
    for lead in range(L):
        for p in range(1, (L - lead) // 2 + 1):
            n = 1
            while lead + (n + 1) * p <= L and \
                    kinds[lead + n * p:lead + (n + 1) * p] \
                    == kinds[lead:lead + p]:
                n += 1
            if n >= 2:
                best = min(best, (L - n * p + p, lead, p, n))
    return best[1:]


def _kinds_layers(params: Params, cfg: ModelConfig, x: jnp.ndarray,
                  pools, conv_op, attn_op, valid: jnp.ndarray,
                  plan: KernelPlan, mix_op=None):
    """The layer loop over ``cfg.layer_kinds``. ``pools`` = (k, v,
    tails) and, for a model with mixers, the pool of states after them,
    carried and updated in place; ``conv_op(lp, h, tails, c) -> (y,
    tails)``, ``attn_op(lp, h, k, v, a) -> (y, k, v)`` and ``mix_op(lp,
    h, pools, a, c) -> (y, pools)`` (attention and a mixer on the same
    input) are the caller's (prefill's or decode's), ``a`` / ``c`` the
    layer's index among the layers that keep keys and values / a
    convolution tail. Returns ``(x, pools, moe_stats)``."""
    kinds = cfg.layer_kinds
    lead, period, repeats = kinds_pattern(kinds)

    def tally(span) -> Dict[str, int]:
        # how many layers of each kind, layers that attend ("attn") and
        # layers that keep a convolution tail ("conv") ``span`` holds
        r = {k: span.count(k) for k in set(kinds)}
        r["attn"] = sum(k.startswith(("attn+", "mix+")) for k in span)
        r["conv"] = sum(k.startswith(("conv+", "mix+")) for k in span)
        return r

    def body(kind: str):
        op, ffn = kind.split("+")
        stack = params["stacks"][kind]
        small, experts = _split_experts(stack) if ffn == "moe" \
            else (stack, None)

        def layer(carry, s, a, c):
            """Layer ``s`` of this kind's stack, the ``a``-th that
            attends and the ``c``-th that keeps a tail."""
            x, pools, stats = carry[0], carry[1:-1], carry[-1]
            lp = jax.tree_util.tree_map(
                lambda w: jax.lax.dynamic_index_in_dim(
                    w, s, axis=0, keepdims=False), small)
            h = rms_norm(x, lp["input_norm"], cfg.rms_norm_eps)
            if op == "conv":
                y, tails = conv_op(lp, h, pools[2], c)
                pools = pools[:2] + (tails,) + pools[3:]
            elif op == "attn":
                y, kp, vp = attn_op(lp, h, pools[0], pools[1], a)
                pools = (kp, vp) + pools[2:]
            else:
                y, pools = mix_op(lp, h, pools, a, c)
            x = x + y
            h = rms_norm(x, lp["post_norm"], cfg.rms_norm_eps)
            if ffn == "moe":
                m, st = _dropless_moe_mlp(cfg, lp, experts, s, h,
                                          valid=valid, plan=plan)
                stats = stats + st
            else:
                gate_m, down_m = cfg.mlp_multipliers
                gate = h @ lp["gate_proj"]
                if gate_m != 1.0:
                    gate = gate * jnp.asarray(gate_m, gate.dtype)
                m = (jax.nn.silu(gate) * (h @ lp["up_proj"])) \
                    @ lp["down_proj"]
                if down_m != 1.0:
                    m = m * jnp.asarray(down_m, m.dtype)
            return (x + m,) + pools + (stats,)
        return layer

    def walk(carry, span, at: Dict[str, int], step, r):
        """The layers ``span`` (kinds), the first of them at ranks
        ``at``; in the scanned period every rank moves on by ``step`` a
        repeat and ``r`` is the (traced) repeat."""
        at = dict(at)
        for kind, count in _runs(span):
            op = kind.split("+")[0]
            s0, a0, c0 = (at[k] + step.get(k, 0) * r
                          for k in (kind, "attn", "conv"))
            layer = body(kind)
            if count == 1:
                carry = layer(carry, s0, a0, c0)
            else:
                carry, _ = jax.lax.scan(
                    lambda cr, j, layer=layer, s0=s0, a0=a0, c0=c0:
                    (layer(cr, s0 + j, a0 + j, c0 + j), None),
                    carry, jnp.arange(count, dtype=jnp.int32))
            at[kind] += count
            for rank in (("attn", "conv") if op == "mix" else (op,)):
                at[rank] += count
        return carry

    carry = (x,) + tuple(pools) + (
        jnp.zeros(moe_stats_shape(cfg), jnp.int32),)
    zero = jnp.zeros((), jnp.int32)
    carry = walk(carry, kinds[:lead], tally(()), {}, zero)
    if repeats:
        span = kinds[lead:lead + period]
        carry, _ = jax.lax.scan(
            lambda cr, r: (walk(cr, span, tally(kinds[:lead]), tally(span),
                                r), None),
            carry, jnp.arange(repeats, dtype=jnp.int32))
    done = lead + period * repeats
    carry = walk(carry, kinds[done:], tally(kinds[:done]), {}, zero)
    return carry[0], carry[1:-1], carry[-1]


def _conv_mix(cfg: ModelConfig, lp, h: jnp.ndarray, tail: jnp.ndarray):
    """The gated short convolution over h [B, T, D] with the K - 1 gated
    inputs before it, ``tail`` [B, K-1, D] (zeros at a sequence's
    start): ``[b, c, u] = h W_in``; ``z = b * u``; ``y_t = (c_t * sum_j
    w_j z_{t-K+1+j}) W_out``. Returns ``(y, zz)``, ``zz`` [B, K-1+T, D]
    the tail and the window's own z one after the other: the state
    after position i of the window is ``zz[:, i+1:i+K]``."""
    K, T = cfg.conv_kernel, h.shape[1]
    b, c, u = jnp.split(h @ lp["conv_in"], 3, axis=-1)
    zz = jnp.concatenate([tail.astype(h.dtype), b * u], axis=1)
    w = lp["conv_w"].astype(jnp.float32)                       # [K, D]
    acc = sum(w[j] * zz[:, j:j + T].astype(jnp.float32) for j in range(K))
    y = (c.astype(jnp.float32) * acc).astype(h.dtype) @ lp["conv_out"]
    return y, zz


def _tails_read(cfg: ModelConfig, tails: jnp.ndarray, c, page_table,
                positions: jnp.ndarray, ps: int) -> jnp.ndarray:
    """[B, K-1, D]: convolution layer ``c``'s state before ``positions``
    [B]: the row of the page that holds the position before, zeros at
    position 0."""
    before = jnp.maximum(positions - 1, 0)
    pid = jnp.take_along_axis(page_table, (before // ps)[:, None],
                              axis=1)[:, 0]
    rows = tails[c, pid].reshape(-1, cfg.conv_kernel - 1, cfg.hidden_size)
    return jnp.where((positions > 0)[:, None, None], rows,
                     jnp.zeros((), rows.dtype))


def _tails_write(cfg: ModelConfig, tails: jnp.ndarray, c, page_table,
                 start: jnp.ndarray, lengths: jnp.ndarray,
                 zz: jnp.ndarray, ps: int) -> jnp.ndarray:
    """Write convolution layer ``c``'s rows of every page that the
    windows [start, start + lengths) touch: page p's row is the state
    after the LAST of the window's positions that lies in p. ``zz``
    [B, K-1+T, D] is ``_conv_mix``'s (the state after window position i
    is ``zz[:, i+1:i+K]``); a row of length 0 (padding, an inactive
    lane) writes nothing."""
    K = cfg.conv_kernel
    B, T = zz.shape[0], zz.shape[1] - (K - 1)
    P, MP = tails.shape[1], page_table.shape[1]
    # a window of T positions touches at most this many pages
    j = jnp.arange(-(-T // ps) + 1 if T > 1 else 1, dtype=jnp.int32)
    last = start + lengths - 1                                   # [B]
    page = start[:, None] // ps + j[None, :]                     # [B, J]
    touched = (lengths[:, None] > 0) & (page * ps <= last[:, None])
    # the window position (0-based) of the page's last written token
    end = jnp.minimum(page * ps + ps - 1, last[:, None]) - start[:, None]
    at = jnp.clip(end, 0, T - 1)[:, :, None] + 1 \
        + jnp.arange(K - 1, dtype=jnp.int32)[None, None, :]      # [B,J,K-1]
    rows = jax.vmap(lambda z, i: z[i])(zz, at)                   # [B,J,K-1,D]
    pid = jnp.take_along_axis(page_table, jnp.minimum(page, MP - 1),
                              axis=1)
    pid = jnp.where(touched, pid, P)            # past the pool: dropped
    return tails.at[c, pid.reshape(-1)].set(
        rows.reshape(pid.size, -1), mode="drop")


# ---------------------------------------------------------------------------
# A mixer beside attention (operator "mix"; Falcon-H1)
#
# A layer's operator is grouped-query attention AND a Mamba-2 mixer on
# the same normed input, their outputs summed. The mixer's state of one
# sequence is a matrix a head, ``S [heads, head width, state]`` in
# float32: ``S_t = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t``, ``y_t =
# S_t C_t + D x_t``. It lives in the fourth pool (each head's matrix
# transposed, [state, head width]), ADDRESSED BY SLOT (a
# state is sixteen pages' worth of a layer's keys and values: a row
# beside every page, as the tails have, would not fit). Which slot is
# whose is the host's business (runtime/kv_cache.py, runtime/engine.py);
# a step program is told, per row, what to read and what to write:
#
# - decode: the row's ``state_rows`` entry r >= 1 owns slots 2r - 1 and
#   2r. The state as of position t lives in slot 2r - 1 + t mod 2:
#   the step at t reads the other one and writes this one, so a step
#   that was launched ahead, discarded and run again reads what the
#   first read (a page's keys and values are re-written at the same
#   place; a state that a discarded step had advanced in place would be
#   advanced twice);
# - prefill: ``state_cols`` [B, 4] = the slot the window starts from (0:
#   from zero), the slot its final state goes to, a snapshot slot (0:
#   none) and how many of the window's tokens the snapshot is taken
#   after: the state at a page boundary that the prefix index can hand
#   to a later request whose prompt shares the pages up to there.
#
# The short convolution's tail rides the third pool, one row a page, as
# a "conv" layer's does, but as a RING: the input at position t in ring
# row t mod conv_kernel. A decode step reads the three rows behind its
# position and writes its own, never one it reads, for the same reason
# as above (a "conv" layer's tail, shifted in place, is not safe under a
# discarded launch: PERF.md section 7).
#
# Prefill runs the chunked form of the recurrence (chunks of
# ``cfg.ssm_chunk``; plain XLA einsums), decode the one-token form (the
# Pallas kernel of ops/pallas/ssm_update.py where ``plan.ssm_decode``).
# ---------------------------------------------------------------------------

_HIGHEST = jax.lax.Precision.HIGHEST


def _ssm_in(cfg: ModelConfig, lp, h: jnp.ndarray):
    """The mixer's input projection of h [B, T, D]: z [B, T, I] (the
    gate), xBC [B, T, C] (before the filter), dt [B, T, H] (before its
    bias), each segment scaled as published (``ssm_multipliers`` over z,
    x, B, C, dt)."""
    m = cfg.ssm_multipliers
    if cfg.ssm_in_multiplier != 1.0:
        h = h * jnp.asarray(cfg.ssm_in_multiplier, h.dtype)
    z, xbc, dt = _pin((h @ lp["ssm_in_z"], h @ lp["ssm_in_xbc"],
                       h @ lp["ssm_in_dt"]))
    gn = cfg.ssm_groups * cfg.ssm_state
    scale = jnp.concatenate([jnp.full((cfg.ssm_inner,), m[1], jnp.float32),
                             jnp.full((gn,), m[2], jnp.float32),
                             jnp.full((gn,), m[3], jnp.float32)])
    return (z * jnp.asarray(m[0], z.dtype), xbc * scale.astype(xbc.dtype),
            dt * jnp.asarray(m[4], dt.dtype))


def _ssm_conv(cfg: ModelConfig, lp, xbc: jnp.ndarray, prev: jnp.ndarray):
    """silu(causal depthwise filter + bias) over xBC [B, T, C] with the
    K - 1 inputs before it, ``prev`` [B, K-1, C]. Returns ``(u, zz)``,
    ``zz`` [B, K-1+T, C] the inputs one after the other."""
    K, T = cfg.conv_kernel, xbc.shape[1]
    zz = jnp.concatenate([prev.astype(xbc.dtype), xbc], axis=1)
    w = lp["ssm_conv_w"].astype(jnp.float32)                   # [K, C]
    acc = sum(w[j] * zz[:, j:j + T].astype(jnp.float32) for j in range(K))
    acc = acc + lp["ssm_conv_b"].astype(jnp.float32)
    return jax.nn.silu(acc).astype(xbc.dtype), zz


def _ssm_split(cfg: ModelConfig, u: jnp.ndarray):
    """u [B, T, C] -> x [B, T, H, P], B and C [B, T, G, N], float32."""
    B, T, _ = u.shape
    I, gn = cfg.ssm_inner, cfg.ssm_groups * cfg.ssm_state
    u = u.astype(jnp.float32)
    return (u[..., :I].reshape(B, T, cfg.ssm_heads, cfg.ssm_head_dim),
            u[..., I:I + gn].reshape(B, T, cfg.ssm_groups, cfg.ssm_state),
            u[..., I + gn:].reshape(B, T, cfg.ssm_groups, cfg.ssm_state))


def _ssm_out(cfg: ModelConfig, lp, y: jnp.ndarray, z: jnp.ndarray):
    """y [B, T, H, P] float32 gated by silu(z), normed in
    ``ssm_groups`` groups (``mamba_rms_norm``, the gate BEFORE the norm),
    projected back to [B, T, D]."""
    B, T = y.shape[:2]
    I, G = cfg.ssm_inner, cfg.ssm_groups
    y = y.reshape(B, T, G, I // G) * jax.nn.silu(
        z.astype(jnp.float32)).reshape(B, T, G, I // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + cfg.rms_norm_eps)
    y = (y.reshape(B, T, I) * lp["ssm_norm"].astype(jnp.float32)
         ).astype(z.dtype)
    out = y @ lp["ssm_out"]
    if cfg.ssm_out_multiplier != 1.0:
        out = out * jnp.asarray(cfg.ssm_out_multiplier, out.dtype)
    return out


def _ssd(cfg: ModelConfig, x, dt, A, Bm, Cm, S0):
    """The chunked form of the recurrence over a window: x [B, T, H, P],
    dt [B, T, H] (0 where a position must not move the state: padding),
    A [H] (negative), Bm and Cm [B, T, G, N], S0 [B, H, N, P] (a head's
    matrix as the pool keeps it, [state, head width]: a transpose here
    would settle its layout on the pool, which every prefill program
    then copied whole, in and out, 12 ms a window on the chip, PR 45);
    float32. Returns ``(y, S)``: y [B, T, H, P] without the D term, S
    the state after the window's last position. ``Cm`` None: the state
    alone.

    Within a chunk of Q positions the output is a masked [Q, Q] product
    (decay from s to t times C_t . B_s); across chunks a scan carries
    the state, which each position of the next chunk reads decayed."""
    B, T, H, P = x.shape
    G, N = Bm.shape[2:]
    J = H // G                                # heads a group
    Q = min(cfg.ssm_chunk, T)
    assert T % Q == 0, (T, Q)
    nc = T // Q
    dt = dt.reshape(B, nc, Q, G, J)
    dtx = dt[..., None] * x.reshape(B, nc, Q, G, J, P)
    Bs = Bm.reshape(B, nc, Q, G, N)
    cum = jnp.cumsum(dt * A.reshape(G, J), axis=2)   # log decay, inclusive
    total = cum[:, :, -1]                                  # [B, nc, G, J]
    # a chunk's own contribution to the state at its end
    local = jnp.einsum(
        "bcqgjp,bcqgn->bcgjnp",
        jnp.exp(total[:, :, None] - cum)[..., None] * dtx, Bs,
        precision=_HIGHEST)

    def step(S, inp):
        tot, loc = inp
        return jnp.exp(tot)[..., None, None] * S + loc, S

    S, starts = jax.lax.scan(
        step, S0.reshape(B, G, J, N, P),
        (jnp.moveaxis(total, 1, 0), jnp.moveaxis(local, 1, 0)))
    S = S.reshape(B, H, N, P)
    if Cm is None:
        return None, S
    Cs = Cm.reshape(B, nc, Q, G, N)
    # what the chunk's positions read of the state it started from
    y = jnp.einsum("bcqgn,cbgjnp->bcqgjp", Cs, starts,
                   precision=_HIGHEST) * jnp.exp(cum)[..., None]
    # and of the chunk's own earlier positions
    scores = jnp.einsum("bcqgn,bcsgn->bcgqs", Cs, Bs, precision=_HIGHEST)
    ct = jnp.moveaxis(cum, 2, -1)                       # [B, nc, G, J, Q]
    seg = ct[..., :, None] - ct[..., None, :]           # from s to t
    causal = jnp.arange(Q)[:, None] >= jnp.arange(Q)[None, :]
    decay = jnp.exp(jnp.where(causal, seg, -jnp.inf))
    y = y + jnp.einsum("bcgjqs,bcsgjp->bcqgjp",
                       scores[:, :, :, None] * decay, dtx,
                       precision=_HIGHEST)
    return y.reshape(B, T, H, P), S


def _ssm_step(cfg: ModelConfig, state, c, read, write, x, dt, A, Bm, Cm,
              plan: KernelPlan):
    """One token a row, in place in the pool: reads layer ``c``'s slots
    ``read`` [B], writes ``write`` [B]. x [B, H, P], dt [B, H] (0 on an
    inactive row, whose slots are the null slot), Bm and Cm [B, G, N].
    Returns ``(y [B, H, P] without the D term, state)``."""
    if plan.ssm_decode:
        from xllm_service_tpu.ops.pallas.ssm_update import ssm_decode_update
        return ssm_decode_update(state, c, read, write, x, dt, A, Bm, Cm,
                                 interpret=plan.interpret)
    J = cfg.ssm_heads // cfg.ssm_groups
    Bh, Ch = jnp.repeat(Bm, J, axis=1), jnp.repeat(Cm, J, axis=1)
    # the pool's [state, head width] matrices
    S = jax.lax.dynamic_index_in_dim(state, c, axis=0, keepdims=False)[read]
    S = jnp.exp(dt * A)[..., None, None] * S \
        + Bh[:, :, :, None] * (dt[..., None] * x)[:, :, None, :]
    y = jnp.einsum("bhnp,bhn->bhp", S, Ch, precision=_HIGHEST)
    return y, state.at[c, write].set(S)


def _ring_read(cfg: ModelConfig, tails: jnp.ndarray, c, page_table,
               positions: jnp.ndarray, ps: int) -> jnp.ndarray:
    """[B, K-1, C]: the mixer's convolution inputs at the K - 1 positions
    before ``positions`` [B], oldest first, from the ring of the page
    that holds the position before; zeros before a sequence's start."""
    K, C = cfg.conv_kernel, cfg.ssm_conv_dim
    before = jnp.maximum(positions - 1, 0)
    pid = jnp.take_along_axis(page_table, (before // ps)[:, None],
                              axis=1)[:, 0]
    ring = tails[c, pid].reshape(-1, K, C)
    at = positions[:, None] - (K - 1) \
        + jnp.arange(K - 1, dtype=jnp.int32)[None, :]            # [B, K-1]
    rows = jnp.take_along_axis(ring, jnp.mod(at, K)[:, :, None], axis=1)
    return jnp.where((at >= 0)[:, :, None], rows,
                     jnp.zeros((), rows.dtype))


def _ring_write(cfg: ModelConfig, tails: jnp.ndarray, c, page_table,
                start: jnp.ndarray, lengths: jnp.ndarray,
                zz: jnp.ndarray, ps: int) -> jnp.ndarray:
    """Write the mixer's ring of every page that the windows [start,
    start + lengths) touch: page p's ring holds the last K inputs as of
    the LAST of the window's positions that lies in p, the input at
    position t in ring row t mod K. ``zz`` [B, K-1+T, C] is
    ``_ssm_conv``'s (the input at window position i is ``zz[:, i+K-1]``);
    a row of length 0 writes nothing."""
    K = cfg.conv_kernel
    B, T = zz.shape[0], zz.shape[1] - (K - 1)
    P, MP = tails.shape[1], page_table.shape[1]
    j = jnp.arange(-(-T // ps) + 1 if T > 1 else 1, dtype=jnp.int32)
    last = start + lengths - 1                                   # [B]
    page = start[:, None] // ps + j[None, :]                     # [B, J]
    touched = (lengths[:, None] > 0) & (page * ps <= last[:, None])
    end = jnp.minimum(page * ps + ps - 1, last[:, None])         # absolute
    # ring row r holds the latest position <= end that is r mod K
    r = jnp.arange(K, dtype=jnp.int32)[None, None, :]
    pos = end[:, :, None] - jnp.mod(end[:, :, None] - r, K)      # [B, J, K]
    at = jnp.clip(pos - start[:, None, None] + (K - 1), 0, K + T - 2)
    rows = jax.vmap(lambda z, i: z[i])(zz, at)                   # [B,J,K,C]
    pid = jnp.take_along_axis(page_table, jnp.minimum(page, MP - 1),
                              axis=1)
    pid = jnp.where(touched, pid, P)            # past the pool: dropped
    return tails.at[c, pid.reshape(-1)].set(
        rows.reshape(pid.size, -1), mode="drop")


def _mixer(cfg: ModelConfig, lp, h, prev, valid, scan):
    """The mixer over h [B, T, D]: ``prev`` [B, K-1, C] the convolution
    inputs before the window, ``valid`` [B, T] the positions that move
    the state, ``scan(x, dt, A, Bm, Cm) -> (y, extra)`` the recurrence
    (prefill's or decode's). Returns ``(out [B, T, D], zz, extra)``."""
    z, xbc, dt = _ssm_in(cfg, lp, h)
    u, zz = _ssm_conv(cfg, lp, xbc, prev)
    x, Bm, Cm = _ssm_split(cfg, u)
    dt = jnp.where(valid[..., None], jax.nn.softplus(
        dt.astype(jnp.float32) + lp["ssm_dt_bias"]), 0.0)
    y, extra = scan(x, dt, -jnp.exp(lp["ssm_a_log"]), Bm, Cm)
    y = y + lp["ssm_d"][:, None] * x
    return _ssm_out(cfg, lp, y, z), zz, extra


def _kv_pack(cfg: ModelConfig) -> int:
    """Key-value heads that share one row of the pools. A TPU tiles an
    array's last axis in 128 lanes: a pool of 8 heads of 64 is stored
    as 8 of 128, half of them padding (3.96 GB for the cell's 1.98, and
    every page read twice over: compiled for a described v5e, PERF.md,
    PR 38). So heads narrower than 128 are packed side by side, as many
    as fit and divide the heads: [.., 8, 64] is kept as [.., 4, 128],
    the same bytes in the same order, and every writer and attention
    kernel sees 4 heads of 128 (``_packed_qkv``)."""
    return math.gcd(cfg.num_kv_heads, max(128 // cfg.head_dim, 1))


def _packed_qkv(cfg: ModelConfig, q, k, v):
    """q [.., Hq, Dh], k / v [.., Hkv, Dh] as attention over the packed
    pools takes them: k and v reshaped to [.., Hkv / p, p * Dh], and
    each query widened to p * Dh with its own values in the slot of ITS
    key-value head and zeros in the others, so that its product with a
    packed key row is exactly its product with its own head's key.
    Returns ``(q, k, v, unpack)``; ``unpack`` takes the attention's
    output [.., Hq, p * Dh] back to [.., Hq, Dh] (the slot of the
    head's own values; the other slots hold its probabilities over a
    neighbour's values and are dropped)."""
    p = _kv_pack(cfg)
    if p == 1:
        return q, k, v, lambda o: o
    Hq, Hkv, Dh = cfg.num_heads, cfg.num_kv_heads, cfg.head_dim
    slot = (jnp.arange(Hq) // (Hq // Hkv)) % p                  # [Hq]
    mask = jax.nn.one_hot(slot, p, dtype=q.dtype)               # [Hq, p]
    q = (q[..., None, :] * mask[:, :, None]).reshape(
        q.shape[:-1] + (p * Dh,))
    k = k.reshape(k.shape[:-2] + (Hkv // p, p * Dh))
    v = v.reshape(v.shape[:-2] + (Hkv // p, p * Dh))

    def unpack(o):
        o = o.reshape(o.shape[:-1] + (p, Dh))
        return jnp.take_along_axis(
            o, slot.reshape((1,) * (o.ndim - 3) + (Hq, 1, 1)),
            axis=-2)[..., 0, :]
    return q, k, v, unpack


def _kinds_head(params: Params, cfg: ModelConfig, x: jnp.ndarray):
    x = rms_norm(x, params["final_norm"], cfg.rms_norm_eps)
    head = params.get("lm_head")
    return x, (params["embed"].T if head is None else head)


def _kinds_embed(params: Params, cfg: ModelConfig, tokens: jnp.ndarray):
    x = params["embed"][tokens].astype(jnp.dtype(cfg.dtype))
    if cfg.embedding_multiplier != 1.0:
        x = x * jnp.asarray(cfg.embedding_multiplier, x.dtype)
    return x


def _kinds_logits(cfg: ModelConfig, x: jnp.ndarray, head: jnp.ndarray):
    logits = _head_logits(cfg, x, head)
    if cfg.lm_head_multiplier != 1.0:
        logits = logits * cfg.lm_head_multiplier
    return logits


def _attn_in(cfg: ModelConfig, lp, h: jnp.ndarray):
    """q, k, v of the normed input, with the multipliers a family puts
    on the attention branch's input and on its keys (1: none)."""
    if cfg.attention_in_multiplier != 1.0:
        h = h * jnp.asarray(cfg.attention_in_multiplier, h.dtype)
    q, k, v = _qkv(lp, cfg, h)
    if cfg.key_multiplier != 1.0:
        k = k * jnp.asarray(cfg.key_multiplier, k.dtype)
    return q, k, v


def _attn_out(cfg: ModelConfig, lp, attn: jnp.ndarray):
    out = attn @ lp["o_proj"]
    if cfg.attention_out_multiplier != 1.0:
        out = out * jnp.asarray(cfg.attention_out_multiplier, out.dtype)
    return out


def _kinds_forward_prefill(params: Params, cfg: ModelConfig,
                           tokens: jnp.ndarray, start_pos: jnp.ndarray,
                           lengths: jnp.ndarray, kv: KVCache,
                           page_table: jnp.ndarray,
                           return_all_logits: bool = False,
                           prompt_lp_targets: Optional[jnp.ndarray] = None,
                           return_stats: bool = False,
                           plan: KernelPlan = KernelPlan(),
                           state_cols: Optional[jnp.ndarray] = None):
    B, T = tokens.shape
    ps = kv[0].shape[2]
    x = _kinds_embed(params, cfg, tokens)
    positions = start_pos[:, None] + jnp.arange(T, dtype=jnp.int32)[None, :]
    kv_lengths = start_pos + lengths
    tok_valid = (jnp.arange(T, dtype=jnp.int32)[None, :]
                 < lengths[:, None])                             # [B, T]
    scale = cfg.head_dim ** -0.5        # of the head, not the packed row

    def conv_op(lp, h, tails, c):
        y, zz = _conv_mix(cfg, lp, h, _tails_read(
            cfg, tails, c, page_table, start_pos, ps))
        return y, _tails_write(cfg, tails, c, page_table, start_pos,
                               lengths, zz, ps)

    def attn_op(lp, h, kp, vp, a):
        q, k, v = _attn_in(cfg, lp, h)
        q = rope_for(cfg.rope_scaling, q, positions, cfg.rope_theta)
        k = rope_for(cfg.rope_scaling, k, positions, cfg.rope_theta)
        q, k, v, unpack = _packed_qkv(cfg, q, k, v)
        # The window's keys and values into the pool first, then
        # attention reads cached prefix and window alike from the pool.
        kp, vp = write_prefill_kv_layer(kp, vp, k, v, page_table,
                                        start_pos, lengths, a, plan)
        if plan.prefill_attn and T % ps == 0:
            from xllm_service_tpu.ops.pallas import (
                paged_prefill_attention_pallas)
            attn = paged_prefill_attention_pallas(
                q, None, None, kp, vp, page_table, start_pos, lengths,
                scale=scale, layer=a, from_pool=True,
                interpret=plan.interpret)
        else:
            attn = mha_prefill_auto(
                q, gather_pages(jax.lax.dynamic_index_in_dim(
                    kp, a, axis=0, keepdims=False), page_table),
                gather_pages(jax.lax.dynamic_index_in_dim(
                    vp, a, axis=0, keepdims=False), page_table),
                kv_lengths, start_pos, scale=scale)
        return _attn_out(cfg, lp, unpack(attn).reshape(B, T, -1)), kp, vp

    def mix_op(lp, h, pools, a, c):
        kp, vp, tails, state = pools
        ya, kp, vp = attn_op(lp, h, kp, vp, a)
        src, dst, snap, snap_len = (state_cols[:, i] for i in range(4))
        n_slots = state.shape[1]
        layer_state = jax.lax.dynamic_index_in_dim(state, c, axis=0,
                                                   keepdims=False)
        S0 = jnp.where((src > 0)[:, None, None, None], layer_state[src],
                       0.0)

        def scan(x_s, dt, A, Bm, Cm):
            # The state a later request resumes from: the window's,
            # stopped after ``snap_len`` of its tokens (the positions
            # behind them do not move it).
            keep = jnp.arange(T, dtype=jnp.int32)[None, :] \
                < snap_len[:, None]
            S_snap = _ssd(cfg, x_s, jnp.where(keep[..., None], dt, 0.0),
                          A, Bm, None, S0)[1]
            y, S = _ssd(cfg, x_s, dt, A, Bm, Cm, S0)
            return y, (S, S_snap)

        ym, zz, (S, S_snap) = _mixer(cfg, lp, h, _ring_read(
            cfg, tails, c, page_table, start_pos, ps), tok_valid, scan)
        tails = _ring_write(cfg, tails, c, page_table, start_pos, lengths,
                            zz, ps)
        # a slot past the pool: nothing is written (mode="drop")
        state = state.at[c, jnp.where(lengths > 0, dst, n_slots)].set(
            S, mode="drop")
        state = state.at[c, jnp.where(snap > 0, snap, n_slots)].set(
            S_snap, mode="drop")
        return ya + ym, (kp, vp, tails, state)

    x, kv, moe_stats = _kinds_layers(params, cfg, x, kv, conv_op, attn_op,
                                     tok_valid, plan, mix_op)
    x, head = _kinds_head(params, cfg, x)
    last_idx = jnp.maximum(lengths - 1, 0)
    last_x = jnp.take_along_axis(x, last_idx[:, None, None], axis=1)[:, 0]
    outs = [_kinds_logits(cfg, last_x, head),
            _kinds_logits(cfg, x, head) if return_all_logits else None, kv]
    if prompt_lp_targets is not None:
        if cfg.lm_head_multiplier != 1.0:
            x = x * jnp.asarray(cfg.lm_head_multiplier, x.dtype)
        outs.append(_prompt_logprobs(x, head, prompt_lp_targets))
    if return_stats:
        outs.append(_moe_stats_dict(moe_stats))
    return tuple(outs)


def _kinds_forward_decode(params: Params, cfg: ModelConfig,
                          tokens: jnp.ndarray, positions: jnp.ndarray,
                          active: jnp.ndarray, kv: KVCache,
                          page_table: jnp.ndarray,
                          return_stats: bool = False,
                          plan: KernelPlan = KernelPlan(),
                          state_rows: Optional[jnp.ndarray] = None):
    B = tokens.shape[0]
    ps = kv[0].shape[2]
    x = _kinds_embed(params, cfg, tokens[:, None])
    pos2 = positions[:, None]
    one = active.astype(jnp.int32)          # an inactive lane: length 0
    scale = cfg.head_dim ** -0.5        # of the head, not the packed row

    def conv_op(lp, h, tails, c):
        y, zz = _conv_mix(cfg, lp, h, _tails_read(
            cfg, tails, c, page_table, positions, ps))
        return y, _tails_write(cfg, tails, c, page_table, positions, one,
                               zz, ps)

    def attn_op(lp, h, kp, vp, a):
        q, k, v = _attn_in(cfg, lp, h)
        q = rope_for(cfg.rope_scaling, q, pos2, cfg.rope_theta)
        k = rope_for(cfg.rope_scaling, k, pos2, cfg.rope_theta)
        q, k, v, unpack = _packed_qkv(cfg, q, k, v)
        kp, vp = write_decode_kv_layer(kp, vp, k[:, 0], v[:, 0],
                                       page_table, positions, active, a,
                                       plan)
        attn = paged_decode_attention_auto(
            q[:, 0], kp, vp, page_table,
            jnp.where(active, positions + 1, 0), plan, scale=scale,
            layer=a)
        return _attn_out(cfg, lp, unpack(attn).reshape(B, 1, -1)), kp, vp

    def mix_op(lp, h, pools, a, c):
        kp, vp, tails, state = pools
        ya, kp, vp = attn_op(lp, h, kp, vp, a)
        # The state as of position t is in the row's slot t mod 2: read
        # the other one, write this one (an inactive lane: the null slot).
        mine = 2 * state_rows - 1
        read = jnp.where(active, mine + (positions + 1) % 2, 0)
        write = jnp.where(active, mine + positions % 2, 0)

        def scan(x_s, dt, A, Bm, Cm):
            y, moved = _ssm_step(cfg, state, c, read, write, x_s[:, 0],
                                 dt[:, 0], A, Bm[:, 0], Cm[:, 0], plan)
            return y[:, None], moved

        ym, zz, state = _mixer(cfg, lp, h, _ring_read(
            cfg, tails, c, page_table, positions, ps), active[:, None],
            scan)
        tails = _ring_write(cfg, tails, c, page_table, positions, one, zz,
                            ps)
        return ya + ym, (kp, vp, tails, state)

    x, kv, moe_stats = _kinds_layers(params, cfg, x, kv, conv_op, attn_op,
                                     active[:, None], plan, mix_op)
    x, head = _kinds_head(params, cfg, x)
    logits = _kinds_logits(cfg, x[:, 0], head)
    if return_stats:
        return logits, kv, _moe_stats_dict(moe_stats)
    return logits, kv
