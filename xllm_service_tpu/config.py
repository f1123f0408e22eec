"""Configuration objects for the service layer, worker engine, and models.

``ServiceOptions`` mirrors the reference's gflags surface
(``common/global_gflags.cpp`` — ports, thread counts, etcd address, load
balance policy, block_size, murmur seed, SLO targets) as a typed dataclass;
``EngineConfig`` and ``ModelConfig`` configure the net-new TPU worker engine
that the reference delegated to NPU-side xLLM.
"""

from __future__ import annotations

import dataclasses
import enum
import json
import os
from typing import Any, Dict, Optional, Tuple


class LoadBalancePolicyType(str, enum.Enum):
    ROUND_ROBIN = "RR"
    CACHE_AWARE = "CAR"
    SLO_AWARE = "SLO_AWARE"


class InstanceType(str, enum.Enum):
    """Worker roles. Mirrors reference ``common/types.h:71-79``; ENCODE is the
    net-new EPD multimodal encode role (reference claims EPD but keeps it
    engine-side)."""

    DEFAULT = "DEFAULT"
    PREFILL = "PREFILL"
    DECODE = "DECODE"
    MIX = "MIX"
    ENCODE = "ENCODE"


@dataclasses.dataclass
class ServiceOptions:
    """Service-process options (reference: common/global_gflags.cpp + options.h)."""

    host: str = "127.0.0.1"
    http_port: int = 9888
    rpc_port: int = 9889
    num_threads: int = 32
    max_concurrency: int = 128

    etcd_addr: str = ""           # empty → in-process coordination store
    load_balance_policy: LoadBalancePolicyType = LoadBalancePolicyType.CACHE_AWARE

    block_size: int = 128          # prefix-hash granularity (tokens per KV block)
    murmur_hash3_seed: int = 0

    tokenizer_path: str = ""
    model_id: str = ""

    enable_request_trace: bool = False
    # .jsonl: the file has always been JSON Lines (one record per line).
    trace_path: str = "trace/trace.jsonl"
    enable_decode_response_to_service: bool = False

    # SLO routing thresholds (hot-reloadable in the reference,
    # global_gflags.cpp:95-104).
    target_ttft_ms: float = 1000.0
    target_tpot_ms: float = 50.0

    # End-to-end bound on one generation (RPC fan-in waits, relay reads).
    request_timeout_s: float = 600.0

    # Cluster cadences.
    heartbeat_interval_s: float = 3.0
    master_upload_interval_s: float = 3.0
    detect_disconnected_instance_interval_s: float = 10.0

    # Token fan-in ordering pools (reference: scheduler.h:114).
    num_output_pools: int = 128

    # Multi-model serverless allocator budget per instance, GB
    # (reference: instance_mgr.h:143).
    instance_memory_budget_gb: float = 60.0

    def to_json(self) -> Dict[str, Any]:
        d = dataclasses.asdict(self)
        d["load_balance_policy"] = self.load_balance_policy.value
        return d


@dataclasses.dataclass(frozen=True)
class ModelConfig:
    """Architecture config covering Llama-2/3, Qwen2(.5), Qwen3, TinyLlama, and the
    MoE (Mixtral-style) variant used for expert parallelism.

    Frozen (hashable) so it can be a static jit argument — one compiled
    program per architecture. Derive variants with ``dataclasses.replace``.
    """

    name: str = "llama"
    vocab_size: int = 32000
    hidden_size: int = 4096
    intermediate_size: int = 11008
    num_layers: int = 32
    num_heads: int = 32
    num_kv_heads: int = 32
    head_dim: Optional[int] = None            # default hidden_size // num_heads
    rope_theta: float = 10000.0
    # Frequency scaling from config.json:rope_scaling, in hashable tuple
    # form ("llama3", factor, low_freq, high_freq, original_max_pos) or
    # ("linear", factor, 0, 0, 0) — see ops/rope.py. None = unscaled.
    rope_scaling: Optional[Tuple[Any, ...]] = None
    rms_norm_eps: float = 1e-5
    max_position_embeddings: int = 4096
    tie_word_embeddings: bool = False
    attention_bias: bool = False              # True for Qwen2 QKV
    # Per-head RMSNorm on q/k before rope (Qwen3's replacement for the
    # Qwen2 QKV bias).
    qk_norm: bool = False
    # Checkpoint stores fused qkv_proj / gate_up_proj rows (Phi-3).
    # Pure load/save-mapping concern: the in-memory tree keeps separate
    # projections, so compute paths are untouched.
    fused_proj: bool = False
    # Sliding-window attention width W (Mistral v0.1's 4096, Phi-3-mini's
    # 2047): each token attends only to the last W positions including
    # itself. None/0 = full causal attention. Threaded as a static mask
    # parameter through every attention path (ops/attention.py), so one
    # transformer body serves both regimes; under a STATIC window the
    # paged decode kernel walks the window's pages alone
    # (ops/plan.py ``decode_walk_columns``, PR 34). In a ``layer_kinds``
    # model it is the window of the "swa" layers, which keep their keys
    # and values in a pool of their own (``num_swa_layers``); the "attn"
    # layers attend to everything.
    sliding_window: Optional[int] = None
    # Per-layer window activation (Gemma-2's alternating local/global
    # layers): tuple of bools, True = this layer uses sliding_window,
    # False = full attention. None = uniform (sliding_window applies to
    # every layer, or to none).
    layer_sliding: Optional[Tuple[bool, ...]] = None
    # Gemma-2 layer-body deltas (all default-off):
    # tanh soft-cap on attention logits / final lm_head logits.
    attn_logit_softcapping: float = 0.0
    final_logit_softcapping: float = 0.0
    # Attention scale = query_pre_attn_scalar**-0.5 instead of
    # head_dim**-0.5 (Gemma-2 fixes it at 256 regardless of head_dim).
    query_pre_attn_scalar: Optional[int] = None
    # Gemma family conventions: sqrt(hidden) embedding scale, the
    # four-norm block (``four_norm_block``, which this implies), and
    # tanh-GELU gating in the MLP. The (1 + weight) RMSNorm convention
    # is normalized away at checkpoint load (runtime/checkpoint.py adds
    # 1; save subtracts it back).
    gemma: bool = False
    # The four-norm block alone, for a family that has it without
    # Gemma's other conventions (Ouro: SiLU, no embedding scale, no
    # soft-cap): a second norm on each SUBLAYER'S OUTPUT before the
    # residual add. Read through ``four_norm_block``.
    four_norm: bool = False
    # Layer passes (Ouro's ``total_ut_steps``): the whole layer stack
    # runs this many times over THE SAME weights, the final norm after
    # every pass, each pass with its own keys and values (cache slot
    # pass * num_layers + layer: ``kv_cache_layers``). Above 1 the model
    # holds an exit gate (hidden -> 1, with a bias) that reads each
    # pass's normed state. 1 = every other model: no loop, no gate.
    total_ut_steps: int = 1
    # A row leaves the loop at the first pass whose cumulative exit
    # probability reaches this. Only 1.0 (every row runs every pass) is
    # implemented; ``from_hf_config`` refuses anything lower.
    early_exit_threshold: float = 1.0
    # Gemma-3: sliding (local) layers rotate with their own rope base
    # and WITHOUT the long-context scaling; full (global) layers use
    # rope_theta + rope_scaling. None = single rope base everywhere.
    rope_local_base_freq: Optional[float] = None
    # MoE (0 experts → dense MLP).
    num_experts: int = 0
    num_experts_per_tok: int = 2
    # Expert MLP width when it differs from the dense intermediate size
    # (Qwen3-MoE's moe_intermediate_size). None → intermediate_size.
    moe_intermediate_size: Optional[int] = None
    # Divide the selected experts' routing weights by their sum (Mixtral
    # semantics; Qwen3-MoE checkpoints declare it via norm_topk_prob —
    # False uses the raw softmax values).
    norm_topk_prob: bool = True
    # Checkpoint expert-key dialect: mlp.experts.N.{gate,up,down}_proj +
    # mlp.gate (Qwen3-MoE) vs block_sparse_moe.experts.N.w1/w3/w2 +
    # block_sparse_moe.gate (Mixtral).
    qwen_moe: bool = False
    # --- DeepSeek-V2 multi-head latent attention (MLA) ---
    # kv_lora_rank > 0 enables MLA: per token the cache holds ONE latent
    # row [kv_lora_rank + qk_rope_head_dim] instead of per-head K/V; the
    # up-projections are absorbed into the query/output sides so the
    # standard paged-attention machinery serves the latent pool with a
    # single KV "head" (models/transformer.py MLA branch).
    kv_lora_rank: int = 0
    q_lora_rank: Optional[int] = None   # None = direct q_proj (V2-Lite)
    qk_nope_head_dim: int = 0
    qk_rope_head_dim: int = 0
    v_head_dim: int = 0
    # DeepSeek MoE shape: shared experts run densely beside the routed
    # ones; routed weights scale by routed_scaling_factor; device-limited
    # routing restricts the top-k to topk_group of n_group expert groups.
    n_shared_experts: int = 0
    routed_scaling_factor: float = 1.0
    topk_method: str = "greedy"         # or "group_limited_greedy"
    n_group: Optional[int] = None
    topk_group: Optional[int] = None
    # First k layers use a dense MLP (DeepSeek's first_k_dense_replace);
    # the layer stack splits into a dense prefix + MoE suffix scan.
    first_k_dense_replace: int = 0
    # DeepSeek-V3 deltas over V2: sigmoid routing with a learned
    # per-expert selection bias (e_score_correction_bias — biases the
    # CHOICE, not the weights) and top-2-sum group scores; a flag for
    # the rope sub-head pair layout; and yarn's mscale² folded into the
    # softmax scale. The mscale fold is keyed on the CHECKPOINT (yarn
    # with nonzero mscale_all_dim), matching DeepSeek's original
    # modeling code and vLLM for both V2 and V3 — real V2/V2-Lite
    # checkpoints ship mscale_all_dim 0.707. (HF's in-tree V2 port
    # omits the factor; that is its divergence, not ours.)
    moe_scoring: str = "softmax"        # or "sigmoid" (V3)
    rope_interleave: bool = True
    mla_yarn_mscale: bool = False
    # GPT-OSS: per-head attention-sink logits, biased q/k/v/o, a
    # post-top-k-softmax router with bias, and clamped-GLU experts
    # ((up+1)·gate·sigmoid(1.702·gate), clamp ±7) with fused interleaved
    # gate_up weights split at load. Alternating sliding layers reuse
    # the layer_sliding machinery.
    gptoss: bool = False
    # Sparse dispatch capacity factor (parallel/expert.py): each expert
    # takes ≤ ceil(k·G·cf/E) tokens per group. ≥ E/k guarantees no drops;
    # 0 selects the dense-compute oracle (every expert on every token).
    # Decides nothing on the latent (MLA) path, whose sparse layers are
    # dropless (expert.dropless_moe).
    moe_capacity_factor: float = 2.0
    # Dispatch group size G: tokens route in groups so the dispatch /
    # combine masks are [G, E, C_g] per group — linear, not quadratic, in
    # window length (GShard's group axis; parallel/expert.py).
    moe_group_size: int = 512
    # Layers that differ in KIND (LFM2: gated short convolutions between
    # attention layers, a dense FFN in the leading layers and routed
    # experts after): one "<operator>+<ffn>" a layer, operator "conv" |
    # "attn" | "swa" | "mix" | "kda" | "ret", ffn "dense" | "moe". Data, not a family: the layer loop
    # (models/transformer.py, "Layers that differ in kind") walks
    # whatever pattern stands here. None = every layer alike (the
    # scanned bodies of the other families).
    layer_kinds: Optional[Tuple[str, ...]] = None
    # Taps of a "conv" layer's causal depthwise filter (HF conv_L_cache):
    # the layer's state is the last conv_kernel - 1 gated inputs.
    conv_kernel: int = 0
    # What the sigmoid gate adds to the chosen scores' sum before it
    # divides by it (norm_topk_prob): 1e-20 in DeepSeek-V3's gate, 1e-6
    # in LFM2's.
    moe_gate_eps: float = 1e-20
    # A Mamba-2 mixer BESIDE attention in a layer (Falcon-H1; the layer's
    # operator is "mix" in ``layer_kinds``): both read the same normed
    # input and their outputs are summed. ``ssm_heads`` heads of
    # ``ssm_head_dim``, each with a matrix state [ssm_head_dim,
    # ssm_state] kept in float32 in a pool addressed by SLOT
    # (transformer.init_kv_cache's fourth pool); B and C in
    # ``ssm_groups`` groups; a causal depthwise filter of ``conv_kernel``
    # taps (with a bias) over the ``ssm_conv_dim`` channels x | B | C;
    # the prefill scan in chunks of ``ssm_chunk``. 0 heads = no mixer.
    ssm_heads: int = 0
    ssm_head_dim: int = 0
    ssm_state: int = 0
    ssm_groups: int = 1
    ssm_chunk: int = 128
    # The family's scalar multipliers (muP), applied where the published
    # code applies them, never folded into a weight: on the embedding,
    # the logits, the attention branch's input, its keys and its output,
    # the mixer's input, the five segments z | x | B | C | dt of its input
    # projection and its output, the feed-forward's gate and its output.
    # 1.0 everywhere = every other model (no operation is emitted).
    embedding_multiplier: float = 1.0
    lm_head_multiplier: float = 1.0
    attention_in_multiplier: float = 1.0
    key_multiplier: float = 1.0
    attention_out_multiplier: float = 1.0
    ssm_in_multiplier: float = 1.0
    ssm_multipliers: Tuple[float, ...] = (1.0, 1.0, 1.0, 1.0, 1.0)
    ssm_out_multiplier: float = 1.0
    mlp_multipliers: Tuple[float, float] = (1.0, 1.0)
    # A delta-rule linear-attention layer (Kimi Delta Attention; operator
    # "kda" in ``layer_kinds``; Solar-Open2) INSTEAD of attention: it
    # keeps no keys and values. ``kda_heads`` heads, each with a matrix
    # state [kda_head_dim, kda_head_dim] (key channel x value channel)
    # in float32 in the pool by slot; q, k and v each through a causal
    # depthwise filter of ``conv_kernel`` taps (no bias) and SiLU, their
    # inputs kept as a ring over the 3 x ``kda_inner`` channels q | k | v;
    # a decay a KEY CHANNEL and an output gate, both through a low-rank
    # pair of rank ``kda_gate_rank``; ``kda_beta_scale`` 2 lets the
    # rank-one correction's eigenvalue pass below zero
    # (kda_allow_neg_eigval). 0 heads = no such layer.
    kda_heads: int = 0
    kda_head_dim: int = 0
    kda_gate_rank: int = 0
    kda_beta_scale: float = 1.0
    # False: attention layers rotate nothing (NoPE; Solar-Open2's
    # ``use_rope``).
    use_rope: bool = True
    # An elementwise sigmoid gate on attention's output before ``o_proj``,
    # from the layer's normed input (hidden -> heads x head_dim;
    # ``use_gqa_gate``).
    attn_gate: bool = False
    # Sliding-window attention layers beside full ones (operator "swa" in
    # ``layer_kinds``; Arcee's afmoe): a "swa" layer attends to the last
    # ``sliding_window`` positions and ALWAYS rotates; an "attn" layer
    # attends to everything and rotates by ``use_rope``. Its keys and
    # values live in a second pair of pools with a page allocator and a
    # page table of their own, trimmed behind the window as a row
    # advances (runtime/engine.py; docs/KV_CACHE.md "Two pools").
    # ``sandwich_norm``: the loop over kinds norms each sublayer's OUTPUT
    # before the residual add as well as its input (``post_attn_norm``,
    # ``post_mlp_norm`` beside ``input_norm`` and ``post_norm``).
    sandwich_norm: bool = False
    # A held SHARE of a wider router: ``expert_share_chips`` chips divide
    # each sparse layer's experts among them, ``num_experts`` is what THIS
    # chip holds and ``expert_share_rank`` which (experts rank *
    # num_experts onward). The gate scores and chooses over all
    # ``router_experts``; an assignment to an expert held elsewhere is
    # computed by nobody here and counted (``expert.MOE_STATS``
    # "elsewhere"). 1 chip = every expert held: every other model.
    expert_share_chips: int = 1
    expert_share_rank: int = 0
    # A power-retention layer (operator "ret" in ``layer_kinds``; Brumby)
    # INSTEAD of attention: causal attention whose weight is (q . k)^p in
    # place of exp(q . k), times a learned decay a KEY-VALUE head, the
    # output divided by the sum of its weights. For even p the weight is
    # a dot product of symmetric-power features, so the layer keeps a
    # fixed state by slot and NOTHING else: no keys and values, no tail,
    # no ring. Heads are the attention's (``num_heads`` queries over
    # ``num_kv_heads`` states), q and k normed and rotated as the
    # attention's are; a head's state is the symmetric half of the
    # degree-2 products of its ``head_dim`` key channels against its
    # value channels, and a normaliser beside it (``ret_state_rows``).
    # Only degree 2 is implemented. 0 = no such layer.
    ret_degree: int = 0
    dtype: str = "bfloat16"

    def __post_init__(self) -> None:
        if self.head_dim is None:
            object.__setattr__(self, "head_dim",
                               self.hidden_size // self.num_heads)
        if self.total_ut_steps < 1:
            raise ValueError(
                f"total_ut_steps={self.total_ut_steps}: a model runs its "
                f"layers at least once")
        ops = {k.split("+")[0] for k in self.layer_kinds or ()}
        if "mix" in ops and (self.ssm_heads <= 0
                             or self.ssm_heads % self.ssm_groups):
            raise ValueError(
                "a layer_kinds model with a 'mix' operator gives "
                "ssm_heads, a multiple of ssm_groups")
        if "kda" in ops and (self.kda_heads <= 0 or self.kda_head_dim <= 0
                             or self.kda_gate_rank <= 0
                             or self.conv_kernel <= 0):
            raise ValueError(
                "a layer_kinds model with a 'kda' operator gives "
                "kda_heads, kda_head_dim, kda_gate_rank and conv_kernel")
        if "conv" in ops and ops & {"mix", "kda"}:
            raise ValueError(
                "a layer_kinds model with a 'conv' operator has no 'mix' "
                "or 'kda' layer: their convolution tails are rings of "
                "conv_kernel rows, a 'conv' layer's are conv_kernel - 1 "
                "gated inputs, and the one pool of tails holds one kind "
                "of row")
        if len(ops & {"mix", "kda", "ret"}) > 1:
            raise ValueError(
                "a layer_kinds model has 'mix', 'kda' or 'ret' layers, one "
                "of the three: the pool of matrix states by slot has one "
                "shape a head and the ring one width (or none)")
        if "ret" in ops and (self.ret_degree != 2 or self.head_dim % 2
                             or self.num_heads % self.num_kv_heads):
            raise ValueError(
                "a layer_kinds model with a 'ret' operator gives "
                "ret_degree 2 (the one degree implemented), an even "
                "head_dim and num_heads a multiple of num_kv_heads")
        if "ret" in ops and "conv" in ops:
            raise ValueError(
                "a layer_kinds model with a 'ret' operator has no 'conv' "
                "layer: no model has both, and a 'conv' tail shifted in "
                "place beside a state that a discarded launch ahead must "
                "leave as it was is run by no test")
        if "swa" in ops and (not self.sliding_window
                             or ops - {"swa", "attn"}):
            raise ValueError(
                "a layer_kinds model with a 'swa' operator gives "
                "sliding_window and has 'swa' and 'attn' layers only: the "
                "window pool beside tails or a state by slot is run by no "
                "test")
        if not (0 <= self.expert_share_rank < self.expert_share_chips):
            raise ValueError(
                f"expert_share_rank={self.expert_share_rank} is not a "
                f"rank among expert_share_chips={self.expert_share_chips}")
        if self.total_ut_steps > 1:
            if self.mla or self.layer_kinds is not None or self.is_moe:
                raise ValueError(
                    "a layer loop (total_ut_steps > 1) wraps the dense "
                    "families' scan only: latent attention, layers that "
                    "differ in kind and sparse experts have none")
            if self.early_exit_threshold < 1.0:
                raise ValueError(
                    f"early_exit_threshold={self.early_exit_threshold} "
                    f"is not implemented: rows of one batch that leave "
                    f"the layer loop at different passes (the skipped "
                    f"passes' keys and values must still be filled) "
                    f"have no step program; only a threshold of 1 "
                    f"(every row runs all {self.total_ut_steps} passes) "
                    f"is served")

    @property
    def is_moe(self) -> bool:
        return self.num_experts > 0

    @property
    def is_mrope(self) -> bool:
        """Qwen2-VL-style 3-D multimodal rope (ops/rope.apply_mrope)."""
        return (self.rope_scaling is not None
                and self.rope_scaling[0] == "mrope")

    @property
    def mla(self) -> bool:
        return self.kv_lora_rank > 0

    @property
    def four_norm_block(self) -> bool:
        """Does a layer norm each sublayer's OUTPUT before the residual
        add, besides its input (``post_norm`` on the attention's,
        ``pre_ff_norm`` / ``post_ff_norm`` around the MLP)?"""
        return self.gemma or self.four_norm

    @property
    def looped(self) -> bool:
        return self.total_ut_steps > 1

    @property
    def kv_cache_layers(self) -> int:
        """Leading axis of the (k, v) pools, and what a worker
        advertises as its cache ids: one slot a layer that keeps keys
        and values, a PASS."""
        return self.num_attn_layers * self.total_ut_steps

    @property
    def num_attn_layers(self) -> int:
        """Layers that keep keys and values: the pools' leading axis."""
        if self.layer_kinds is None:
            return self.num_layers
        return sum(k.startswith(("attn+", "mix+")) for k in self.layer_kinds)

    @property
    def num_swa_layers(self) -> int:
        """Sliding-window attention layers: the leading axis of the
        WINDOW pools (a pair of their own, behind the others)."""
        return sum(k.startswith("swa+") for k in self.layer_kinds or ())

    @property
    def num_conv_layers(self) -> int:
        """Layers that keep a convolution tail (the third pool): a "conv"
        operator's, and the filter ring of a "mix" or a "kda" operator."""
        return sum(k.startswith(("conv+", "mix+", "kda+"))
                   for k in self.layer_kinds or ())

    @property
    def num_state_layers(self) -> int:
        """Layers that keep a matrix state a head (the fourth pool, by
        slot): a "mix" operator's mixer, a "kda" operator, a "ret"
        operator (which keeps nothing else)."""
        return sum(k.startswith(("mix+", "kda+", "ret+"))
                   for k in self.layer_kinds or ())

    @property
    def num_ssm_layers(self) -> int:
        """Layers with a Mamba-2 mixer beside attention."""
        return sum(k.startswith("mix+") for k in self.layer_kinds or ())

    @property
    def num_kda_layers(self) -> int:
        """Delta-rule linear-attention layers."""
        return sum(k.startswith("kda+") for k in self.layer_kinds or ())

    @property
    def num_ret_layers(self) -> int:
        """Power-retention layers."""
        return sum(k.startswith("ret+") for k in self.layer_kinds or ())

    @property
    def ret_blocks(self) -> int:
        """Blocks of a retention head's state: block d holds the products
        of key channels i and (i - d) mod head_dim for every i, and d = 0
        .. head_dim / 2 reaches every unordered pair once (the last block
        twice: its upper half stays zero)."""
        return self.head_dim // 2 + 1

    @property
    def ret_state_rows(self) -> int:
        """Rows of a retention head's state in the pool: ``ret_blocks``
        blocks of ``head_dim`` rows (a block is [value channel, key
        channel i]: the matrix, 8,320 rows of which 8,256 are the
        symmetric half at a head of 128) and the normaliser's
        ``ret_blocks`` rows [key channel i] behind them, to a tile's 8
        (8,392 in all). NOT the head_dim x head_dim = 16,384-row full
        product."""
        return self.ret_blocks * self.head_dim + -(-self.ret_blocks // 8) * 8

    @property
    def state_shape(self) -> Tuple[int, int, int]:
        """A layer's state of one sequence in the pool by slot: (heads,
        sublane axis, lane axis). A mixer's matrix is kept [state, head
        width], a delta-rule head's [key channel, value channel], a
        retention head's matrix AND normaliser as ``ret_state_rows`` rows
        over the key channel."""
        if self.num_ret_layers:
            return (self.num_kv_heads, self.ret_state_rows, self.head_dim)
        if self.num_kda_layers:
            return (self.kda_heads, self.kda_head_dim, self.kda_head_dim)
        return (self.ssm_heads, self.ssm_state, self.ssm_head_dim)

    @property
    def kda_inner(self) -> int:
        """Width of a delta-rule layer's q, k and v: heads x head width."""
        return self.kda_heads * self.kda_head_dim

    @property
    def ring_channels(self) -> int:
        """Channels of the filter ring a state layer keeps a page: a
        mixer's x | B | C, a delta-rule layer's q | k | v."""
        if self.num_kda_layers:
            return 3 * self.kda_inner
        return self.ssm_conv_dim

    @property
    def router_experts(self) -> int:
        """Outputs of a sparse layer's router: the experts of the whole
        deployment, of which this chip holds ``num_experts``."""
        return self.num_experts * self.expert_share_chips

    @property
    def first_held_expert(self) -> int:
        return self.num_experts * self.expert_share_rank

    @property
    def ssm_inner(self) -> int:
        """Width of the mixer's x and z: heads x head width."""
        return self.ssm_heads * self.ssm_head_dim

    @property
    def ssm_conv_dim(self) -> int:
        """Channels the mixer's short convolution runs over: x | B | C."""
        return self.ssm_inner + 2 * self.ssm_groups * self.ssm_state

    @property
    def conv_tail_shape(self) -> tuple:
        """One row of the pool of convolution tails (of which a model
        without convolution layers keeps none:
        ``transformer.init_kv_cache``). A "conv" layer keeps
        its last conv_kernel - 1 gated inputs over hidden_size channels,
        flat; a state layer (a mixer, a delta-rule layer) keeps a RING
        of conv_kernel inputs over its ``ring_channels``, [K, C], the
        input at position t in row t mod conv_kernel, so that a decode
        step writes one ring row and never one it reads (transformer,
        "A mixer beside attention")."""
        if self.num_state_layers:
            return (self.conv_kernel, self.ring_channels)
        return (max(self.conv_kernel - 1, 1) * self.hidden_size,)

    @property
    def dropless_experts(self) -> bool:
        """Do the sparse layers run ``expert.dropless_moe`` (what the
        gate chose is computed, nothing dropped, the routing counted)?
        The latent family's and a ``layer_kinds`` model's do; ``_mlp``'s
        families bucket by capacity."""
        return self.is_moe and (self.mla or self.layer_kinds is not None)

    @property
    def qk_head_dim(self) -> int:
        """Per-head query/key width under MLA (nope + rope parts)."""
        return self.qk_nope_head_dim + self.qk_rope_head_dim

    @property
    def kv_cache_heads(self) -> int:
        """KV-pool head count: 1 latent "head" under MLA."""
        return 1 if self.mla else self.num_kv_heads

    @property
    def kv_cache_dim(self) -> int:
        """KV-pool per-head width: the latent row under MLA."""
        return (self.kv_lora_rank + self.qk_rope_head_dim if self.mla
                else self.head_dim)

    @classmethod
    def llama3_8b(cls) -> "ModelConfig":
        return cls(name="llama3-8b", vocab_size=128256, hidden_size=4096,
                   intermediate_size=14336, num_layers=32, num_heads=32,
                   num_kv_heads=8, rope_theta=500000.0,
                   max_position_embeddings=8192)

    @classmethod
    def llama3_1b(cls) -> "ModelConfig":
        # Llama-3.2-1B shape: what chip_smoke.py serves on one chip.
        return cls(name="llama3-1b", vocab_size=128256, hidden_size=2048,
                   intermediate_size=8192, num_layers=16, num_heads=32,
                   num_kv_heads=8, head_dim=64, rope_theta=500000.0,
                   max_position_embeddings=8192, tie_word_embeddings=True)

    @classmethod
    def qwen2_7b(cls) -> "ModelConfig":
        return cls(name="qwen2-7b", vocab_size=152064, hidden_size=3584,
                   intermediate_size=18944, num_layers=28, num_heads=28,
                   num_kv_heads=4, rope_theta=1000000.0, rms_norm_eps=1e-6,
                   attention_bias=True, max_position_embeddings=32768)

    @classmethod
    def qwen25_7b(cls) -> "ModelConfig":
        # Qwen2.5-7B: identical wiring to Qwen2-7B (per-checkpoint quirks
        # come from config.json when loading from a model dir).
        return dataclasses.replace(cls.qwen2_7b(), name="qwen2.5-7b")

    @classmethod
    def qwen3_8b(cls) -> "ModelConfig":
        # Qwen3-8B: qk-norm generation (no attention bias).
        return cls(name="qwen3-8b", vocab_size=151936, hidden_size=4096,
                   intermediate_size=12288, num_layers=36, num_heads=32,
                   num_kv_heads=8, head_dim=128, rope_theta=1000000.0,
                   rms_norm_eps=1e-6, max_position_embeddings=40960,
                   qk_norm=True)

    @classmethod
    def mistral_7b(cls) -> "ModelConfig":
        # Mistral-7B v0.3: llama wiring, full attention (v0.2+ dropped
        # the sliding window).
        return cls(name="mistral-7b", vocab_size=32768, hidden_size=4096,
                   intermediate_size=14336, num_layers=32, num_heads=32,
                   num_kv_heads=8, rope_theta=1000000.0,
                   max_position_embeddings=32768)

    @classmethod
    def mistral_7b_v01(cls) -> "ModelConfig":
        # Mistral-7B v0.1: the original sliding-window checkpoint
        # (W=4096 over a 32k position range).
        return cls(name="mistral-7b-v01", vocab_size=32000,
                   hidden_size=4096, intermediate_size=14336,
                   num_layers=32, num_heads=32, num_kv_heads=8,
                   rope_theta=10000.0, max_position_embeddings=32768,
                   sliding_window=4096)

    @classmethod
    def phi3_mini(cls) -> "ModelConfig":
        # Phi-3-mini-4k: llama-shaped compute, fused-projection files,
        # sliding window 2047 (as the real config.json declares).
        return cls(name="phi3-mini", vocab_size=32064, hidden_size=3072,
                   intermediate_size=8192, num_layers=32, num_heads=32,
                   num_kv_heads=32, rope_theta=10000.0,
                   max_position_embeddings=4096, fused_proj=True,
                   sliding_window=2047)

    @classmethod
    def qwen3_30b_a3b(cls) -> "ModelConfig":
        # Qwen3-30B-A3B: 128-expert top-8 MoE with qk-norm attention and
        # narrow expert MLPs (3B active of 30B total).
        return cls(name="qwen3-30b-a3b", vocab_size=151936,
                   hidden_size=2048, intermediate_size=6144,
                   moe_intermediate_size=768, num_layers=48, num_heads=32,
                   num_kv_heads=4, head_dim=128, rope_theta=1000000.0,
                   rms_norm_eps=1e-6, max_position_embeddings=40960,
                   qk_norm=True, num_experts=128, num_experts_per_tok=8,
                   norm_topk_prob=True, qwen_moe=True)

    @classmethod
    def deepseek_v2_lite(cls) -> "ModelConfig":
        # DeepSeek-V2-Lite: MLA (latent KV rank 512 + 64 rope dims → the
        # paged cache holds 576 values/token instead of 16·384), 64
        # routed + 2 shared experts, greedy top-6, one dense first layer.
        # Real checkpoints add yarn scaling (factor 40, mscale 0.707 both
        # ways → attention factor cancels to 1.0) with the mscale²
        # softmax-scale fold live (mscale_all_dim 0.707 ≠ 0).
        return cls(name="deepseek-v2-lite", vocab_size=102400,
                   hidden_size=2048, intermediate_size=10944,
                   moe_intermediate_size=1408, num_layers=27,
                   num_heads=16, num_kv_heads=16, head_dim=64,
                   rope_theta=10000.0, rms_norm_eps=1e-6,
                   max_position_embeddings=163840,
                   rope_scaling=("yarn", 40.0, 32.0, 1.0, 4096, 1.0,
                                 True, 0.707),
                   kv_lora_rank=512, qk_nope_head_dim=128,
                   qk_rope_head_dim=64, v_head_dim=128,
                   num_experts=64, num_experts_per_tok=6,
                   n_shared_experts=2, first_k_dense_replace=1,
                   routed_scaling_factor=1.0, norm_topk_prob=False,
                   mla_yarn_mscale=True)

    @classmethod
    def deepseek_v3(cls) -> "ModelConfig":
        # DeepSeek-V3/R1 shape: 256-expert top-8 with sigmoid scoring +
        # learned selection bias, 8-group device-limited routing, MLA
        # with q compression, 3 dense prefix layers, yarn long context
        # (mscale folded into the softmax scale).
        return cls(name="deepseek-v3", vocab_size=129280,
                   hidden_size=7168, intermediate_size=18432,
                   moe_intermediate_size=2048, num_layers=61,
                   num_heads=128, num_kv_heads=128, head_dim=64,
                   rope_theta=10000.0, rms_norm_eps=1e-6,
                   max_position_embeddings=163840,
                   rope_scaling=("yarn", 40.0, 32.0, 1.0, 4096, 1.0,
                                 True, 1.0),
                   kv_lora_rank=512, q_lora_rank=1536,
                   qk_nope_head_dim=128, qk_rope_head_dim=64,
                   v_head_dim=128, num_experts=256,
                   num_experts_per_tok=8, n_shared_experts=1,
                   first_k_dense_replace=3, n_group=8, topk_group=4,
                   routed_scaling_factor=2.5, norm_topk_prob=True,
                   topk_method="group_limited_greedy",
                   moe_scoring="sigmoid", mla_yarn_mscale=True)

    @classmethod
    def gpt_oss_20b(cls) -> "ModelConfig":
        # GPT-OSS-20B: 32-expert top-4 clamped-GLU MoE, attention sinks,
        # alternating 128-token sliding layers, yarn long context.
        return cls(name="gpt-oss-20b", vocab_size=201088,
                   hidden_size=2880, intermediate_size=2880,
                   moe_intermediate_size=2880, num_layers=24,
                   num_heads=64, num_kv_heads=8, head_dim=64,
                   rope_theta=150000.0, rms_norm_eps=1e-5,
                   max_position_embeddings=131072,
                   rope_scaling=("yarn", 32.0, 32.0, 1.0, 4096,
                                 1.3465735902799727, False, 0.0),
                   attention_bias=True, sliding_window=128,
                   layer_sliding=tuple((i + 1) % 2 == 1
                                       for i in range(24)),
                   num_experts=32, num_experts_per_tok=4,
                   norm_topk_prob=False, gptoss=True)

    @classmethod
    def gemma2_9b(cls) -> "ModelConfig":
        # Gemma-2-9B: alternating local/global attention (W=4096 on even
        # layers), soft-caps, four-norm blocks, GeGLU, 256-dim heads.
        return cls(name="gemma2-9b", vocab_size=256000, hidden_size=3584,
                   intermediate_size=14336, num_layers=42, num_heads=16,
                   num_kv_heads=8, head_dim=256, rope_theta=10000.0,
                   rms_norm_eps=1e-6, max_position_embeddings=8192,
                   tie_word_embeddings=True, sliding_window=4096,
                   layer_sliding=tuple((i + 1) % 2 == 1
                                       for i in range(42)),
                   attn_logit_softcapping=50.0,
                   final_logit_softcapping=30.0,
                   query_pre_attn_scalar=256, gemma=True)

    @classmethod
    def gemma3_12b(cls) -> "ModelConfig":
        # Gemma-3-12B text stack: 5:1 local:global layers (W=1024),
        # per-layer rope bases (local 10k unscaled, global 1M with 8x
        # linear scaling), qk-norm, no soft-caps.
        return cls(name="gemma3-12b", vocab_size=262208,
                   hidden_size=3840, intermediate_size=15360,
                   num_layers=48, num_heads=16, num_kv_heads=8,
                   head_dim=256, rope_theta=1000000.0,
                   rope_local_base_freq=10000.0, rms_norm_eps=1e-6,
                   max_position_embeddings=131072,
                   rope_scaling=("linear", 8.0, 0.0, 0.0, 0),
                   tie_word_embeddings=True, qk_norm=True,
                   sliding_window=1024,
                   layer_sliding=tuple((i + 1) % 6 != 0
                                       for i in range(48)),
                   query_pre_attn_scalar=256, gemma=True)

    @classmethod
    def mixtral_8x7b(cls) -> "ModelConfig":
        # Mixtral-8x7B: the expert-parallel flagship (parallel/expert.py
        # top-k dispatch; experts shard over the mesh's ep axis).
        return cls(name="mixtral-8x7b", vocab_size=32000, hidden_size=4096,
                   intermediate_size=14336, num_layers=32, num_heads=32,
                   num_kv_heads=8, rope_theta=1000000.0,
                   max_position_embeddings=32768, num_experts=8,
                   num_experts_per_tok=2)

    @classmethod
    def tiny(cls, vocab_size: int = 256, num_experts: int = 0) -> "ModelConfig":
        """Small config for CPU tests."""
        return cls(name="tiny", vocab_size=vocab_size, hidden_size=64,
                   intermediate_size=128, num_layers=2, num_heads=4,
                   num_kv_heads=2, head_dim=16, rope_theta=10000.0,
                   max_position_embeddings=512, num_experts=num_experts)

    @classmethod
    def from_hf_config(cls, d: Dict[str, Any], name: str = "hf") -> "ModelConfig":
        """Build from a HuggingFace ``config.json`` dict (LlamaConfig/Qwen2Config).

        Unsupported architectures are REFUSED here, not approximated: a
        model that needs sliding-window masks or layer-body deltas this
        transformer does not implement must fail at load, never emit
        silently-wrong tokens."""
        mt = d.get("model_type", "llama")
        supported = ("llama", "mistral", "qwen2", "qwen3", "phi3",
                     "mixtral", "gemma2", "gemma3", "gemma3_text",
                     "qwen2_vl", "qwen2_5_vl",
                     "qwen3_moe", "deepseek_v2", "deepseek_v3",
                     "joyai_llm_flash", "gpt_oss", "lfm2_moe", "ouro",
                     "falcon_h1", "solar_open2", "brumby", "afmoe")
        # The latent family: DeepSeek-V2, and the V3 layer (sigmoid
        # scores, selection bias) that JD's JoyAI-LLM-Flash shares.
        _v3 = mt in ("deepseek_v3", "joyai_llm_flash")
        _dsk = mt == "deepseek_v2" or _v3
        if _dsk:
            tkm = d.get("topk_method")
            ok = ((None, "greedy", "group_limited_greedy")
                  if mt == "deepseek_v2"
                  # V3/R1 checkpoints say "noaux_tc" — the aux-loss-free
                  # biased sigmoid selection with grouped top-k, exactly
                  # the sigmoid gate implemented here.
                  else (None, "noaux_tc", "group_limited_greedy"))
            if tkm not in ok:
                raise ValueError(
                    f"deepseek topk_method {tkm!r} is not implemented")
            sf = d.get("scoring_func")
            want_sf = "sigmoid" if _v3 else "softmax"
            if sf is not None and sf != want_sf:
                raise ValueError(
                    f"{mt} with scoring_func {sf!r} is not implemented "
                    f"(expected {want_sf!r})")
        if mt == "qwen3_moe":
            # Mixed sparse/dense layer schedules can't share the one
            # scanned layer body — refuse, never approximate.
            if d.get("decoder_sparse_step", 1) != 1 \
                    or d.get("mlp_only_layers"):
                raise ValueError(
                    "qwen3_moe with dense layers (decoder_sparse_step "
                    "!= 1 or mlp_only_layers) is not implemented")
        if mt not in supported:
            raise ValueError(
                f"unsupported model_type {mt!r} (supported: "
                f"{', '.join(supported)})")
        if mt in ("qwen2_vl", "qwen2_5_vl", "gemma3"):
            # Current transformers nests the text stack under
            # text_config (published checkpoints keep it top-level) —
            # flatten, keeping the outer model_type.
            d = {**d, **d.get("text_config", {}), "model_type": mt}
        if mt in ("gemma3", "gemma3_text"):
            mt = "gemma3_text"
            # transformers' to_diff_dict omits class-default keys, and
            # Gemma3TextConfig's defaults differ from the generic HF
            # fallbacks below (head_dim 256 ≠ hidden/heads, theta 1e6,
            # 262k vocab, tied embeddings) — overlay them first so a
            # diff-style config.json loads faithfully.
            d = {**{"vocab_size": 262208, "head_dim": 256,
                    "rope_theta": 1000000.0,
                    "max_position_embeddings": 131072,
                    "sliding_window": 4096, "rms_norm_eps": 1e-6,
                    "tie_word_embeddings": True,
                    "query_pre_attn_scalar": 256,
                    "intermediate_size": d.get(
                        "intermediate_size", 9216),
                    "num_key_value_heads": d.get(
                        "num_key_value_heads", 4)},
                 **d, "model_type": mt}
            rs_kind = (d.get("rope_scaling") or {}).get(
                "rope_type", (d.get("rope_scaling") or {}).get("type"))
            if rs_kind not in (None, "default", "linear"):
                raise ValueError(
                    f"gemma3 rope_scaling {rs_kind!r} is not implemented "
                    f"(global layers support linear scaling only)")
        layer_kinds = None
        _lfm = mt == "lfm2_moe"
        if _lfm:
            # LiquidAI LFM2-MoE: layer_types says which operator a layer
            # has, num_dense_layers how many leading layers keep a dense
            # FFN; the rest route. Anything this loop has no body for is
            # refused, as everywhere here.
            if d.get("conv_bias"):
                raise ValueError("lfm2_moe with conv_bias is not implemented")
            ops = {"conv": "conv", "full_attention": "attn"}
            lt = d["layer_types"]
            if len(lt) != d["num_hidden_layers"] \
                    or any(t not in ops for t in lt):
                raise ValueError(
                    f"lfm2_moe layer_types {sorted(set(lt))} over "
                    f"{len(lt)} of {d['num_hidden_layers']} layers is not "
                    f"implemented (conv, full_attention; one a layer)")
            n_dense = int(d.get("num_dense_layers", 0))
            layer_kinds = tuple(
                ops[t] + ("+dense" if i < n_dense else "+moe")
                for i, t in enumerate(lt))
            # rope_parameters is transformers' newer spelling of
            # rope_theta + rope_scaling.
            rp = d.get("rope_parameters") or {}
            d = {"rope_theta": rp.get("rope_theta", 1000000.0),
                 "rope_scaling": rp if rp.get("rope_type", "default")
                 != "default" else None,
                 "rms_norm_eps": d.get("norm_eps", 1e-5),
                 # Lfm2MoeConfig ties the head to the embedding.
                 "tie_word_embeddings": True, **d}
        _fh1 = mt == "falcon_h1"
        if _fh1:
            # TII Falcon-H1: EVERY layer runs grouped-query attention and
            # a Mamba-2 mixer on the same normed input and sums them,
            # then a SwiGLU. What this loop has no body for is refused,
            # by the key that asks for it.
            for key, want in (("mamba_rms_norm", True),
                              ("mamba_norm_before_gate", False),
                              ("mamba_use_mlp", True),
                              ("rope_scaling", None),
                              ("attn_layer_indices", None),
                              ("attention_bias", False),
                              ("mamba_proj_bias", False),
                              ("mlp_bias", False),
                              ("projectors_bias", False),
                              ("hidden_act", "silu")):
                if d.get(key, want) != want:
                    raise ValueError(
                        f"falcon_h1 with {key}={d[key]!r} is not "
                        f"implemented (only {want!r})")
            if d["mamba_d_ssm"] != d["mamba_n_heads"] * d["mamba_d_head"]:
                raise ValueError(
                    f"falcon_h1 with mamba_d_ssm={d['mamba_d_ssm']} is not "
                    f"implemented (only mamba_n_heads x mamba_d_head = "
                    f"{d['mamba_n_heads'] * d['mamba_d_head']})")
            layer_kinds = ("mix+dense",) * d["num_hidden_layers"]
        _so2 = mt == "solar_open2"
        if _so2:
            # Upstage Solar-Open2: delta-rule linear-attention layers
            # (Kimi Delta Attention) between gated grouped-query
            # attention layers that rotate nothing, routed experts in
            # every layer. What this loop has no body for is refused, by
            # the key that asks for it.
            for key, want in (("first_k_dense_replace", 0),
                              ("kda_use_full_proj", False),
                              ("kda_allow_neg_eigval", True),
                              ("use_rope", False),
                              ("use_gqa_gate", True),
                              ("n_shared_experts", 1),
                              ("attention_bias", False)):
                if d.get(key, want) != want:
                    raise ValueError(
                        f"solar_open2 with {key}={d[key]!r} is not "
                        f"implemented (only {want!r})")
            la = d["linear_attn_config"]
            if la.get("num_kv_heads") not in (None, la["num_heads"]):
                raise ValueError(
                    f"solar_open2 with linear_attn_config.num_kv_heads="
                    f"{la['num_kv_heads']!r} is not implemented (only as "
                    f"many as num_heads)")
            gqa = set(d["gqa_layers"])
            layer_kinds = tuple(
                ("attn" if i in gqa else "kda") + "+moe"
                for i in range(d["num_hidden_layers"]))
            # n_routed_experts counts the experts HELD here, of
            # ``expert_share_chips`` times as many routed (the
            # deployment's share: the program's own two keys beside the
            # published ones, 1 and 0 where a config has neither).
        _brm = mt == "brumby"
        if _brm:
            # Manifest AI Brumby: Qwen3's block (q and k normed a head and
            # rotated, SwiGLU, untied head) with power retention in place
            # of softmax attention in EVERY layer. The config carries no
            # key of the retention itself: degree 2 and one sigmoid decay
            # a key-value head are the family's published description.
            for key, want in (("attention_bias", False),
                              ("hidden_act", "silu"),
                              ("rope_scaling", None),
                              ("use_sliding_window", False)):
                if d.get(key, want) != want:
                    raise ValueError(
                        f"brumby with {key}={d[key]!r} is not implemented "
                        f"(only {want!r})")
            layer_kinds = ("ret+dense",) * d["num_hidden_layers"]
        _afm = mt == "afmoe"
        if _afm:
            # Arcee afmoe (Trinity): sliding-window layers that rotate
            # between full layers that rotate nothing, q and k normed a
            # head, a sigmoid gate on attention's output, four norms a
            # layer, dense feed-forwards in the leading layers and
            # sigmoid-routed experts beside one shared expert after them,
            # the embedding scaled by sqrt(hidden) (mup_enabled). The
            # config has no key for the gate, the norms or the unrotated
            # full layers: they are the family's published block. What
            # this loop has no body for is refused, by the key that asks
            # for it.
            for key, want in (("rope_scaling", None),
                              ("n_group", 1), ("topk_group", 1),
                              ("num_expert_groups", 1),
                              ("num_limited_groups", 1),
                              ("score_func", "sigmoid"),
                              ("hidden_act", "silu"),
                              ("num_shared_experts", 1),
                              ("route_norm", True),
                              ("mup_enabled", True),
                              ("attention_bias", False),
                              ("tie_word_embeddings", False)):
                if d.get(key, want) != want:
                    raise ValueError(
                        f"afmoe with {key}={d[key]!r} is not implemented "
                        f"(only {want!r})")
            ops = {"sliding_attention": "swa", "full_attention": "attn"}
            lt = d["layer_types"]
            if len(lt) != d["num_hidden_layers"] \
                    or any(t not in ops for t in lt):
                raise ValueError(
                    f"afmoe layer_types {sorted(set(lt))} over {len(lt)} of "
                    f"{d['num_hidden_layers']} layers is not implemented "
                    f"(sliding_attention, full_attention; one a layer)")
            if "sliding_attention" in lt and not d.get("sliding_window"):
                raise ValueError(
                    "afmoe with sliding_attention layers and no "
                    "sliding_window is not implemented")
            n_dense = int(d.get("num_dense_layers", 0))
            layer_kinds = tuple(
                ops[t] + ("+dense" if i < n_dense else "+moe")
                for i, t in enumerate(lt))
            # num_experts counts the experts HELD here, of
            # ``expert_share_chips`` times as many routed (Solar-Open2's
            # two keys for a deployment's share; 1 and 0 without them).
        if mt == "ouro" and set(d.get("layer_types") or ()) \
                - {"full_attention"}:
            raise ValueError(
                f"ouro layer_types {sorted(set(d['layer_types']))} is not "
                f"implemented (full_attention in every layer)")
        layer_sliding = None
        if mt in ("gemma2", "gemma3_text", "gpt_oss"):
            # Alternating local/global layers: HF's layer_types (or the
            # shared default pattern — sliding on even-indexed layers).
            L = d["num_hidden_layers"]
            if mt == "gemma3_text":
                # Gemma-3 default pattern: every 6th layer is global.
                lt = d.get("layer_types") or [
                    "full_attention" if (i + 1) % 6 == 0
                    else "sliding_attention" for i in range(L)]
            else:
                lt = d.get("layer_types") or [
                    "sliding_attention" if (i + 1) % 2
                    else "full_attention" for i in range(L)]
            layer_sliding = tuple(t == "sliding_attention" for t in lt)
        # sliding_window is honored for ANY supported model_type — real
        # Phi-3 checkpoints declare it too (Phi-3-mini-4k ships 2047), not
        # just Mistral v0.1 (round-3 advisor finding). A declared window
        # at least max_position_embeddings is inert and normalized away so
        # the full-attention fast paths stay eligible.
        sw = d.get("sliding_window") or None
        if sw is not None \
                and mt in ("qwen2", "qwen3", "qwen2_vl", "qwen2_5_vl",
                           "qwen3_moe", "brumby") \
                and not d.get("use_sliding_window", False):
            # Qwen2-family raw config.json declares-but-disables the
            # window (e.g. Qwen2.5-7B-Instruct-1M: sliding_window 32768,
            # use_sliding_window false — and HF's default for the gate is
            # False, so an omitted key also means full attention): HF
            # torch normalizes it to None; so must we. Mistral/Phi-3
            # have no gate — a set window is always live there.
            sw = None
        if sw is not None \
                and sw >= d.get("max_position_embeddings", 4096) \
                and mt not in ("gemma3_text", "afmoe"):
            # An at-least-context-wide window never binds, so dropping
            # it keeps full-attention fast paths eligible. Gemma-3 is
            # EXEMPT: its sliding/full layer pattern also selects the
            # per-layer rope base, which must survive even when the
            # window itself is inert (the mask is harmless then).
            sw = None
        if sw is not None:
            # Qwen2-family per-layer windows: the first max_window_layers
            # layers run FULL attention, the rest SWA. A uniform window
            # can express the all-SWA (0) and all-full (>= L) extremes
            # only; a genuine mix must refuse, not approximate.
            mwl = d.get("max_window_layers")
            L = d["num_hidden_layers"]
            if mwl is not None and 0 < mwl < L:
                raise ValueError(
                    f"per-layer sliding window (max_window_layers={mwl} "
                    f"of {L}) is not implemented")
            if mwl is not None and mwl >= L:
                sw = None           # every layer full attention — inert
        if layer_sliding is not None and not any(layer_sliding):
            # Every layer declared full attention: a shipped
            # sliding_window value is inert (HF ignores it too).
            sw = None
        if sw is None:
            layer_sliding = None
        elif layer_sliding is not None and all(layer_sliding):
            layer_sliding = None        # uniform window, static fast path
        parsed_rs = cls._parse_rope_scaling(
            d.get("rope_scaling"),
            d.get("max_position_embeddings", 4096))
        return cls(
            name=name,
            vocab_size=d["vocab_size"],
            hidden_size=d["hidden_size"],
            intermediate_size=d["intermediate_size"],
            num_layers=d["num_hidden_layers"],
            num_heads=d["num_attention_heads"],
            num_kv_heads=d.get("num_key_value_heads", d["num_attention_heads"]),
            # A latent model's head widths are qk_nope/qk_rope/v_head_dim;
            # a head_dim its config.json also carries (JoyAI-LLM-Flash:
            # 64, the rope part) is no width of any of its tensors.
            head_dim=None if _dsk else d.get("head_dim"),
            # float: a published 100000000000 (Falcon-H1) overflows int32
            rope_theta=float(d.get("rope_theta", 10000.0)),
            rms_norm_eps=d.get("rms_norm_eps", 1e-5),
            max_position_embeddings=d.get("max_position_embeddings", 4096),
            tie_word_embeddings=d.get("tie_word_embeddings",
                                      mt == "gemma2"),
            attention_bias=d.get("attention_bias",
                                 d.get("model_type")
                                 in ("qwen2", "qwen2_vl", "qwen2_5_vl",
                                     "gpt_oss")),
            qk_norm=d.get("model_type") in ("qwen3", "qwen3_moe",
                                            "gemma3_text", "lfm2_moe",
                                            "brumby", "afmoe"),
            fused_proj=d.get("model_type") == "phi3",
            sliding_window=sw,
            layer_sliding=layer_sliding,
            # HF's Gemma2Config DEFAULTS the caps to 50/30 when the keys
            # are absent; an explicit null disables them. Mirror both.
            attn_logit_softcapping=(
                (d["attn_logit_softcapping"] or 0.0
                 if "attn_logit_softcapping" in d else 50.0)
                if mt == "gemma2" else 0.0),
            final_logit_softcapping=(
                (d["final_logit_softcapping"] or 0.0
                 if "final_logit_softcapping" in d else 30.0)
                if mt == "gemma2" else 0.0),
            query_pre_attn_scalar=(
                d.get("query_pre_attn_scalar", 256)
                if mt in ("gemma2", "gemma3_text") else None),
            gemma=mt in ("gemma2", "gemma3_text"),
            # ByteDance Ouro: a llama layer with a second norm on each
            # sublayer's output, the stack run total_ut_steps times over
            # the same weights (the exit threshold is checked by
            # ``__post_init__``, which refuses one under 1).
            four_norm=mt == "ouro",
            total_ut_steps=(int(d.get("total_ut_steps", 1))
                            if mt == "ouro" else 1),
            early_exit_threshold=(float(d.get("early_exit_threshold", 1.0))
                                  if mt == "ouro" else 1.0),
            rope_local_base_freq=(d.get("rope_local_base_freq", 10000.0)
                                  if mt == "gemma3_text" else None),
            num_experts=(d.get("num_experts", 0)
                         if mt in ("qwen3_moe", "lfm2_moe", "afmoe")
                         else d.get("n_routed_experts", 0) if _dsk or _so2
                         else d.get("num_local_experts", 0)),
            num_experts_per_tok=d.get("num_experts_per_tok", 2),
            moe_intermediate_size=d.get("moe_intermediate_size"),
            kv_lora_rank=(d.get("kv_lora_rank") or 0) if _dsk else 0,
            q_lora_rank=d.get("q_lora_rank") if _dsk else None,
            qk_nope_head_dim=d.get("qk_nope_head_dim", 0) if _dsk else 0,
            qk_rope_head_dim=d.get("qk_rope_head_dim", 0) if _dsk else 0,
            v_head_dim=d.get("v_head_dim", 0) if _dsk else 0,
            n_shared_experts=(d.get("n_shared_experts") or 0)
            if _dsk or _so2 else int(d.get("num_shared_experts", 1))
            if _afm else 0,
            routed_scaling_factor=(float(d.get("route_scale", 1.0)) if _afm
                                   else d.get("routed_scaling_factor", 1.0)),
            # V3's "noaux_tc" IS grouped selection under sigmoid scoring;
            # with one group (JoyAI-LLM-Flash) nothing is limited.
            topk_method=(("group_limited_greedy"
                          if (d.get("n_group") or 1) > 1 else "greedy")
                         if _v3 else d.get("topk_method", "greedy")),
            n_group=d.get("n_group"),
            topk_group=d.get("topk_group"),
            first_k_dense_replace=(d.get("first_k_dense_replace", 0)
                                   if _dsk else 0),
            # LFM2's gate is V3's with one group: sigmoid scores, a bias
            # that shapes the choice only (use_expert_bias; zeros without
            # it), the chosen scores over their sum + 1e-6.
            moe_scoring="sigmoid" if _v3 or _lfm or _so2 or _afm
            else "softmax",
            moe_gate_eps=1e-6 if _lfm else 1e-20,
            layer_kinds=layer_kinds,
            conv_kernel=(int(d.get("conv_L_cache", 0)) if _lfm
                         else int(d["mamba_d_conv"]) if _fh1
                         else int(d["linear_attn_config"]
                                  ["short_conv_kernel_size"]) if _so2
                         else 0),
            **({"kda_heads": int(d["linear_attn_config"]["num_heads"]),
                "kda_head_dim": int(d["linear_attn_config"]["head_dim"]),
                # kda_use_full_proj false: the decay's and the output
                # gate's projections are low-rank pairs, rank = head_dim
                # (Kimi Linear's form).
                "kda_gate_rank": int(d["linear_attn_config"]["head_dim"]),
                "kda_beta_scale": 2.0,
                "use_rope": False, "attn_gate": True,
                "expert_share_chips": int(d.get("expert_share_chips", 1)),
                "expert_share_rank": int(d.get("expert_share_rank", 0))}
               if _so2 else {}),
            **({"ssm_heads": int(d["mamba_n_heads"]),
                "ssm_head_dim": int(d["mamba_d_head"]),
                "ssm_state": int(d["mamba_d_state"]),
                "ssm_groups": int(d["mamba_n_groups"]),
                "ssm_chunk": int(d["mamba_chunk_size"]),
                "embedding_multiplier": float(d["embedding_multiplier"]),
                "lm_head_multiplier": float(d["lm_head_multiplier"]),
                "attention_in_multiplier":
                    float(d["attention_in_multiplier"]),
                "key_multiplier": float(d["key_multiplier"]),
                "attention_out_multiplier":
                    float(d["attention_out_multiplier"]),
                "ssm_in_multiplier": float(d["ssm_in_multiplier"]),
                "ssm_multipliers":
                    tuple(float(x) for x in d["ssm_multipliers"]),
                "ssm_out_multiplier": float(d["ssm_out_multiplier"]),
                "mlp_multipliers":
                    tuple(float(x) for x in d["mlp_multipliers"])}
               if _fh1 else {}),
            **({"use_rope": False, "attn_gate": True, "sandwich_norm": True,
                "embedding_multiplier": float(d["hidden_size"]) ** 0.5,
                "expert_share_chips": int(d.get("expert_share_chips", 1)),
                "expert_share_rank": int(d.get("expert_share_rank", 0))}
               if _afm else {}),
            ret_degree=2 if _brm else 0,
            gptoss=mt == "gpt_oss",
            rope_interleave=bool(d.get("rope_interleave", True)),
            # The mscale² softmax-scale fold follows the CHECKPOINT, not
            # the model_type: DeepSeek's own modeling code (and vLLM)
            # apply it whenever yarn ships a nonzero mscale_all_dim —
            # real V2/V2-Lite checkpoints carry 0.707 — while HF's
            # in-tree V2 port omits it (round-4 advisor finding).
            mla_yarn_mscale=bool(
                _dsk and parsed_rs is not None and parsed_rs[0] == "yarn"
                and len(parsed_rs) > 7 and parsed_rs[7]),
            # HF defaults: Mixtral always normalizes top-k weights;
            # Qwen3MoeConfig defaults norm_topk_prob to FALSE when the
            # key is absent; the DeepSeek-V2 gate never normalizes.
            norm_topk_prob=bool(d.get("norm_topk_prob",
                                      mt != "qwen3_moe"))
            and mt != "deepseek_v2",
            qwen_moe=mt == "qwen3_moe",
            rope_scaling=parsed_rs,
        )

    @staticmethod
    def _parse_rope_scaling(rs: Optional[Dict[str, Any]],
                            max_position_embeddings: int = 4096
                            ) -> Optional[Tuple[Any, ...]]:
        """config.json:rope_scaling dict → the hashable tuple ops/rope.py
        takes. Unknown types raise at load time rather than silently
        mis-rotating positions (checkpoint-fidelity contract)."""
        if not rs:
            return None
        kind = rs.get("rope_type", rs.get("type"))
        if rs.get("mrope_section") and kind in (None, "default", "mrope"):
            # Qwen2-VL 3-D multimodal rope: (t, h, w) frequency-band
            # sections (ops/rope.py apply_mrope). Published checkpoints
            # say type "mrope"; transformers re-serializes it as
            # "default" + mrope_section.
            return ("mrope", tuple(int(s) for s in rs["mrope_section"]))
        if kind in (None, "default"):
            return None
        if kind == "llama3":
            return ("llama3", float(rs["factor"]),
                    float(rs["low_freq_factor"]),
                    float(rs["high_freq_factor"]),
                    int(rs["original_max_position_embeddings"]))
        if kind == "linear":
            return ("linear", float(rs["factor"]), 0.0, 0.0, 0)
        if kind == "yarn":
            # NTK-by-parts (YaRN, 2309.00071): low-frequency bands
            # interpolate by `factor`, high-frequency extrapolate, a
            # linear ramp blends between; cos/sin scale by the attention
            # factor (inferred from factor/mscale when not explicit —
            # HF modeling_rope_utils._compute_yarn_parameters).
            factor = float(rs["factor"])
            attn = rs.get("attention_factor")
            if attn is None:
                ms, msa = rs.get("mscale"), rs.get("mscale_all_dim")

                def _mscale(scale, m=1.0):
                    import math
                    return (0.1 * m * math.log(scale) + 1.0) if scale > 1 \
                        else 1.0

                attn = (_mscale(factor, ms) / _mscale(factor, msa)
                        if ms and msa else _mscale(factor))
            orig = int(rs.get("original_max_position_embeddings")
                       or max_position_embeddings)
            return ("yarn", factor,
                    float(rs.get("beta_fast") or 32.0),
                    float(rs.get("beta_slow") or 1.0),
                    orig, float(attn),
                    bool(rs.get("truncate", True)),
                    float(rs.get("mscale_all_dim") or 0.0))
        raise NotImplementedError(
            f"rope_scaling type {kind!r} not supported")


@dataclasses.dataclass
class EngineConfig:
    """Worker-engine runtime config (paged KV cache + continuous batching)."""

    page_size: int = 64                 # tokens per KV page (HBM granularity)
    num_pages: int = 1024               # KV pool size (per layer, per chip-shard)
    max_model_len: int = 2048           # max tokens per sequence
    max_batch_size: int = 8             # decode batch capacity
    max_prefill_tokens: int = 2048      # prefill token budget per step
    prefill_buckets: Tuple[int, ...] = (64, 128, 256, 512, 1024, 2048)
    enable_prefix_cache: bool = True
    # Top-k alternative logprobs computed inside every compiled step
    # (static k — 0 disables the top_k entirely; OpenAI callers may ask
    # for at most this many ``top_logprobs``).
    num_top_logprobs: int = 0
    # Parallel degrees of this instance's mesh.
    tp: int = 1
    dp: int = 1
    sp: int = 1
    # Offline (batch) requests are preempted by online ones.
    max_num_seqs: int = 256             # scheduler queue cap
    # Write-then-attend KV plumbing (round-5 "known residue" fix): the
    # pool rides the layer scan as a carry, each layer writes its fresh
    # K/V in place (aliased Pallas writer) BEFORE attending, and the
    # attention kernels read everything — including the current window /
    # token — from the pool. Kills the jit-call-boundary pool copies XLA
    # inserts around the post-scan writer (~10-15 GB per prefill call at
    # the bench shape). None = auto: on wherever the Pallas kernels are
    # on, off on the pure-XLA path. Resolved once, when an engine is
    # built (ops/plan.py KernelPlan.from_env), where
    # XLLM_WRITE_THEN_ATTEND=0/1 overrides this field.
    write_then_attend: Optional[bool] = None
    # One-dispatch ragged mixed steps: when on, an iteration with both
    # running decoders and schedulable prefill windows packs BOTH into
    # one ragged batch (decode rows are length-1 continuation windows)
    # and launches ONE attention program
    # (ops/pallas/ragged_attention.py) instead of a decode step plus a
    # prefill call. Pure-decode and pure-prefill iterations keep their
    # dedicated programs.
    # None = auto: off (opt-in while the kernel soaks). Resolved once,
    # when an engine is built (ops/plan.py KernelPlan.from_env), where
    # XLLM_RAGGED_ATTN=0/1 overrides this field.
    ragged_attn: Optional[bool] = None
    # The iteration's token budget (staggered admission, arxiv
    # 2512.16134): every engine iteration decodes the running set FIRST
    # (bounding TPOT by construction), then spends the residual of this
    # budget on chunked-prefill windows — the prefill quantum shrinks
    # under decode load instead of the engine running prompt-priority
    # steps that stall every live stream. 0 = default from
    # max_prefill_tokens. Env XLLM_STEP_TOKEN_BUDGET overrides.
    step_token_budget: int = 0
    # Anti-starvation deadline (ms): once the oldest waiting prompt has
    # queued past this, the iteration's prefill budget is floored at one
    # minimum quantum (smallest prefill bucket) even if decode consumed
    # the whole token budget. Derived from the service plane's default
    # TTFT target (1000 ms): half the budget reserved for queueing
    # leaves the other half for the prefill itself. 0 = the floor
    # applies every iteration. Env XLLM_PREFILL_DEADLINE_MS overrides.
    prefill_deadline_ms: float = 500.0
    # Tiered KV spill (docs/KV_CACHE.md): when > 0, prefix-cache pages
    # evicted from HBM under allocation pressure are parked in a bounded
    # host-DRAM tier of this many MB instead of dropped, and restored
    # through the donated pool scatter on a later prefix hit. 0 = off
    # (evictions drop content, the pre-tier behavior). Env
    # XLLM_KV_SPILL_MB overrides.
    kv_spill_mb: float = 0.0
    # Optional disk tier behind the DRAM tier: blocks LRU-demoted from
    # DRAM land as raw header+bytes .kv files under this directory
    # (cold path; .npz can't round-trip ml_dtypes bfloat16), bounded by
    # kv_spill_disk_mb. Needs BOTH knobs: an empty dir OR a zero budget
    # means no disk tier (demotions drop). Env XLLM_KV_SPILL_DIR /
    # XLLM_KV_SPILL_DISK_MB override.
    kv_spill_dir: str = ""
    kv_spill_disk_mb: float = 0.0

    def __post_init__(self) -> None:
        if self.max_model_len % self.page_size != 0:
            raise ValueError(
                f"max_model_len={self.max_model_len} must be a multiple of "
                f"page_size={self.page_size}")
        self.max_pages_per_seq = self.max_model_len // self.page_size
        env = os.environ.get("XLLM_STEP_TOKEN_BUDGET", "").strip()
        if env:
            try:
                self.step_token_budget = int(env)
            except ValueError:
                pass
        env = os.environ.get("XLLM_PREFILL_DEADLINE_MS", "").strip()
        if env:
            try:
                self.prefill_deadline_ms = float(env)
            except ValueError:
                pass
        env = os.environ.get("XLLM_KV_SPILL_MB", "").strip()
        if env:
            try:
                self.kv_spill_mb = float(env)
            except ValueError:
                pass
        env = os.environ.get("XLLM_KV_SPILL_DIR", "").strip()
        if env:
            self.kv_spill_dir = env
        env = os.environ.get("XLLM_KV_SPILL_DISK_MB", "").strip()
        if env:
            try:
                self.kv_spill_disk_mb = float(env)
            except ValueError:
                pass


def load_json(path: str) -> Dict[str, Any]:
    with open(path, "r", encoding="utf-8") as f:
        return json.load(f)


def options_from_env(**overrides: Any) -> ServiceOptions:
    """Build ServiceOptions honoring the reference's env toggles
    (``ENABLE_DECODE_RESPONSE_TO_SERVICE``, ``ENABLE_XLLM_DEBUG_LOG`` —
    http_service/service.cpp:54-55, common/utils.cpp:28-41)."""
    opts = ServiceOptions(**overrides)
    if os.environ.get("ENABLE_DECODE_RESPONSE_TO_SERVICE", "").lower() in (
            "1", "true", "yes"):
        opts.enable_decode_response_to_service = True
    raw_mc = os.environ.get("XLLM_MAX_CONCURRENCY", "").strip()
    if raw_mc and "max_concurrency" not in overrides:
        # Admission-gate ceiling override: the saturation harness
        # (benchmarks/service_bench.py --saturate) spawns a master that
        # must admit thousands of concurrent streams; there is no CLI
        # flag for it because only benchmarks legitimately raise it.
        try:
            opts.max_concurrency = max(1, int(raw_mc))
        except ValueError:
            pass
    return opts
