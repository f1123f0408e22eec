"""Plain reference of the family with delta-rule linear-attention layers
between gated attention layers that rotate nothing (``model_type``
``solar_open2``: Kimi Delta Attention, arXiv:2510.26692, beside
grouped-query attention, over sigmoid-routed experts and one shared
expert in every layer). Straightforward ``jax.numpy`` in float32 at
precision ``highest``: no kernel, cache, state pool, chunk, batching,
sorting or capacity, and nothing imported from the program.

Every layer: ``x += operator(norm(x)); x += ffn(norm(x))``, no biases.

- ``kda`` operator (every layer not in ``gqa_layers``), H heads of width
  d: ``q~, k~, v~ = h W_q, h W_k, h W_v``, each through its own causal
  depthwise filter of ``short_conv_kernel_size`` taps over the WHOLE
  sequence (zeros before it) and SiLU; a head's ``q = l2norm(q) /
  sqrt(d)``, ``k = l2norm(k)``; a decay a KEY CHANNEL ``alpha_t =
  exp(-exp(A_log[head]) * softplus(h W_fa W_fb + dt_bias))``; ``beta_t =
  2 sigmoid(h W_b)`` a head (``kda_allow_neg_eigval``). The state ``S
  [d, d]`` a head starts at zero and moves ONE POSITION AFTER ANOTHER
  (``lax.scan`` over the whole sequence): ``S' = Diag(alpha_t) S``;
  ``S = S' + beta_t k_t (v_t - S'^T k_t)^T``; ``o_t = S^T q_t``. Then
  ``(rmsnorm_head(o_t) * w_norm * sigmoid(h W_ga W_gb)) W_o``. (The
  program's prefill solves a chunk at a time and its decode is a
  kernel.)
- ``attn`` operator (``gqa_layers``): grouped-query attention without a
  rotation (``use_rope`` false), scores ``q . k / sqrt(head_dim)``,
  causal softmax over the whole context, the output under an
  elementwise ``sigmoid(h W_g)`` before ``o_proj`` (``use_gqa_gate``).
  Heads go one after another.
- experts, every layer: ``s = sigmoid(gate(h))`` over ALL the
  deployment's routed experts (``n_routed_experts`` HELD here times
  ``expert_share_chips``); the ``num_experts_per_tok`` largest of ``s +
  e_score_correction_bias`` are chosen; a chosen expert weighs by its
  ``s`` over the chosen ones' sum (+ 1e-20) times
  ``routed_scaling_factor``. ONLY THE HELD experts (``expert_share_rank``
  x held onward) are computed, each over the tokens that chose it: what
  a layer hands on is this chip's PART of the routed sum plus the
  shared expert, as the program's. ``held=None`` in ``experts`` gives
  the uncut layer (a test adds the shares up to it).

``mm`` is the matrix multiplication of every linear layer (the router's
and the low-rank pairs' too), swapped by the lower-precision control
(``chipbench/check.py``). Layers hand nothing on.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

# What no family's reference does differently: the latent family's body
# has them.
from chipbench.reference.latent_moe import (  # noqa: F401
    BLOCK, HIGHEST, embed, mm_f32, rms_norm, swiglu)


def layer_kinds(cfg: Dict[str, Any]):
    gqa = set(cfg["gqa_layers"])
    return [("attn" if i in gqa else "kda") + "+moe"
            for i in range(int(cfg["num_hidden_layers"]))]


def short_filter(x, w):
    """silu of a causal depthwise filter w [K, C] over x [T, C], zeros
    before the sequence; tap j on the input K - 1 - j positions back."""
    K, t = w.shape[0], x.shape[0]
    pad = jnp.pad(x, ((K - 1, 0), (0, 0)))
    w = w.astype(jnp.float32)
    return jax.nn.silu(sum(w[j] * pad[j:j + t] for j in range(K)))


def l2norm(x):
    return x * jax.lax.rsqrt(jnp.sum(x * x, axis=-1, keepdims=True) + 1e-6)


def delta_attention(h, lp, cfg, mm):
    """The delta-rule layer over one whole sequence h [T, D], from a
    zero state, one position after another."""
    if cfg.get("kda_use_full_proj") or not cfg.get("kda_allow_neg_eigval"):
        raise ValueError("this body has low-rank gates and beta in (0, 2)")
    la = cfg["linear_attn_config"]
    H, d = int(la["num_heads"]), int(la["head_dim"])
    t = h.shape[0]
    q = l2norm(short_filter(mm(h, lp["self_attn.q_proj"]),
                            lp["self_attn.q_conv1d"]).reshape(t, H, d)
               ) / jnp.sqrt(jnp.float32(d))
    k = l2norm(short_filter(mm(h, lp["self_attn.k_proj"]),
                            lp["self_attn.k_conv1d"]).reshape(t, H, d))
    v = short_filter(mm(h, lp["self_attn.v_proj"]),
                     lp["self_attn.v_conv1d"]).reshape(t, H, d)
    f = mm(mm(h, lp["self_attn.f_a_proj"]), lp["self_attn.f_b_proj"])
    alpha = jnp.exp(
        -jnp.exp(lp["self_attn.A_log"].astype(jnp.float32))[None, :, None]
        * jax.nn.softplus(f + lp["self_attn.dt_bias"].astype(jnp.float32)
                          ).reshape(t, H, d))
    beta = 2.0 * jax.nn.sigmoid(mm(h, lp["self_attn.b_proj"]))   # [T, H]

    def step(S, at):                           # S [H, d, d]: one position
        q_t, k_t, v_t, a_t, b_t = at
        S = a_t[:, :, None] * S
        r = v_t - jnp.einsum("hkv,hk->hv", S, k_t, precision=HIGHEST)
        S = S + (b_t[:, None] * k_t)[:, :, None] * r[:, None, :]
        return S, jnp.einsum("hkv,hk->hv", S, q_t, precision=HIGHEST)

    _, o = jax.lax.scan(step, jnp.zeros((H, d, d), jnp.float32),
                        (q, k, v, alpha, beta))
    gate = mm(mm(h, lp["self_attn.g_a_proj"]), lp["self_attn.g_b_proj"])
    o = rms_norm(o, lp["self_attn.o_norm"], float(cfg["rms_norm_eps"])) \
        * jax.nn.sigmoid(gate).reshape(t, H, d)
    return mm(o.reshape(t, H * d), lp["self_attn.o_proj"])


def attention(h, lp, cfg, mm):
    if cfg.get("use_rope", True) or not cfg.get("use_gqa_gate"):
        raise ValueError("this body rotates nothing and gates its output")
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg["head_dim"])
    t = h.shape[0]
    pos = jnp.arange(t)
    q = mm(h, lp["self_attn.q_proj"]).reshape(t, H, d)
    k = mm(h, lp["self_attn.k_proj"]).reshape(t, Hkv, d)
    v = mm(h, lp["self_attn.v_proj"]).reshape(t, Hkv, d)
    causal = pos[None, :] <= pos[:, None]
    scale = 1.0 / jnp.sqrt(jnp.float32(d))

    def head(qh, kh, vh):                                        # one head
        s = jnp.matmul(qh, kh.T, precision=HIGHEST) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.matmul(p, vh, precision=HIGHEST)              # [T, d]

    # query head i reads key-value head i // (H / Hkv)
    o = jax.lax.map(lambda a: head(*a), (
        jnp.swapaxes(q, 0, 1),
        jnp.repeat(jnp.swapaxes(k, 0, 1), H // Hkv, axis=0),
        jnp.repeat(jnp.swapaxes(v, 0, 1), H // Hkv, axis=0)))
    o = jnp.swapaxes(o, 0, 1).reshape(t, H * d)
    return mm(o * jax.nn.sigmoid(mm(h, lp["self_attn.g_proj"])),
              lp["self_attn.o_proj"])


def share(cfg: Dict[str, Any]):
    """(first held expert, experts held, experts routed)."""
    held = int(cfg["n_routed_experts"])
    chips = int(cfg.get("expert_share_chips", 1))
    return int(cfg.get("expert_share_rank", 0)) * held, held, held * chips


def gate_map(h, lp, cfg, mm):
    """[T, routed]: each token's weight on every expert of the
    deployment, zero off its choice."""
    E, k = share(cfg)[2], int(cfg["num_experts_per_tok"])
    s = jax.nn.sigmoid(mm(h, lp["mlp.gate"]))
    choice = s + lp["mlp.gate.e_score_correction_bias"].astype(jnp.float32)
    chosen = jax.lax.top_k(choice, k)[1]                         # [T, k]
    on = jnp.any(chosen[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    w = jnp.where(on, s, 0.0)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * float(cfg.get("routed_scaling_factor", 1.0))


def routed_part(h, w, gate, up, down, mm):
    """The experts whose stacks are given, each over the tokens that
    weigh it in ``w`` [T, len(stack)] and no others, ``BLOCK`` at a time."""
    t = h.shape[0]
    block = min(BLOCK, t)

    def one(acc, e):                   # expert e over the tokens it weighs
        g, u, d, we = e
        n = jnp.sum(we != 0)
        mine = jnp.pad(jnp.nonzero(we != 0, size=t, fill_value=0)[0],
                       (0, -t % block))

        def some(b, acc):              # its b-th block of tokens
            at = jax.lax.dynamic_slice(mine, (b * block,), (block,))
            live = b * block + jnp.arange(block) < n
            y = swiglu(h[at], g, u, d, mm)
            return acc.at[at].add(y * jnp.where(live, we[at], 0.0)[:, None])

        return jax.lax.fori_loop(0, -(-n // block), some, acc), None

    return jax.lax.scan(one, jnp.zeros_like(h), (gate, up, down, w.T))[0]


def shared_expert(h, lp, mm):
    return swiglu(h, lp["mlp.shared_experts.gate_proj"],
                  lp["mlp.shared_experts.up_proj"],
                  lp["mlp.shared_experts.down_proj"], mm)


def experts(h, lp, cfg, mm):
    """This chip's part of the routed sum (the held experts alone, under
    the gate over all the routed ones) plus the shared expert."""
    first, held, _ = share(cfg)
    w = gate_map(h, lp, cfg, mm)[:, first:first + held]
    return routed_part(h, w, lp["mlp.experts.gate_proj"],
                       lp["mlp.experts.up_proj"],
                       lp["mlp.experts.down_proj"], mm) \
        + shared_expert(h, lp, mm)


def layer(x, lp: Dict[str, Any], cfg: Dict[str, Any], mm: Callable,
          kind: str, carry):
    """One layer of ``kind`` over one whole sequence x [T, D] (float32);
    returns ``(x, carry)``."""
    if carry is not None:
        raise ValueError("this family's layers hand nothing on")
    if int(cfg.get("first_k_dense_replace", 0)):
        raise ValueError("this body routes in every layer")
    eps = float(cfg["rms_norm_eps"])
    h = rms_norm(x, lp["input_layernorm"], eps)
    if kind == "kda+moe":
        x = x + delta_attention(h, lp, cfg, mm)
    elif kind == "attn+moe":
        x = x + attention(h, lp, cfg, mm)
    else:
        raise ValueError(f"no layer of kind {kind!r} in this family")
    h = rms_norm(x, lp["post_attention_layernorm"], eps)
    return x + experts(h, lp, cfg, mm), None


def logits(x, final_norm, lm_head, cfg: Dict[str, Any],
           mm: Callable = mm_f32):
    return mm(rms_norm(x, final_norm, float(cfg["rms_norm_eps"])), lm_head)


def forward(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
            mm: Callable = mm_f32, n_layers: Optional[int] = None):
    """Logits [T, V] of one whole sequence: the small-size entry the CPU
    tests use. ``params['layers']`` is a list of per-layer dicts as
    stored, in layer order."""
    x = embed(jnp.asarray(tokens), params["embed"])
    for kind, lp in list(zip(layer_kinds(cfg), params["layers"]))[:n_layers]:
        x, _ = layer(x, lp, cfg, mm, kind, None)
    return logits(x[:len(tokens)], params["final_norm"], params["lm_head"],
                  cfg, mm)
