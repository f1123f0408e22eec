"""Plain reference of the family whose layers differ in kind
(``model_type`` ``lfm2_moe``; HF ``Lfm2Moe*``). Straightforward
``jax.numpy`` in float32 at precision ``highest``: no kernel, cache,
state, batching, sorting or capacity, and nothing imported from the
program.

Every layer: ``x += operator(norm(x)); x += ffn(norm(x))``, no biases.

- ``conv`` operator, the gated short convolution: ``[b, c, u] = h W_in``
  (in that order); ``z = b * u``; a depthwise causal filter of
  ``conv_L_cache`` taps over the WHOLE sequence, ``conv_t = sum_j w_j *
  z_{t-K+1+j}`` with ``z`` zero before the sequence; ``(c * conv) W_out``.
  No activation. (The program keeps the last K - 1 values of ``z`` beside
  each page and never sees the whole sequence.)
- ``attn`` operator: grouped-query attention, ``q`` and ``k`` normed per
  head (a learned weight over the head's width) BEFORE the rotation; the
  rotation is over the whole head, half against half, unscaled
  (``rope_parameters.rope_type: default`` is all this body knows);
  scores ``q . k / sqrt(width)``, causal softmax over the whole context.
  Heads go one after another, so that a 10k-token sequence's scores are
  one head's [T, T] at a time.
- ``dense`` feed-forward: SwiGLU (``w1`` gates, ``w3`` lifts, ``w2``
  projects back).
- ``moe`` feed-forward: ``s = sigmoid(gate(h))``; the ``num_experts_per_tok``
  largest of ``s + expert_bias`` are CHOSEN (``use_expert_bias``: the bias
  shapes the choice only); a chosen expert WEIGHS by its ``s`` over the
  chosen ones' sum plus 1e-6 (``norm_topk_prob``) times
  ``routed_scaling_factor``. Experts go one after another, each over the
  tokens that chose it and no others (the rest have weight zero in that
  map), ``BLOCK`` of them at a time until it has done them all: no
  capacity, nothing dropped, nothing sorted (PR 36's allowance). No
  shared expert, no groups.

``mm`` is the matrix multiplication of every linear layer (the router's
too), swapped by the lower-precision control (``chipbench/check.py``).
Layers hand nothing on: ``carry`` comes in and goes out as ``None``.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

# What no family's reference does differently (float32 matmul at
# ``highest``, the norm, SwiGLU, a sequence's rows filled up to one
# shape): the latent family's body has them.
from chipbench.reference.latent_moe import (  # noqa: F401
    BLOCK, HIGHEST, embed, mm_f32, rms_norm, swiglu)


def layer_kinds(cfg: Dict[str, Any]):
    ops = {"conv": "conv", "full_attention": "attn"}
    return [ops[t] + ("+dense" if i < int(cfg["num_dense_layers"])
                      else "+moe")
            for i, t in enumerate(cfg["layer_types"])]


def short_conv(h, lp, cfg, mm):
    K = int(cfg["conv_L_cache"])
    if cfg.get("conv_bias"):
        raise ValueError("this body's filter has no bias")
    t = h.shape[0]
    b, c, u = jnp.split(mm(h, lp["conv.in_proj"]), 3, axis=-1)
    z = jnp.pad(b * u, ((K - 1, 0), (0, 0)))        # zero before the start
    w = lp["conv.conv"].astype(jnp.float32)                      # [K, D]
    conv = sum(w[j] * z[j:j + t] for j in range(K))
    return mm(c * conv, lp["conv.out_proj"])


def rope_halves(x, positions, theta):
    """x [T, H, d]: (x[i], x[i + d/2]) turns by position * theta ** (-2i/d)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def attention(h, lp, cfg, mm):
    rp = cfg.get("rope_parameters") or {}
    if rp.get("rope_type", "default") != "default":
        raise ValueError("this body rotates unscaled (rope_type default)")
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d = int(cfg["hidden_size"]) // H
    eps, theta = float(cfg["norm_eps"]), float(rp["rope_theta"])
    t = h.shape[0]
    pos = jnp.arange(t)
    q = mm(h, lp["self_attn.q_proj"]).reshape(t, H, d)
    k = mm(h, lp["self_attn.k_proj"]).reshape(t, Hkv, d)
    v = mm(h, lp["self_attn.v_proj"]).reshape(t, Hkv, d)
    q = rope_halves(rms_norm(q, lp["self_attn.q_layernorm"], eps), pos, theta)
    k = rope_halves(rms_norm(k, lp["self_attn.k_layernorm"], eps), pos, theta)
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
    return mm(jnp.swapaxes(o, 0, 1).reshape(t, H * d),
              lp["self_attn.out_proj"])


def gate_map(h, lp, cfg, mm):
    """[T, E]: each token's weight on every expert, zero off its choice."""
    E, k = int(cfg["num_experts"]), int(cfg["num_experts_per_tok"])
    s = jax.nn.sigmoid(mm(h, lp["feed_forward.gate"]))
    choice = s
    if cfg.get("use_expert_bias"):
        choice = s + lp["feed_forward.expert_bias"].astype(jnp.float32)
    chosen = jax.lax.top_k(choice, k)[1]                         # [T, k]
    on = jnp.any(chosen[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    w = jnp.where(on, s, 0.0)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-6)
    return w * float(cfg.get("routed_scaling_factor", 1.0))


def experts(h, lp, cfg, mm):
    w = gate_map(h, lp, cfg, mm)                                 # [T, E]
    t = h.shape[0]
    block = min(BLOCK, t)

    def one(acc, e):                   # expert e over the tokens it weighs
        g, u, d, we = e
        n = jnp.sum(we != 0)
        # Its tokens first, in order; the list is filled up with token 0
        # at weight zero, to a whole number of blocks.
        mine = jnp.pad(jnp.nonzero(we != 0, size=t, fill_value=0)[0],
                       (0, -t % block))

        def some(b, acc):              # its b-th block of tokens
            at = jax.lax.dynamic_slice(mine, (b * block,), (block,))
            live = b * block + jnp.arange(block) < n
            y = swiglu(h[at], g, u, d, mm)
            return acc.at[at].add(y * jnp.where(live, we[at], 0.0)[:, None])

        return jax.lax.fori_loop(0, -(-n // block), some, acc), None

    routed, _ = jax.lax.scan(one, jnp.zeros_like(h), (
        lp["feed_forward.experts.w1"], lp["feed_forward.experts.w3"],
        lp["feed_forward.experts.w2"], w.T))
    return routed


def layer(x, lp: Dict[str, Any], cfg: Dict[str, Any], mm: Callable,
          kind: str, carry):
    """One layer of ``kind`` over one whole sequence x [T, D] (float32);
    returns ``(x, carry)``."""
    if carry is not None:
        raise ValueError("this family's layers hand nothing on")
    eps = float(cfg["norm_eps"])
    op, ffn = kind.split("+")
    h = rms_norm(x, lp["operator_norm"], eps)
    if op == "conv":
        x = x + short_conv(h, lp, cfg, mm)
    elif op == "attn":
        x = x + attention(h, lp, cfg, mm)
    else:
        raise ValueError(f"no operator {op!r} in this family")
    h = rms_norm(x, lp["ffn_norm"], eps)
    if ffn == "dense":
        return x + swiglu(h, lp["feed_forward.w1"], lp["feed_forward.w3"],
                          lp["feed_forward.w2"], mm), None
    if ffn == "moe":
        return x + experts(h, lp, cfg, mm), None
    raise ValueError(f"no feed-forward {ffn!r} in this family")


def logits(x, final_norm, lm_head, cfg: Dict[str, Any],
           mm: Callable = mm_f32):
    return mm(rms_norm(x, final_norm, float(cfg["norm_eps"])), lm_head)


def forward(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
            mm: Callable = mm_f32, n_layers: Optional[int] = None):
    """Logits [T, V] of one whole sequence: the small-size entry the CPU
    tests use. ``params['layers']`` is a list of per-layer dicts as
    stored, in the order of ``layer_types``."""
    x = embed(jnp.asarray(tokens), params["embed"])
    for kind, lp in list(zip(layer_kinds(cfg), params["layers"]))[:n_layers]:
        x, _ = layer(x, lp, cfg, mm, kind, None)
    return logits(x[:len(tokens)], params["final_norm"], params["lm_head"],
                  cfg, mm)
