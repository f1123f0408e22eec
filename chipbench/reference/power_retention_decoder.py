"""Plain reference of the power-retention decoder (``model_type``
``brumby``; Manifest AI, "Scaling Context Requires Rethinking Attention",
arXiv:2507.04239): Qwen3's block with softmax attention replaced in
EVERY layer by power retention of degree 2. Straightforward ``jax.numpy``
in float32 at precision ``highest``: no kernel, no cache, no pool, no
batching, and nothing imported from the program. **The layer runs in its
ATTENTION form over the whole sequence**, in blocks of queries: the
quotient of two masked sums. No state, no expanded features, no chunks:
the program never computes the attention form over more than a chunk of
a page (its prefill carries a state between chunks and its decode is a
kernel over the state), so the two share no arithmetic beyond the
projections and check each other.

With ``n`` RMSNorm (weight multiplies), every product without bias:

    h        = n_in(x)
    q        = rope(n_q(h Wq))      [T, heads, d]     n_q, n_k: a head, weight [d]
    k        = rope(n_k(h Wk))      [T, kv heads, d]
    v        = h Wv                 [T, kv heads, d]
    gamma_t  = sigmoid(h Wg)        [T, kv heads]     one decay a KEY-VALUE head
    G_t      = sum_{s <= t} log gamma_s
    w_tj     = exp(G_t[b] - G_j[b]) (q_t[a] . k_j[b] / sqrt(d))^2     j <= t, b = a // (heads / kv heads)
    o_t[a]   = sum_j w_tj v_j[b] / sum_j w_tj
    x        = x + concat(o) Wo
    g        = n_ff(x)
    x        = x + (silu(g Wgate) * (g Wup)) Wdown
    logits   = n_f(x) W_head

The own term has weight ``(q_t . k_t)^2 / d``, undecayed. The rotation
is half against half over the whole head, unscaled (``rope_scaling:
null`` is all this body knows).

Leaves carry the checkpoint's names (Qwen3's, and ``self_attn.g_proj``
for the decay: assumed, ``meta.json``) and are stored [in, out].

``mm`` is the matrix multiplication of every linear layer, the decay's
too, swapped by the lower-precision control (``chipbench/check.py``).
Every layer is of the one kind ``ret+dense`` and hands nothing on.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST
KIND = "ret+dense"
BLOCK = 512             # queries a block (check.py pads a sequence to it)


def mm_f32(x, w):
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HIGHEST)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def embed(tokens, embed_w):
    return embed_w[tokens].astype(jnp.float32)


def rope_halves(x, positions, theta):
    """x [T, H, d]: (x[i], x[i + d/2]) turns by position * theta ** (-2i/d)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None, None] * inv[None, None, :]
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin],
                           axis=-1)


def retention(h, lp, cfg, mm):
    """Power retention of degree 2 over one whole sequence h [T, D], in
    the attention form."""
    if cfg.get("rope_scaling"):
        raise ValueError("this body rotates unscaled (rope_scaling null)")
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, theta = int(cfg["head_dim"]), float(cfg["rope_theta"])
    eps = float(cfg["rms_norm_eps"])
    t = h.shape[0]
    pos = jnp.arange(t)
    q = rms_norm(mm(h, lp["self_attn.q_proj"]).reshape(t, H, d),
                 lp["self_attn.q_norm"], eps)
    k = rms_norm(mm(h, lp["self_attn.k_proj"]).reshape(t, Hkv, d),
                 lp["self_attn.k_norm"], eps)
    v = mm(h, lp["self_attn.v_proj"]).reshape(t, Hkv, d)
    q, k = rope_halves(q, pos, theta), rope_halves(k, pos, theta)
    G = jnp.cumsum(jax.nn.log_sigmoid(mm(h, lp["self_attn.g_proj"])),
                   axis=0)                                      # [T, Hkv]
    block = min(BLOCK, t)
    if t % block:
        raise ValueError(f"{t} positions are not whole blocks of {block}")

    def head(args):                   # one query head over its kv head
        qh, kh, vh, Gh = args         # [T, d], [T, d], [T, d], [T]

        def rows(i):                  # one block of queries
            at = i * block + jnp.arange(block)
            s = jnp.matmul(qh[at], kh.T, precision=HIGHEST) / jnp.sqrt(
                jnp.float32(d))                                 # [blk, T]
            seen = pos[None, :] <= at[:, None]
            w = jnp.exp(jnp.where(seen, Gh[at][:, None] - Gh[None, :],
                                  -jnp.inf)) * s * s
            return jnp.matmul(w, vh, precision=HIGHEST) \
                / jnp.sum(w, axis=-1, keepdims=True)
        return jax.lax.map(rows, jnp.arange(t // block)).reshape(t, d)

    g = H // Hkv                      # query head a reads kv head a // g
    o = jax.lax.map(head, (
        jnp.swapaxes(q, 0, 1), jnp.repeat(jnp.swapaxes(k, 0, 1), g, axis=0),
        jnp.repeat(jnp.swapaxes(v, 0, 1), g, axis=0),
        jnp.repeat(G.T, g, axis=0)))
    return mm(jnp.swapaxes(o, 0, 1).reshape(t, H * d), lp["self_attn.o_proj"])


def layer_kinds(cfg: Dict[str, Any]):
    return [KIND] * int(cfg["num_hidden_layers"])


def layer(x, lp: Dict[str, Any], cfg: Dict[str, Any], mm: Callable,
          kind: str, carry):
    """One layer over one whole sequence x [T, D] (float32); returns
    ``(x, None)``: this family hands nothing on."""
    if kind != KIND:
        raise ValueError(f"no layer of kind {kind!r} in this family")
    if cfg.get("attention_bias") or cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("this body has no bias and a SiLU")
    eps = float(cfg["rms_norm_eps"])
    x = x + retention(rms_norm(x, lp["input_layernorm"], eps), lp, cfg, mm)
    g = rms_norm(x, lp["post_attention_layernorm"], eps)
    act = jax.nn.silu(mm(g, lp["mlp.gate_proj"])) * mm(g, lp["mlp.up_proj"])
    return x + mm(act, lp["mlp.down_proj"]), None


def logits(x, final_norm, lm_head, cfg: Dict[str, Any],
           mm: Callable = mm_f32):
    return mm(rms_norm(x, final_norm, float(cfg["rms_norm_eps"])), lm_head)


def forward(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
            mm: Callable = mm_f32, n_layers: Optional[int] = None):
    """Logits [T, V] of one whole sequence: the small-size entry the CPU
    tests use. ``params['layers']`` is a list of per-layer dicts as
    stored."""
    x, carry = embed(jnp.asarray(tokens), params["embed"]), None
    for lp in params["layers"][:n_layers]:
        x, carry = layer(x, lp, cfg, mm, KIND, carry)
    return logits(x, params["final_norm"], params["lm_head"], cfg, mm)
