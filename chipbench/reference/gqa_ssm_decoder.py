"""Plain reference of the family with a Mamba-2 mixer BESIDE attention in
every layer (``model_type`` ``falcon_h1``; HF ``FalconH1*``).
Straightforward ``jax.numpy`` in float32 at precision ``highest``: no
kernel, no cache, no pool, no chunking, no batching, and nothing imported
from the program. **The recurrence runs token by token** (``lax.scan``
over positions); the program's prefill runs the chunked form and its
decode a kernel, so the two derivations check each other.

With ``n`` RMSNorm (weight multiplies), every product without bias:

    x0       = embed[token] * embedding_multiplier
    h        = n_in(x)
    # attention branch: grouped-query, causal, full
    q, k, v  = (h a_in) Wq, (h a_in) Wk * key_multiplier, (h a_in) Wv
    a        = softmax(rope(q) rope(k)^T / sqrt(head_dim)) v Wo * attention_out_multiplier
    # mixer branch
    [z | xBC | dt] = ((h * ssm_in_multiplier) W_in) * mup
    xBC      = silu(causal depthwise filter of mamba_d_conv taps over xBC + bias)
    x, B, C  = split(xBC)           # x: heads x head width; B, C: groups x state
    dt       = softplus(dt + dt_bias)
    S_t      = exp(dt_t A) S_{t-1} + dt_t x_t (outer) B_t      # A = -exp(A_log)
    y_t      = S_t C_t + D x_t
    y        = rmsnorm over mamba_n_groups groups of (y * silu(z)), * w_norm
    m        = y W_out * ssm_out_multiplier
    x        = x + a + m
    g        = n_ff(x)
    x        = x + (silu(g Wg * mlp_multipliers[0]) * (g Wu)) Wd * mlp_multipliers[1]
    logits   = n_f(x) W_head * lm_head_multiplier

``mup`` scales the five segments z, x, B, C, dt of the projection by
``ssm_multipliers`` in that order (the family's ``compute_mup_vector``);
a group of B and C serves ``heads / groups`` consecutive heads; the
rotation is half against half over the whole head, unscaled
(``rope_scaling: null`` is all this body knows). No multiplier is folded
into a weight.

Leaves carry the published checkpoint's names and are stored [in, out];
``mamba.in_proj`` is the ONE published matrix [hidden, z | xBC | dt] (the
program keeps its three column blocks apart), ``mamba.conv1d.weight``
is [taps, channels], tap j on the input ``taps - 1 - j`` positions back.

``mm`` is the matrix multiplication of every linear layer, swapped by
the lower-precision control (``chipbench/check.py``). ``embed`` is given
no configuration, so the FIRST layer (``carry`` None) applies
``embedding_multiplier`` and hands on a marker that it is done.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

HIGHEST = jax.lax.Precision.HIGHEST


def mm_f32(x, w):
    return jnp.matmul(x.astype(jnp.float32), w.astype(jnp.float32),
                      precision=HIGHEST)


def rms_norm(x, w, eps):
    x = x.astype(jnp.float32)
    var = jnp.mean(x * x, axis=-1, keepdims=True)
    return x * jax.lax.rsqrt(var + eps) * w.astype(jnp.float32)


def embed(tokens, embed_w):
    """x [T, D], NOT yet scaled by ``embedding_multiplier`` (``layer``)."""
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


def attention(h, lp, cfg, mm):
    if cfg.get("rope_scaling"):
        raise ValueError("this body rotates unscaled (rope_scaling null)")
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, theta = int(cfg["head_dim"]), float(cfg["rope_theta"])
    t = h.shape[0]
    pos = jnp.arange(t)
    h = h * float(cfg["attention_in_multiplier"])
    q = mm(h, lp["self_attn.q_proj"]).reshape(t, H, d)
    k = (mm(h, lp["self_attn.k_proj"]) * float(cfg["key_multiplier"])
         ).reshape(t, Hkv, d)
    v = mm(h, lp["self_attn.v_proj"]).reshape(t, Hkv, d)
    q, k = rope_halves(q, pos, theta), rope_halves(k, pos, theta)
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
              lp["self_attn.o_proj"]) * float(cfg["attention_out_multiplier"])


def mup_vector(cfg):
    """[z | x | B | C | dt] widths of the projection, each scaled by its
    ``ssm_multipliers`` entry."""
    I = int(cfg["mamba_d_ssm"])
    gn = int(cfg["mamba_n_groups"]) * int(cfg["mamba_d_state"])
    widths = (I, I, gn, gn, int(cfg["mamba_n_heads"]))
    return jnp.concatenate([jnp.full((w,), float(m), jnp.float32)
                            for w, m in zip(widths, cfg["ssm_multipliers"])])


def mixer(h, lp, cfg, mm):
    """The Mamba-2 mixer over one whole sequence h [T, D], from a zero
    state, one position after another."""
    if not cfg.get("mamba_rms_norm", True) \
            or cfg.get("mamba_norm_before_gate", False):
        raise ValueError("this body gates, then norms in groups")
    K, G = int(cfg["mamba_d_conv"]), int(cfg["mamba_n_groups"])
    H, P = int(cfg["mamba_n_heads"]), int(cfg["mamba_d_head"])
    N, I = int(cfg["mamba_d_state"]), int(cfg["mamba_d_ssm"])
    t = h.shape[0]
    proj = mm(h * float(cfg["ssm_in_multiplier"]), lp["mamba.in_proj"]) \
        * mup_vector(cfg)
    z, xbc, dt = jnp.split(proj, [I, 2 * I + 2 * G * N], axis=-1)
    w = lp["mamba.conv1d.weight"].astype(jnp.float32)            # [K, C]
    pad = jnp.pad(xbc, ((K - 1, 0), (0, 0)))         # zero before the start
    conv = sum(w[j] * pad[j:j + t] for j in range(K))
    if cfg.get("mamba_conv_bias", True):
        conv = conv + lp["mamba.conv1d.bias"].astype(jnp.float32)
    u = jax.nn.silu(conv)
    x = u[:, :I].reshape(t, H, P)
    Bm = u[:, I:I + G * N].reshape(t, G, N)
    Cm = u[:, I + G * N:].reshape(t, G, N)
    dt = jax.nn.softplus(dt + lp["mamba.dt_bias"].astype(jnp.float32))
    A = -jnp.exp(lp["mamba.A_log"].astype(jnp.float32))          # [H]
    D = lp["mamba.D"].astype(jnp.float32)

    def step(S, at):                           # S [H, P, N]: one position
        x_t, B_t, C_t, dt_t = at
        Bh = jnp.repeat(B_t, H // G, axis=0)                     # [H, N]
        Ch = jnp.repeat(C_t, H // G, axis=0)
        S = jnp.exp(dt_t * A)[:, None, None] * S \
            + (dt_t[:, None] * x_t)[:, :, None] * Bh[:, None, :]
        y = jnp.einsum("hpn,hn->hp", S, Ch, precision=HIGHEST)
        return S, y + D[:, None] * x_t

    _, y = jax.lax.scan(step, jnp.zeros((H, P, N), jnp.float32),
                        (x, Bm, Cm, dt))
    y = (y.reshape(t, I) * jax.nn.silu(z)).reshape(t, G, I // G)
    y = y * jax.lax.rsqrt(jnp.mean(y * y, axis=-1, keepdims=True)
                          + float(cfg["rms_norm_eps"]))
    y = y.reshape(t, I) * lp["mamba.norm"].astype(jnp.float32)
    return mm(y, lp["mamba.out_proj"]) * float(cfg["ssm_out_multiplier"])


def layer_kinds(cfg: Dict[str, Any]):
    return ["mix+dense"] * int(cfg["num_hidden_layers"])


def layer(x, lp: Dict[str, Any], cfg: Dict[str, Any], mm: Callable,
          kind: str, carry):
    """One layer over one whole sequence x [T, D] (float32); returns
    ``(x, carry)``. ``carry`` None: x is the bare embedding, scaled
    here; every layer hands on a marker that this is done."""
    if kind != "mix+dense":
        raise ValueError(f"no layer of kind {kind!r} in this family")
    if not cfg.get("mamba_use_mlp", True):
        raise ValueError("this body has a feed-forward in every layer")
    for key in ("attention_bias", "mamba_proj_bias", "mlp_bias",
                "projectors_bias"):
        if cfg.get(key):
            raise ValueError(f"this body has no bias ({key})")
    if carry is None:
        x = x * float(cfg["embedding_multiplier"])
    eps = float(cfg["rms_norm_eps"])
    h = rms_norm(x, lp["input_layernorm"], eps)
    x = x + attention(h, lp, cfg, mm) + mixer(h, lp, cfg, mm)
    g = rms_norm(x, lp["pre_ff_layernorm"], eps)
    gate_m, down_m = (float(m) for m in cfg["mlp_multipliers"])
    act = jax.nn.silu(mm(g, lp["feed_forward.gate_proj"]) * gate_m) \
        * mm(g, lp["feed_forward.up_proj"])
    return x + mm(act, lp["feed_forward.down_proj"]) * down_m, \
        jnp.ones((), jnp.float32)


def logits(x, final_norm, lm_head, cfg: Dict[str, Any],
           mm: Callable = mm_f32):
    return mm(rms_norm(x, final_norm, float(cfg["rms_norm_eps"])),
              lm_head) * float(cfg["lm_head_multiplier"])


def forward(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
            mm: Callable = mm_f32, n_layers: Optional[int] = None):
    """Logits [T, V] of one whole sequence: the small-size entry the CPU
    tests use. ``params['layers']`` is a list of per-layer dicts as
    stored."""
    x, carry = embed(jnp.asarray(tokens), params["embed"]), None
    for lp in params["layers"][:n_layers]:
        x, carry = layer(x, lp, cfg, mm, "mix+dense", carry)
    return logits(x, params["final_norm"], params["lm_head"], cfg, mm)
