"""Plain reference of the family with sliding-window attention layers
that rotate between full attention layers that rotate nothing
(``model_type`` ``afmoe``: Arcee's Trinity), dense feed-forwards in the
leading layers and sigmoid-routed experts beside one shared expert after
them. Straightforward ``jax.numpy`` in float32 at precision ``highest``:
no kernel, cache, pool, page, trimming, batching, sorting or capacity,
and nothing imported from the program. The whole sequence at once, a
MASK for the window (scores are formed ``ROWS`` queries at a time, so
that 33k positions fit, against every key up to the block's last query
that the mask can keep: all of them on a full layer, the block and one
window before it on a sliding layer; each row's softmax is one softmax
over all it attends to).

With ``x`` the residual stream and ``norm`` an RMSNorm with a weight and
``rms_norm_eps``:

1. ``x = embed[token] * sqrt(hidden_size)`` (``mup_enabled``); no
   multiplier on the logits.
2. attention, every layer: ``a = input_layernorm(x)``; ``q = a W_q``
   (H heads of d), ``k = a W_k``, ``v = a W_v`` (Hkv heads), no bias; q
   and k through an RMSNorm a HEAD over its d channels (``q_norm``,
   ``k_norm``); a ``sliding_attention`` layer rotates q and k (the whole
   head, theta ``rope_theta``, first half against second half) and a
   ``full_attention`` layer rotates NOTHING; causal softmax at scale
   ``d ** -0.5``, and on a sliding layer position i attends to j iff ``0
   <= i - j < sliding_window``; ``o = (attn * sigmoid(a W_g)) W_o``.
3. four norms: ``x += post_attention_layernorm(o)``; ``m =
   pre_mlp_layernorm(x)``; ``x += post_mlp_layernorm(ffn(m))``.
4. ``ffn``, the first ``num_dense_layers`` layers: ``down(silu(gate(m))
   * up(m))``.
5. ``ffn``, the others: ``s = sigmoid(m W_r)`` over ALL the deployment's
   routed experts (``num_experts`` HELD here times
   ``expert_share_chips``); the ``num_experts_per_tok`` largest of ``s +
   expert_bias`` are chosen (the bias shapes the choice only); a chosen
   expert weighs ``s / (sum of the chosen s + 1e-20) * route_scale``.
   ONLY THE HELD experts (``expert_share_rank`` x held onward) are
   computed, each over the tokens that chose it: what a layer hands on
   is this chip's PART of the routed sum plus the shared expert (a
   SwiGLU of ``moe_intermediate_size`` on every token), as the
   program's. ``experts(..., held=None)`` gives the uncut layer (a test
   adds the eight shares up to it).
6. the final norm, then the untied head.

``mm`` is the matrix multiplication of every linear layer (the router's
too), swapped by the lower-precision control (``chipbench/check.py``).
Layers hand nothing on.
"""

from __future__ import annotations

from typing import Any, Callable, Dict, Optional

import jax
import jax.numpy as jnp

# What no family's reference does differently, from the bodies that have
# them: the matrix product, the norm and the SwiGLU (latent family), the
# experts' blocked sum (the delta-rule family).
from chipbench.reference.kda_gqa_moe import routed_part  # noqa: F401
from chipbench.reference.latent_moe import (  # noqa: F401
    HIGHEST, mm_f32, rms_norm, swiglu)

KINDS = {"sliding_attention": "swa", "full_attention": "attn"}
ROWS = 1024     # queries scored at a time (it divides both ``SEQ_PADS``)
PARTS = 3       # a full layer's blocks of queries, by how many keys they see


def layer_kinds(cfg: Dict[str, Any]):
    n_dense = int(cfg.get("num_dense_layers", 0))
    return [KINDS[t] + ("+dense" if i < n_dense else "+moe")
            for i, t in enumerate(cfg["layer_types"])
            ][:int(cfg["num_hidden_layers"])]


# A sequence's rows are filled up to ONE OF TWO lengths (or a multiple of
# the longer): 4,096 for what fits it (the CPU tests, a rehearsal), and
# 33,792 = 33 x ROWS for everything longer, which is every sequence of
# the cell, its 4k, 16k and 32k documents' alike (the longest is 33,276
# positions; 33,792 is the cell's ``max_model_len``). A layer at precision ``highest`` compiles in 10 to 20 s a
# SHAPE, a check of two or three requests of three lengths would compile
# each kind of layer three times, and the driver stops a run at 360 s:
# with one shape a check compiles each kind once (my chip runs, PR 57:
# the compiles, not the sums, were most of a check's 125 s).
SEQ_PADS = (4096, 33792)


def embed(tokens, embed_w, cfg: Optional[Dict[str, Any]] = None):
    """The tokens' rows times sqrt(hidden) (``mup_enabled``), then rows
    of zeros up to the sequence's padded length (``SEQ_PADS``): attention
    is causal, so rows after a sequence's end change nothing before it.
    ``check.py`` calls it without the configuration: the multiplier is
    the table's own width."""
    x = embed_w[tokens].astype(jnp.float32) \
        * jnp.sqrt(jnp.float32(embed_w.shape[1]))
    n = x.shape[0]
    pad = SEQ_PADS[0] if n <= SEQ_PADS[0] else -(-n // SEQ_PADS[1]) \
        * SEQ_PADS[1]
    return jnp.pad(x, ((0, pad - n), (0, 0)))


def rotate(x, positions, theta):
    """x [T, H, d]: channel i against channel i + d/2 by position *
    theta ** (-2i/d) (the Llama convention's pairing)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def attention(h, lp, cfg, mm, sliding: bool):
    if cfg.get("rope_scaling"):
        raise ValueError("this body rotates unscaled (rope_scaling null)")
    H, Hkv = int(cfg["num_attention_heads"]), int(cfg["num_key_value_heads"])
    d, eps = int(cfg["head_dim"]), float(cfg["rms_norm_eps"])
    t = h.shape[0]
    pos = jnp.arange(t)
    q = rms_norm(mm(h, lp["self_attn.q_proj"]).reshape(t, H, d),
                 lp["self_attn.q_norm"], eps)
    k = rms_norm(mm(h, lp["self_attn.k_proj"]).reshape(t, Hkv, d),
                 lp["self_attn.k_norm"], eps)
    v = mm(h, lp["self_attn.v_proj"]).reshape(t, Hkv, d)
    window = int(cfg["sliding_window"]) if sliding else t
    if sliding:
        theta = float(cfg["rope_theta"])
        q, k = rotate(q, pos, theta), rotate(k, pos, theta)
    scale = 1.0 / jnp.sqrt(jnp.float32(d))
    rows = min(ROWS, t)
    if t % rows:
        raise ValueError(f"{t} positions are not whole blocks of {rows}")

    # The keys a block of queries is scored against: those the mask can
    # keep and few others. On a sliding layer the block's own positions
    # and one window before them; on a full layer every key up to the
    # end of the block's PART of the sequence (``PARTS`` parts, so that
    # the early blocks do not form scores against keys that all lie
    # behind them: at 33,792 positions a score block is 138 MB and the
    # scores' traffic, not the products, is most of a layer's time).
    blocks = t // rows
    per = blocks // PARTS if blocks % PARTS == 0 else blocks

    def head(qh, kh, vh):                                        # one head
        def scored(i, lo, span):      # ``rows`` queries against ``span`` keys
            at = i * rows + jnp.arange(rows)
            key = lo + jnp.arange(span)
            ks = jax.lax.dynamic_slice_in_dim(kh, lo, span)
            vs = jax.lax.dynamic_slice_in_dim(vh, lo, span)
            s = jnp.matmul(qh[at], ks.T, precision=HIGHEST) * scale
            seen = (key[None, :] <= at[:, None]) \
                & (at[:, None] - key[None, :] < window)
            p = jax.nn.softmax(jnp.where(seen, s, -jnp.inf), axis=-1)
            return jnp.matmul(p, vs, precision=HIGHEST)          # [rows, d]

        if sliding:
            span = min(t, rows + window - 1)
            out = jax.lax.map(lambda i: scored(
                i, jnp.clip((i + 1) * rows - span, 0, t - span), span),
                jnp.arange(blocks))
        else:
            out = jnp.concatenate([jax.lax.map(
                lambda i, end=(part + 1) * per * rows: scored(i, 0, end),
                part * per + jnp.arange(per))
                for part in range(blocks // per)])
        return out.reshape(t, d)

    # query head i reads key-value head i // (H / Hkv)
    o = jax.lax.map(lambda a: head(*a), (
        jnp.swapaxes(q, 0, 1),
        jnp.repeat(jnp.swapaxes(k, 0, 1), H // Hkv, axis=0),
        jnp.repeat(jnp.swapaxes(v, 0, 1), H // Hkv, axis=0)))
    o = jnp.swapaxes(o, 0, 1).reshape(t, H * d)
    return mm(o * jax.nn.sigmoid(mm(h, lp["self_attn.gate_proj"])),
              lp["self_attn.o_proj"])


def share(cfg: Dict[str, Any]):
    """(first held expert, experts held, experts routed)."""
    held = int(cfg["num_experts"])
    chips = int(cfg.get("expert_share_chips", 1))
    return int(cfg.get("expert_share_rank", 0)) * held, held, held * chips


def gate_map(h, lp, cfg, mm):
    """[T, routed]: each token's weight on every expert of the
    deployment, zero off its choice."""
    for key in ("n_group", "topk_group"):
        if int(cfg.get(key) or 1) != 1:
            raise ValueError(f"this body's router has no groups ({key})")
    if cfg.get("score_func", "sigmoid") != "sigmoid":
        raise ValueError("this body's router scores by sigmoid")
    E, k = share(cfg)[2], int(cfg["num_experts_per_tok"])
    s = jax.nn.sigmoid(mm(h, lp["mlp.router.gate"]))
    choice = s + lp["mlp.expert_bias"].astype(jnp.float32)
    chosen = jax.lax.top_k(choice, k)[1]                         # [T, k]
    on = jnp.any(chosen[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    w = jnp.where(on, s, 0.0)
    if cfg.get("route_norm", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * float(cfg.get("route_scale", 1.0))


def shared_expert(h, lp, mm):
    return swiglu(h, lp["mlp.shared_experts.gate_proj"],
                  lp["mlp.shared_experts.up_proj"],
                  lp["mlp.shared_experts.down_proj"], mm)


def experts(h, lp, cfg, mm):
    """This chip's part of the routed sum (the held experts alone, under
    the gate over all the routed ones) plus the shared expert."""
    if int(cfg.get("num_shared_experts", 1)) != 1:
        raise ValueError("this body has one shared expert")
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
    if cfg.get("hidden_act", "silu") != "silu":
        raise ValueError("this body's feed-forwards gate by SiLU")
    op, ffn = kind.split("+")
    if op not in ("swa", "attn") or ffn not in ("dense", "moe"):
        raise ValueError(f"no layer of kind {kind!r} in this family")
    eps = float(cfg["rms_norm_eps"])
    a = rms_norm(x, lp["input_layernorm"], eps)
    o = attention(a, lp, cfg, mm, sliding=op == "swa")
    x = x + rms_norm(o, lp["post_attention_layernorm"], eps)
    m = rms_norm(x, lp["pre_mlp_layernorm"], eps)
    f = swiglu(m, lp["mlp.gate_proj"], lp["mlp.up_proj"],
               lp["mlp.down_proj"], mm) if ffn == "dense" \
        else experts(m, lp, cfg, mm)
    return x + rms_norm(f, lp["post_mlp_layernorm"], eps), None


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
