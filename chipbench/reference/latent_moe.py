"""Plain reference of the latent-attention, sparse-expert family
(``model_type`` ``deepseek_v3`` and its kin, ``joyai_llm_flash`` among
them). Straightforward ``jax.numpy`` in float32 at precision
``highest``: no kernel, cache, batching, sorting or capacity, and
nothing imported from the program.

Every layer: ``x += attention(norm(x)); x += ffn(norm(x))``, no biases.

- attention, latent, un-absorbed: ``q = q_b(norm(q_a(h)))`` per head,
  split into a ``nope`` and a ``rope`` part; ``[c, k_pe] = kv_a(h)``,
  ``c`` normed, ``k_pe`` ONE rotary key shared by all heads;
  ``[k_nope, v] = kv_b(c)`` per head. Scores ``(q_nope . k_nope + q_pe .
  k_pe) / sqrt(nope + rope)`` over the causal history. (The program
  never forms ``k_nope`` and ``v``: it absorbs ``kv_b`` into query and
  output and attends over the cached ``[c, k_pe]`` rows.) The rotation
  is over adjacent pairs (``rope_interleave``), unscaled
  (``rope_scaling: null`` is all this body knows). Heads go one after
  another, so that a 10k-token sequence's scores are one head's
  [T, T] at a time.
- ``dense`` layers: SwiGLU feed-forward.
- ``sparse`` layers: ``s = sigmoid(gate(h))``; experts are CHOSEN by ``s +
  e_score_correction_bias`` (among the ``topk_group`` best of ``n_group``
  groups, ranked by the sum of their two best, where there is more than
  one group), the ``num_experts_per_tok`` best; a chosen expert WEIGHS by
  its ``s`` over the chosen ones' sum (``norm_topk_prob``) times
  ``routed_scaling_factor``; the shared experts are added to every
  token. Experts go one after another, each over the tokens that chose
  it and no others (the rest have weight zero in that map), ``BLOCK`` of
  them at a time until it has done them all: no capacity, nothing
  dropped, nothing sorted.

``mm`` is the matrix multiplication of every linear layer (the router's
too), swapped by the lower-precision control (``chipbench/check.py``).
Layers hand nothing on: ``carry`` comes in and goes out as ``None``.
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


def rope_pairs(x, positions, theta):
    """x [T, ..., d]: pair (x[2i], x[2i+1]) turns by position * theta ** (-2i/d)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    ang = ang.reshape(ang.shape[:1] + (1,) * (x.ndim - 2) + ang.shape[1:])
    cos, sin = jnp.cos(ang), jnp.sin(ang)
    x1, x2 = x[..., 0::2], x[..., 1::2]
    return jnp.stack([x1 * cos - x2 * sin, x1 * sin + x2 * cos],
                     axis=-1).reshape(x.shape)


SEQ_PAD = 4096  # a sequence's rows are filled up to a multiple of this


def embed(tokens, embed_w):
    """x [T', D]: the tokens' rows, then rows of zeros up to a multiple of
    ``SEQ_PAD``. Attention is causal, so rows after a sequence's end
    change nothing before it, and whoever reads the result takes the
    rows of its own tokens. Sequences of 8k to 10.5k tokens are then ONE
    shape, and one compiled layer of each kind serves them all: a layer
    at precision ``highest`` compiles in 10 to 20 s."""
    x = embed_w[tokens].astype(jnp.float32)
    return jnp.pad(x, ((0, -x.shape[0] % SEQ_PAD), (0, 0)))


def attention(h, lp, cfg, mm):
    if cfg.get("rope_scaling"):
        raise ValueError("this body rotates unscaled (rope_scaling null)")
    H = int(cfg["num_attention_heads"])
    r, rope = int(cfg["kv_lora_rank"]), int(cfg["qk_rope_head_dim"])
    nope, vd = int(cfg["qk_nope_head_dim"]), int(cfg["v_head_dim"])
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    t = h.shape[0]
    pos = jnp.arange(t)
    q = mm(rms_norm(mm(h, lp["q_a_proj"]), lp["q_a_layernorm"], eps),
           lp["q_b_proj"]).reshape(t, H, nope + rope)
    q_nope, q_pe = q[..., :nope], rope_pairs(q[..., nope:], pos, theta)
    ckv = mm(h, lp["kv_a_proj_with_mqa"])
    c = rms_norm(ckv[:, :r], lp["kv_a_layernorm"], eps)
    k_pe = rope_pairs(ckv[:, r:], pos, theta)                    # [T, rope]
    kv = mm(c, lp["kv_b_proj"]).reshape(t, H, nope + vd)
    k_nope, v = kv[..., :nope], kv[..., nope:]
    causal = pos[None, :] <= pos[:, None]
    scale = 1.0 / jnp.sqrt(jnp.float32(nope + rope))

    def head(qn, qp, kn, vh):                                    # one head
        s = (jnp.matmul(qn, kn.T, precision=HIGHEST)
             + jnp.matmul(qp, k_pe.T, precision=HIGHEST)) * scale
        p = jax.nn.softmax(jnp.where(causal, s, -jnp.inf), axis=-1)
        return jnp.matmul(p, vh, precision=HIGHEST)              # [T, vd]

    o = jax.lax.map(lambda a: head(*a), tuple(
        jnp.swapaxes(a, 0, 1) for a in (q_nope, q_pe, k_nope, v)))
    return mm(jnp.swapaxes(o, 0, 1).reshape(t, H * vd), lp["o_proj"])


def gate_map(h, lp, cfg, mm):
    """[T, E]: each token's weight on every expert, zero off its choice."""
    E, k = int(cfg["n_routed_experts"]), int(cfg["num_experts_per_tok"])
    G = int(cfg.get("n_group") or 1)
    s = jax.nn.sigmoid(mm(h, lp["gate"]))
    choice = s + lp["e_score_correction_bias"].astype(jnp.float32)
    if G > 1:
        keep = int(cfg["topk_group"])
        groups = choice.reshape(-1, G, E // G)
        rank = jnp.sum(jax.lax.top_k(groups, 2)[0], axis=-1)     # [T, G]
        kept = jax.lax.top_k(rank, keep)[1]                      # [T, keep]
        in_kept = jnp.any(kept[:, :, None] == jnp.arange(G)[None, None, :],
                          axis=1)                                # [T, G]
        choice = jnp.where(jnp.repeat(in_kept, E // G, axis=-1), choice,
                           0.0)
    chosen = jax.lax.top_k(choice, k)[1]                         # [T, k]
    on = jnp.any(chosen[:, :, None] == jnp.arange(E)[None, None, :], axis=1)
    w = jnp.where(on, s, 0.0)
    if cfg.get("norm_topk_prob", True):
        w = w / (jnp.sum(w, axis=-1, keepdims=True) + 1e-20)
    return w * float(cfg.get("routed_scaling_factor", 1.0))


def swiglu(h, gate, up, down, mm):
    return mm(jax.nn.silu(mm(h, gate)) * mm(h, up), down)


BLOCK = 256     # tokens of one expert computed at a time


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
        lp["experts.gate_proj"], lp["experts.up_proj"],
        lp["experts.down_proj"], w.T))
    return routed + swiglu(h, lp["shared_experts.gate_proj"],
                           lp["shared_experts.up_proj"],
                           lp["shared_experts.down_proj"], mm)


def layer(x, lp: Dict[str, Any], cfg: Dict[str, Any], mm: Callable,
          kind: str, carry):
    """One layer of ``kind`` over one whole sequence x [T, D] (float32);
    returns ``(x, carry)``."""
    if carry is not None:
        raise ValueError("this family's layers hand nothing on")
    eps = float(cfg["rms_norm_eps"])
    x = x + attention(rms_norm(x, lp["input_layernorm"], eps), lp, cfg, mm)
    h = rms_norm(x, lp["post_attention_layernorm"], eps)
    if kind == "dense":
        return x + swiglu(h, lp["gate_proj"], lp["up_proj"],
                          lp["down_proj"], mm), None
    if kind == "sparse":
        return x + experts(h, lp, cfg, mm), None
    raise ValueError(f"no kind of layer {kind!r} in this family")


def logits(x, final_norm, lm_head, cfg: Dict[str, Any],
           mm: Callable = mm_f32):
    return mm(rms_norm(x, final_norm, float(cfg["rms_norm_eps"])), lm_head)


def forward(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
            mm: Callable = mm_f32, n_layers: Optional[int] = None):
    """Logits [T, V] of one whole sequence: the small-size entry the CPU
    tests use. ``params['layers']`` is a list of per-layer dicts as
    stored, the first ``first_k_dense_replace`` of them dense."""
    x = embed(jnp.asarray(tokens), params["embed"])
    for i, lp in enumerate(params["layers"][:n_layers]):
        kind = "dense" if i < int(cfg["first_k_dense_replace"]) else "sparse"
        x, _ = layer(x, lp, cfg, mm, kind, None)
    return logits(x[:len(tokens)], params["final_norm"], params["lm_head"],
                  cfg, mm)
