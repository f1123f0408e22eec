"""Plain reference of a dense grouped-query decoder (``MistralForCausalLM``
and its kin), shared by every configuration of that family.

The published architecture (pre-norm decoder, RMSNorm, rotary positions
in the half-split layout, grouped-query attention, SwiGLU feed-forward,
untied output head) in straightforward ``jax.numpy`` and float32, with
no kernel, cache or batching, and nothing imported from the program.
What differs between configurations is in their ``config.json`` alone:
with ``sliding_window: n`` a query at position p attends in every layer
to positions p - n + 1 .. p and to nothing older (the published
implementation's rolling window; older tokens reach p only through the
layers below); with ``sliding_window: null`` to its whole causal history.
Each configuration's ``reference.py``, beside its ``config.json``, binds
this module to it; a configuration of another family brings a body of
its own there.

``mm`` is the matrix multiplication every linear layer goes through:
float32 at ``highest`` precision for the reference, swapped by the
lower-precision control (``chipbench/check.py``).

Blocks: ``embed`` -> ``layer`` x L -> ``logits``. ``layer`` works on one
sequence [T, D] and walks its queries in blocks, so that a 16k-token
sequence fits beside nothing else on a 16 GB chip. Every layer is of the
one kind ``"layer"`` and hands nothing on to the next: ``carry`` comes
in and goes out as ``None``.
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


def rope(x, positions, theta):
    """x [T, H, Dh]; rotate_half layout (first half / second half)."""
    half = x.shape[-1] // 2
    inv = 1.0 / (theta ** (jnp.arange(half, dtype=jnp.float32) / half))
    ang = positions.astype(jnp.float32)[:, None] * inv[None, :]
    cos, sin = jnp.cos(ang)[:, None, :], jnp.sin(ang)[:, None, :]
    x1, x2 = x[..., :half], x[..., half:]
    return jnp.concatenate([x1 * cos - x2 * sin, x2 * cos + x1 * sin], -1)


def embed(tokens, embed_w):
    return embed_w[tokens].astype(jnp.float32)


def _attend(q, k, v, q0, hq, hkv, window):
    """Queries [Tq, Hq, Dh] at positions q0.. against all keys [T, Hkv, Dh]:
    causal, and within ``window`` positions where one is set."""
    tq, t = q.shape[0], k.shape[0]
    g = hq // hkv
    qg = q.reshape(tq, hkv, g, q.shape[-1])
    s = jnp.einsum("qhgd,khd->hgqk", qg, k, precision=HIGHEST)
    s = s / jnp.sqrt(jnp.float32(q.shape[-1]))
    qpos = q0 + jnp.arange(tq)[:, None]
    kpos = jnp.arange(t)[None, :]
    ok = kpos <= qpos
    if window:
        ok = ok & (kpos > qpos - window)
    s = jnp.where(ok[None, None], s, -jnp.inf)
    p = jax.nn.softmax(s, axis=-1)
    o = jnp.einsum("hgqk,khd->qhgd", p, v, precision=HIGHEST)
    return o.reshape(tq, hq * q.shape[-1])


def _blocks(fn, x, block):
    """``fn`` over row blocks of x [T, ...], one block alive at a time
    (T a multiple of ``block``, else the whole of x at once)."""
    t = x.shape[0]
    if t % block or t == block:
        return fn(jnp.int32(0), x)
    n = t // block
    out = jax.lax.map(lambda a: fn(a[0], a[1]),
                      (jnp.arange(n, dtype=jnp.int32) * block,
                       x.reshape(n, block, *x.shape[1:])))
    return out.reshape(t, *out.shape[2:])


def layer(x, lp: Dict[str, Any], cfg: Dict[str, Any], mm: Callable,
          kind: str, carry, q_block: int = 512, row_block: int = 2048):
    """One decoder layer over one whole sequence x [T, D] (float32);
    returns ``(x, carry)``. Traceable: queries and feed-forward rows go
    block by block."""
    if kind != "layer" or carry is not None:
        raise ValueError(f"a dense grouped-query decoder has one kind of "
                         f"layer and carries nothing: got {kind!r}")
    hq = int(cfg["num_attention_heads"])
    hkv = int(cfg.get("num_key_value_heads") or hq)
    dh = int(cfg.get("head_dim") or cfg["hidden_size"] // hq)
    eps, theta = float(cfg["rms_norm_eps"]), float(cfg["rope_theta"])
    window = int(cfg.get("sliding_window") or 0)
    t = x.shape[0]
    pos = jnp.arange(t)

    h = rms_norm(x, lp["input_norm"], eps)
    q = rope(mm(h, lp["q_proj"]).reshape(t, hq, dh), pos, theta)
    k = rope(mm(h, lp["k_proj"]).reshape(t, hkv, dh), pos, theta)
    v = mm(h, lp["v_proj"]).reshape(t, hkv, dh)
    att = _blocks(lambda q0, qb: _attend(qb, k, v, q0, hq, hkv, window),
                  q, q_block)
    x = x + mm(att, lp["o_proj"])

    def ffn(_, xb):
        h = rms_norm(xb, lp["post_norm"], eps)
        act = jax.nn.silu(mm(h, lp["gate_proj"])) * mm(h, lp["up_proj"])
        return xb + mm(act, lp["down_proj"])

    return _blocks(ffn, x, row_block), None


def logits(x, final_norm, lm_head, cfg: Dict[str, Any],
           mm: Callable = mm_f32):
    return mm(rms_norm(x, final_norm, float(cfg["rms_norm_eps"])), lm_head)


def forward(params: Dict[str, Any], tokens, cfg: Dict[str, Any],
            mm: Callable = mm_f32, n_layers: Optional[int] = None):
    """Logits [T, V] of one whole sequence: the small-size entry the CPU
    tests use. ``params['layers']`` is a list of per-layer dicts."""
    x = embed(jnp.asarray(tokens), params["embed"])
    for lp in params["layers"][:n_layers]:
        x, _ = layer(x, lp, cfg, mm, "layer", None)
    return logits(x, params["final_norm"], params["lm_head"], cfg, mm)
