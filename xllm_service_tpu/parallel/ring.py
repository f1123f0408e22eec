"""Ring attention: exact causal attention over a sequence-sharded axis.

Long-context prefill shards the sequence over the ``sp`` mesh axis. Each
device keeps its Q block resident and streams every KV block past it around
a ring of ``ppermute``s, folding each block into a running flash-style
(online-softmax) accumulator — so peak memory per device is O(T/sp) and the
KV transfer overlaps the attention compute of the previous block (XLA
schedules the ppermute DMA concurrently with the einsums; on TPU the ring
maps onto neighbor ICI links).

The reference stack has nothing comparable anywhere (SURVEY.md §5.7 —
long-context is entirely engine-side and its engine is out-of-repo); this
is the net-new TPU path. Technique per Liu et al., "Ring Attention with
Blockwise Transformers" (PAPERS.md).

``ring_attention`` is the shard_map-ready core: call it inside
``shard_map(..., axis_names including axis_name)`` with Q/K/V already
sharded on their sequence axes. ``ring_attention_sharded`` wraps that for a
given mesh.
"""

from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

_NEG_INF = -1e30


def ring_attention(q: jnp.ndarray, k: jnp.ndarray, v: jnp.ndarray,
                   axis_name: str,
                   kv_lengths: Optional[jnp.ndarray] = None) -> jnp.ndarray:
    """Causal GQA attention with Q/K/V sharded along seq over ``axis_name``.

    q: [B, Tq, Hq, D] local block (global positions offset by
    ``axis_index * Tq``); k/v: [B, Tk, Hkv, D] local block. ``kv_lengths``
    [B] masks padding by *global* position. Returns the local output block
    [B, Tq, Hq, D].
    """
    n = jax.lax.psum(1, axis_name)
    my_idx = jax.lax.axis_index(axis_name)
    B, Tq, Hq, D = q.shape
    Tk, Hkv = k.shape[1], k.shape[2]
    G = Hq // Hkv
    qg = q.reshape(B, Tq, Hkv, G, D)
    scale = 1.0 / jnp.sqrt(jnp.asarray(D, jnp.float32))
    q_pos = my_idx * Tq + jnp.arange(Tq, dtype=jnp.int32)        # [Tq] global

    # Running flash accumulator, fp32.
    o0 = jnp.zeros((B, Tq, Hkv, G, D), jnp.float32)
    m0 = jnp.full((B, Tq, Hkv, G), _NEG_INF, jnp.float32)
    l0 = jnp.zeros((B, Tq, Hkv, G), jnp.float32)

    # Send to the next rank; after s steps we hold the block that originated
    # at rank (my_idx - s) mod n.
    perm = [(i, (i + 1) % n) for i in range(n)]

    def fold_block(o, m, l, kb, vb, s):
        """Fold KV block ``s`` hops upstream into the flash accumulator
        (numerics shared with the chunked prefill path —
        ops/attention.flash_fold)."""
        from xllm_service_tpu.ops.attention import flash_fold
        src = (my_idx - s) % n
        k_pos = src * Tk + jnp.arange(Tk, dtype=jnp.int32)       # [Tk] global
        mask = k_pos[None, :] <= q_pos[:, None]                  # [Tq, Tk]
        if kv_lengths is not None:
            mask = mask[None] & (k_pos[None, None, :]
                                 < kv_lengths[:, None, None])    # [B, Tq, Tk]
            mask = mask[:, :, None, None, :]
        else:
            mask = mask[None, :, None, None, :]
        return flash_fold(o, m, l, qg, kb, vb, mask, scale)

    # Local block first, then (n-1) permute-then-fold steps — the last
    # block is not rotated onward, saving one full KV ring hop per call.
    o, m, l = fold_block(o0, m0, l0, k, v, 0)

    def step(carry, s):
        o, m, l, kb, vb = carry
        kb = jax.lax.ppermute(kb, axis_name, perm)
        vb = jax.lax.ppermute(vb, axis_name, perm)
        o, m, l = fold_block(o, m, l, kb, vb, s)
        return (o, m, l, kb, vb), None

    if n > 1:
        (o, m, l, _, _), _ = jax.lax.scan(
            step, (o, m, l, k, v), jnp.arange(1, n))
    from xllm_service_tpu.ops.attention import flash_finalize
    out = flash_finalize(o, l)
    return out.reshape(B, Tq, Hq, D).astype(q.dtype)


def ring_attention_sharded(mesh: Mesh, axis_name: str = "sp",
                           head_axis: Optional[str] = None):
    """Build a jit-able ring attention partitioned over ``mesh``: Q/K/V
    [B, T, H, D] sharded on T over ``axis_name`` (and optionally on H over
    ``head_axis``, e.g. "tp" when both head counts divide it — the GQA
    grouping inside the block must stay aligned), lengths replicated."""
    qkv_spec = P(None, axis_name, head_axis, None)

    # The one sanctioned shard_map spelling site is ops/pallas/_compat.py
    # (enforced by tools/xlint mosaic-compat).
    from xllm_service_tpu.ops.pallas._compat import shard_map_unchecked
    smap = shard_map_unchecked()

    @functools.partial(
        smap, mesh=mesh,
        in_specs=(qkv_spec, qkv_spec, qkv_spec, P()),
        out_specs=qkv_spec)
    def _ring(q, k, v, kv_lengths):
        return ring_attention(q, k, v, axis_name, kv_lengths)

    return _ring
