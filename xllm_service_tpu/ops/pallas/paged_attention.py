"""Pallas TPU kernel: single-token GQA attention over the paged KV cache.

Replaces the reference XLA path (ops/attention.py ``paged_decode_attention``)
which gathers every referenced page into a dense [B, S, Hkv, D] tensor
before attending — 2× the HBM traffic and a full materialization per layer
per decode step. Here each batch program streams its sequence's pages
HBM→VMEM via a **scalar-prefetched page table** (the BlockSpec index map
reads ``page_table[b, p]`` before the kernel body runs, so the pipeline
DMAs exactly the right page), folding each page into a flash-style
online-softmax accumulator in VMEM scratch.

Grid: (B, walk), pages fastest → the scratch accumulator carries
across the page walk of one batch row (standard TPU flash pattern). Each
block is a whole page with all KV heads ([ps, Hkv, D] — Pallas TPU wants
the trailing two block dims full or (8,128)-aligned, so heads stay in the
block and the GQA grouping happens in-kernel). NULL pages (id 0) and
positions ≥ context_len are masked; a page wholly out of range skips its
compute via ``pl.when`` but still pays its grid step (0.12 us on a v5e
against 0.96 us for a page folded: PERF.md section 6, PR 34).

The walk. ``walk`` is the table's width MP, except under a STATIC
sliding window W (``ops/plan.py`` ``decode_walk_columns``): a window
spans at most ceil(W / ps) + 1 pages, so the grid has that many columns
and column p of row b is table column ``first[b] + p``, where ``first``
is the page of the oldest position the window keeps, computed in the
index maps and the body from the prefetched scalars. The pages folded,
and their order, are those of the full walk: the result is the same
arithmetic. A traced window (per-layer window vectors) cannot shape a
grid and walks all MP columns, as does full attention.

The V2–V5 experiment variants (transpose-free fold, whole-row manual-DMA
walk, multi-row cells, wide block-diagonal) were deleted when the ragged
kernel (ops/pallas/ragged_attention.py) subsumed the mixed-step decode
path — none of them beat this base kernel on hardware, and their flag
matrix fragmented the bench slots and xlint pins (docs/PERF_NOTES.md
keeps the post-mortems).
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas._compat import (
    CompilerParams as _CompilerParams)
from xllm_service_tpu.ops.plan import decode_walk_columns

_NEG_INF = -1e30

# Window sentinel: larger than any context. A plain int — module-level
# jnp constants would be captured as pallas closure constants, which
# pallas_call rejects; the shared definition documents the <= 2^30
# int32-safety bound.
from xllm_service_tpu.ops.attention import FULL_WINDOW as _FULL


def _query_pos(ctx, has_current: bool):
    """The query's logical position: with the current token held
    in-registers the cache holds [0, ctx) and the query sits at ctx;
    without it, ctx INcludes the query token (position ctx − 1)."""
    return ctx if has_current else ctx - 1


def _first_column(q_pos, w, page_size: int):
    """Table column of the oldest position a window of ``w`` keeps for a
    query at ``q_pos``: max(0, (q_pos − w + 1) // ps). Scalar int32
    arithmetic on prefetched values: the block index maps and the body
    share it."""
    return jax.lax.div(jnp.maximum(q_pos - w + 1, 0), page_size)


def _kernel(ctx_ref, pt_ref, win_ref, q_ref, k_ref, v_ref, kc_ref, vc_ref,
            sk_ref, o_ref, m_ref, l_ref, acc_ref, *, page_size: int,
            pages_per_seq: int, walk: int, num_kv_heads: int,
            has_current: bool, logits_soft_cap: float, scale: float,
            has_sinks: bool, layered: bool = False):
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[b]
    page_start = p * page_size
    w = win_ref[0]
    w_eff = jnp.where(w > 0, w, _FULL)
    # The window keeps cache slot j > q_pos − W (slot j holds position j).
    q_pos = _query_pos(ctx, has_current)
    win_floor = q_pos - w_eff
    if walk < pages_per_seq:
        # Grid column p is table column first + p, UNclamped here: one
        # past the table lies past the context (ctx <= MP * ps), so the
        # fold below skips it like any other.
        page_start += _first_column(q_pos, w, page_size) * page_size

    @pl.when((page_start < ctx) & (page_start + page_size - 1 > win_floor))
    def _fold():
        hq, d = q_ref.shape[1], q_ref.shape[2]
        g = hq // num_kv_heads
        q = q_ref[0].astype(jnp.float32)                     # [Hq, D]
        qg = q.reshape(num_kv_heads, g, d)                   # [Hkv, G, D]
        # ``layered``: the pool rides FULL as [L, P, ps, Hkv, D] and the
        # block is [1, 1, ps, Hkv, D] (the round-5 fix for the per-layer
        # 134 MB slice materialization feeding this custom call).
        k = (k_ref[0, 0] if layered else k_ref[0]).astype(jnp.float32)
        v = (v_ref[0, 0] if layered else v_ref[0]).astype(jnp.float32)
        kt = jnp.transpose(k, (1, 0, 2))                     # [Hkv, ps, D]
        # Batched over Hkv: [Hkv, G, D] x [Hkv, ps, D] -> [Hkv, G, ps]
        logits = jax.lax.dot_general(
            qg, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        logits = logits.reshape(hq, page_size)               # [Hq, ps]
        if logits_soft_cap > 0.0:
            logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)
        pos = page_start + jax.lax.broadcasted_iota(
            jnp.int32, (1, page_size), 1)
        mask = (pos < ctx) & (pos > win_floor)               # [1, ps]
        logits = jnp.where(mask, logits, _NEG_INF)
        m_prev = m_ref[:]                                    # [Hq, 1]
        blk_max = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, blk_max)
        prob = jnp.exp(logits - m_new)
        prob = jnp.where(mask, prob, 0.0)                    # [Hq, ps]
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(prob, axis=-1,
                                             keepdims=True)
        vt = jnp.transpose(v, (1, 0, 2))
        # [Hkv, G, ps] x [Hkv, ps, D] -> [Hkv, G, D]
        pv = jax.lax.dot_general(
            prob.reshape(num_kv_heads, g, page_size), vt,
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * corr + pv.reshape(hq, d)
        m_ref[:] = m_new

    @pl.when(p == walk - 1)
    def _finalize():
        m_fin = m_ref[:]
        l_fin = l_ref[:]
        acc_fin = acc_ref[:]
        if has_current:
            # Fold the current token's K/V (held in-registers, not yet in
            # the pool) as a final always-valid single-position block
            # (soft-capped like any cache logit; inside its own window).
            hq, d = q_ref.shape[1], q_ref.shape[2]
            g = hq // num_kv_heads
            q = q_ref[0].astype(jnp.float32)
            qg = q.reshape(num_kv_heads, g, d)
            kc = kc_ref[0].astype(jnp.float32)               # [Hkv, D]
            vc = vc_ref[0].astype(jnp.float32)
            lc = jnp.sum(qg * kc[:, None, :], axis=-1) * scale  # [Hkv, G]
            lc = lc.reshape(hq, 1)
            if logits_soft_cap > 0.0:
                lc = logits_soft_cap * jnp.tanh(lc / logits_soft_cap)
            m_new = jnp.maximum(m_fin, lc)
            corr = jnp.exp(m_fin - m_new)
            pc = jnp.exp(lc - m_new)                         # [Hq, 1]
            l_fin = l_fin * corr + pc
            vc_full = jnp.broadcast_to(
                vc[:, None, :], (num_kv_heads, g, d)).reshape(hq, d)
            acc_fin = acc_fin * corr + pc * vc_full
            m_fin = m_new
        if has_sinks:
            # GPT-OSS sinks: the per-head logit joins the denominator
            # only (never capped, never scaled — reference semantics,
            # ops/attention.py paged_decode_attention_current).
            sk = sk_ref[:].astype(jnp.float32)               # [Hq, 1]
            m_sk = jnp.maximum(m_fin, sk)
            corr = jnp.exp(m_fin - m_sk)
            l_fin = l_fin * corr + jnp.exp(sk - m_sk)
            acc_fin = acc_fin * corr
        denom = jnp.maximum(l_fin, 1e-30)
        o_ref[0] = (acc_fin / denom).astype(o_ref.dtype)


def paged_decode_attention_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
                                  v_pages: jnp.ndarray,
                                  page_table: jnp.ndarray,
                                  context_lens: jnp.ndarray,
                                  k_cur: jnp.ndarray = None,
                                  v_cur: jnp.ndarray = None,
                                  interpret: bool = None,
                                  sliding_window=0,
                                  logits_soft_cap: float = 0.0,
                                  scale=None,
                                  sinks=None,
                                  layer=None) -> jnp.ndarray:
    """q: [B, Hq, D]; k/v_pages: [P, ps, Hkv, D]; page_table: [B, MP];
    context_lens: [B] valid cache tokens. With ``k_cur``/``v_cur``
    [B, Hkv, D], the current (not-yet-written) token is folded as a final
    block — the contract of ``paged_decode_attention_current``. Returns
    [B, Hq, D].

    ``sliding_window`` is a static int OR a traced int32 scalar (per-layer
    window vectors riding the layer scan — Gemma-2/3, GPT-OSS); 0
    disables. A static one narrower than the table also shortens each
    row's page walk to the window's span (module docstring).
    ``logits_soft_cap``/``scale`` static floats (Gemma); ``sinks`` an
    optional [Hq] array (GPT-OSS).

    ``interpret=None`` → Pallas interpreter off TPU (XLLM_PALLAS=1 on CPU
    exercises the kernel path in tests instead of crashing in Mosaic)."""
    if interpret is None:
        from xllm_service_tpu.ops import pallas
        interpret = pallas.default_interpret()
    win = jnp.asarray(sliding_window, jnp.int32).reshape(1)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _paged_decode_attention_impl(
        q, k_pages, v_pages, page_table, context_lens, k_cur, v_cur, win,
        sinks, interpret=interpret,
        logits_soft_cap=float(logits_soft_cap), scale=float(scale),
        layer=layer,
        walk=decode_walk_columns(page_table.shape[1], k_pages.shape[-3],
                                 sliding_window))


def _kernel_layered(ctx_ref, pt_ref, win_ref, lyr_ref, *rest, **kw):
    """Layered-pool entry: the 4th scalar-prefetch ref (layer) is
    consumed by the BLOCK INDEX MAPS only — the body never reads it."""
    return _kernel(ctx_ref, pt_ref, win_ref, *rest, **kw)


@functools.partial(jax.jit,
                   static_argnames=("interpret", "logits_soft_cap",
                                    "scale", "walk"))
def _paged_decode_attention_impl(q: jnp.ndarray, k_pages: jnp.ndarray,
                                 v_pages: jnp.ndarray,
                                 page_table: jnp.ndarray,
                                 context_lens: jnp.ndarray,
                                 k_cur: jnp.ndarray = None,
                                 v_cur: jnp.ndarray = None,
                                 win: jnp.ndarray = None,
                                 sinks: jnp.ndarray = None,
                                 interpret: bool = False,
                                 logits_soft_cap: float = 0.0,
                                 scale: float = None,
                                 layer: jnp.ndarray = None,
                                 walk: int = None) -> jnp.ndarray:
    """``walk``: grid columns a row (``decode_walk_columns`` of the
    caller's STATIC window; None or MP walks the whole table). Under a
    shorter walk ``win`` must hold that window."""
    B, Hq, D = q.shape
    layered = layer is not None
    if layered:
        _, _, page_size, Hkv, _ = k_pages.shape
    else:
        _, page_size, Hkv, _ = k_pages.shape
    MP = page_table.shape[1]
    has_current = k_cur is not None
    if not has_current:
        k_cur = jnp.zeros((B, Hkv, D), q.dtype)
        v_cur = jnp.zeros((B, Hkv, D), q.dtype)
    if win is None:
        win = jnp.zeros((1,), jnp.int32)
    if scale is None:
        scale = 1.0 / (D ** 0.5)
    has_sinks = sinks is not None
    sk2 = (sinks.astype(jnp.float32).reshape(Hq, 1) if has_sinks
           else jnp.zeros((Hq, 1), jnp.float32))
    if walk is None:
        walk = MP

    def column(b, p, ctx, w):
        """Table column of grid column p in row b. Clamped to the table:
        the body skips what the clamp repeats."""
        if walk < MP:
            first = _first_column(_query_pos(ctx[b], has_current), w[0],
                                  page_size)
            return jnp.minimum(first + p, MP - 1)
        return p

    if layered:
        # Pool blocks index (layer, page) straight out of the FULL
        # [L, P, ps, Hkv, D] pool — no per-layer slice exists for XLA
        # to materialize (134 MB x layers x 2 pools per decode step).
        lyr = layer.reshape(1).astype(jnp.int32)
        pool_spec = pl.BlockSpec(
            (1, 1, page_size, Hkv, D),
            lambda b, p, ctx, pt, w, l: (
                l[0], pt[b, column(b, p, ctx, w)], 0, 0, 0))
        n_prefetch = 4
        def small(ix):
            return lambda b, p, ctx, pt, w, l: ix(b)
    else:
        pool_spec = pl.BlockSpec(
            (1, page_size, Hkv, D),
            lambda b, p, ctx, pt, w: (
                pt[b, column(b, p, ctx, w)], 0, 0, 0))
        n_prefetch = 3
        def small(ix):
            return lambda b, p, ctx, pt, w: ix(b)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,  # ctx, page_table, win[, layer]
        grid=(B, walk),
        in_specs=[
            pl.BlockSpec((1, Hq, D), small(lambda b: (b, 0, 0))),
            pool_spec,
            pool_spec,
            pl.BlockSpec((1, Hkv, D), small(lambda b: (b, 0, 0))),
            pl.BlockSpec((1, Hkv, D), small(lambda b: (b, 0, 0))),
            pl.BlockSpec((Hq, 1), small(lambda b: (0, 0))),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), small(lambda b: (b, 0, 0))),
        scratch_shapes=[
            pltpu.VMEM((Hq, 1), jnp.float32),    # running max
            pltpu.VMEM((Hq, 1), jnp.float32),    # running denom
            pltpu.VMEM((Hq, D), jnp.float32),    # output accumulator
        ],
    )
    prefetch = (context_lens, page_table, win) + (
        (lyr,) if layered else ())
    out = pl.pallas_call(
        functools.partial(_kernel_layered if layered else _kernel,
                          page_size=page_size, pages_per_seq=MP,
                          walk=walk, num_kv_heads=Hkv,
                          has_current=has_current,
                          logits_soft_cap=logits_soft_cap, scale=scale,
                          has_sinks=has_sinks, layered=layered),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        grid_spec=grid_spec,
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*prefetch, q, k_pages, v_pages, k_cur, v_cur, sk2)
    return out
