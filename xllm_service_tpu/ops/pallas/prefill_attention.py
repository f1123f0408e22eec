"""Pallas TPU kernel: causal GQA prefill attention over the paged KV cache.

Replaces the XLA prefill path (ops/attention.py) which, per layer, gathers
every referenced page into a dense [B, S, Hkv, D] view and overlays the
window's fresh K/V before attending — a full cache materialization whose
HBM traffic grows with table width even for short windows. Here each
(batch, query-block) program walks the KV sources directly:

- the first ``MP`` steps of the kv axis stream the sequence's *pool pages*
  HBM→VMEM via a scalar-prefetched page table (exactly the decode kernel's
  pattern, ops/pallas/paged_attention.py) — these cover the cached prefix
  positions ``[0, q_start)``;
- the remaining ``T // ps`` steps stream the *fresh* K/V blocks of the
  current window (global positions ``[q_start, q_start + len)``), which at
  attention time are not yet written to the pool (the engine defers pool
  writes to one post-scan scatter, models/transformer.py).

Each step folds one ``ps``-wide KV block into a flash-style online-softmax
accumulator in VMEM scratch. The query block is re-laid out for the MXU
once per (b, q-block) — at kv step 0, into scratch as [Hkv, QB·G, D] — so
every fold uses the same batched-over-Hkv 3D dot shapes the decode kernel
uses, with no per-step relayout.

Both KV refs are DMA'd every step (Pallas loads every input block per grid
cell); the unused source indexes block 0 and its bytes are ignored. The
pipeline overlaps these DMAs with the previous step's compute.

Masking: pool positions are valid while ``pos < q_start[b]`` (the cached
prefix only — pool content past it is stale); fresh positions are valid
while their window-local index is ``< lengths[b]``; causality masks
``pos > q_pos``. Fully-masked steps skip their MXU work via ``pl.when``.

Model deltas beyond plain causal GQA (so SWA families are NOT bypassed to
the gather path — round-4 verdict item 3):

- ``sliding_window`` — a DYNAMIC int32 scalar (4th scalar-prefetch
  operand), so Gemma-2/3 / GPT-OSS per-layer window vectors can ride the
  layer scan as traced values (full-attention layers pass 0 or the
  larger-than-any-context sentinel). The mask keeps
  ``kv_pos > q_pos − W`` (HF semantics, ops/attention.py:200-202) and a
  kv step entirely below every query's window skips its MXU work AND its
  fold — with the engine's O(W) page trimming the dead steps are exactly
  the trimmed (NULL) pages, whose stale bytes the mask would discard
  anyway.
- ``logits_soft_cap`` — Gemma-2's ``cap·tanh(logits/cap)``, static.
- ``scale`` — Gemma's ``query_pre_attn_scalar**-0.5`` override, static.
- ``sinks`` — GPT-OSS per-head sink logits, folded into the softmax
  denominator at finalize (never capped, never scaled — matching
  ``mha_prefill``'s concat-column-then-drop reference semantics). The
  caller pre-broadcasts them to the kernel's [Hkv, QB·G, 1] block layout
  in XLA, where the relayout is free.
"""

from __future__ import annotations

import functools
import math
import os
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas._compat import (
    CompilerParams as _CompilerParams)

from xllm_service_tpu.ops.attention import FULL_WINDOW

_NEG_INF = -1e30

# Read ONCE at import: this feeds the jit static arg q_block, and an
# env read per call is both hot-path overhead and a recompile hazard if
# the variable ever changes mid-run (xlint recompile-hazard). 64 is the
# shape-safe default — the offline v5e AOT envelope
# (tools/aot_kernel_probes.py, round 5) showed q_block=128 blowing
# XLA's default scoped-VMEM budget at several serving shapes (incl.
# B=32/64 with T=128 — the bench prefill shape) while 64 compiles
# everywhere tested (T 128-2048, B 1-64). Override for on-chip A/Bs;
# 128 also works with --xla_tpu_scoped_vmem_limit_kib=32768.
try:
    _QBLOCK_DEFAULT = int(os.environ.get(
        "XLLM_PALLAS_PREFILL_QBLOCK", "64"))
except ValueError:
    _QBLOCK_DEFAULT = 64
# Larger than any context: a window of 0 (= disabled) is normalized to
# this so the mask arithmetic stays branch-free in-kernel. A plain int
# (not a jnp constant — module-level jax arrays would be captured as
# pallas closure constants, which pallas_call rejects); the shared
# definition documents the <= 2^30 int32-safety bound.
_FULL = FULL_WINDOW


def _kernel_layered(qstart_ref, lens_ref, pt_ref, win_ref, lyr_ref,
                    *rest, **kw):
    """Layered-pool entry: the 5th scalar-prefetch ref (layer) is
    consumed by the BLOCK INDEX MAPS only."""
    return _kernel(qstart_ref, lens_ref, pt_ref, win_ref, *rest,
                   layered=True, **kw)


def _kernel_pool(qstart_ref, lens_ref, pt_ref, win_ref, q_ref, kp_ref,
                 vp_ref, sk_ref, o_ref, m_ref, l_ref, acc_ref, **kw):
    """Pool-only entry (write-then-attend): no fresh-block operands —
    the window's K/V is already IN the pool, so every kv step streams
    pool pages and the ragged tail reads through the page table."""
    return _kernel(qstart_ref, lens_ref, pt_ref, win_ref, q_ref, kp_ref,
                   vp_ref, None, None, sk_ref, o_ref, m_ref, l_ref,
                   acc_ref, pool_only=True, **kw)


def _kernel_layered_pool(qstart_ref, lens_ref, pt_ref, win_ref, lyr_ref,
                         q_ref, kp_ref, vp_ref, sk_ref, o_ref, m_ref,
                         l_ref, acc_ref, **kw):
    """Layered pool-only entry (the write-then-attend serving form)."""
    return _kernel(qstart_ref, lens_ref, pt_ref, win_ref, q_ref, kp_ref,
                   vp_ref, None, None, sk_ref, o_ref, m_ref, l_ref,
                   acc_ref, layered=True, pool_only=True, **kw)


def _kernel(qstart_ref, lens_ref, pt_ref, win_ref, q_ref, kp_ref, vp_ref,
            kf_ref, vf_ref, sk_ref, o_ref, m_ref, l_ref, acc_ref, *,
            page_size: int, q_block: int, num_pool_steps: int,
            num_kv_steps: int, logits_soft_cap: float, scale: float,
            has_sinks: bool, layered: bool = False,
            pool_only: bool = False):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    s = pl.program_id(2)

    # q arrives PRE-relaid as [Hkv, QB*G, D] (the caller does the 4D
    # transpose in XLA where it is free): in-kernel 4D transposes are a
    # known Mosaic lowering hazard on v5e (the V3 decode kernel died on
    # exactly this class — docs/PERF_NOTES.md round 3).
    g = q_ref.shape[3] // q_block
    q_start = qstart_ref[b]
    length = lens_ref[b]
    w = win_ref[0]
    w_eff = jnp.where(w > 0, w, _FULL)

    @pl.when(s == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    # Global position of this block's first kv token.
    pool_base = s * page_size
    is_pool = (s < num_pool_steps) if not pool_only else True
    if pool_only:
        # Write-then-attend: the pool holds the window too, so every
        # step is a pool step and positions are valid through
        # q_start + length (the ragged tail reads through the table).
        base = pool_base
    else:
        fresh_local_base = (s - num_pool_steps) * page_size
        base = jnp.where(is_pool, pool_base, q_start + fresh_local_base)

    # Query rows of this block sit at global positions q_start + qi*QB + t
    # (padded rows past ``length`` produce garbage that the engine never
    # reads — the last valid row is selected downstream).
    q_lo = q_start + qi * q_block

    # A kv step is live while some (q, kv) pair satisfies causality AND
    # the window: needs kv ≤ q for some q in the block (base ≤ last query
    # row) and kv > q − W for some q (block's last kv position above the
    # FIRST query row's window floor). Pool steps additionally intersect
    # the cached prefix; fresh steps the true window.
    in_win = base + page_size - 1 > q_lo - w_eff
    if pool_only:
        live = (pool_base < q_start + length) \
            & (base <= q_lo + q_block - 1) & in_win
    else:
        live_pool = is_pool & (pool_base < q_start) & in_win
        live_fresh = jnp.logical_not(is_pool) & \
            (fresh_local_base < length) & (base <= q_lo + q_block - 1) \
            & in_win
        live = live_pool | live_fresh

    @pl.when(live)
    def _fold():
        kp_blk = kp_ref[0, 0] if layered else kp_ref[0]
        vp_blk = vp_ref[0, 0] if layered else vp_ref[0]
        if pool_only:
            kb = kp_blk.astype(jnp.float32)                  # [ps, Hkv, D]
            vb = vp_blk.astype(jnp.float32)
        else:
            kb = jnp.where(is_pool, kp_blk.astype(jnp.float32),
                           kf_ref[0, 0].astype(jnp.float32))
            vb = jnp.where(is_pool, vp_blk.astype(jnp.float32),
                           vf_ref[0, 0].astype(jnp.float32))
        qt = q_ref[0, 0].astype(jnp.float32)                 # [Hkv, QB*G, D]
        kt = jnp.transpose(kb, (1, 0, 2))                    # [Hkv, ps, D]
        vt = jnp.transpose(vb, (1, 0, 2))
        # [Hkv, QB*G, D] x [Hkv, ps, D] -> [Hkv, QB*G, ps]
        logits = jax.lax.dot_general(
            qt, kt, (((2,), (2,)), ((0,), (0,))),
            preferred_element_type=jnp.float32) * scale
        if logits_soft_cap > 0.0:
            logits = logits_soft_cap * jnp.tanh(logits / logits_soft_cap)

        # Positions: kv along ps, queries along QB (replicated over G).
        kv_pos = base + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, g, page_size), 2)
        q_pos = q_lo + jax.lax.broadcasted_iota(
            jnp.int32, (q_block, g, page_size), 0)
        # Pool: valid while pos < q_start. Fresh: valid while the local
        # index < length. Both: causal + inside the sliding window.
        # Select the scalar THRESHOLD, not the boolean vectors: a select
        # whose operands are i1 VECTORS is unlegalizable for Mosaic
        # ("failed to legalize arith.select" on vector<...xi1> — found
        # by the offline v5e AOT probe, tools/aot_kernel_probes.py).
        # Pool-only: the pool holds the window too, so the whole
        # [0, q_start + length) range is valid.
        if pool_only:
            src_limit = q_start + length
        else:
            src_limit = jnp.where(is_pool, q_start, q_start + length)
        src_ok = kv_pos < src_limit
        mask3 = (src_ok & (kv_pos <= q_pos)
                 & (kv_pos > q_pos - w_eff)).reshape(
            1, q_block * g, page_size)                       # [1, QB*G, ps]

        logits = jnp.where(mask3, logits, _NEG_INF)
        m_prev = m_ref[:]                                    # [Hkv, QB*G, 1]
        blk_max = jnp.max(logits, axis=-1, keepdims=True)
        m_new = jnp.maximum(m_prev, blk_max)
        prob = jnp.exp(logits - m_new)
        prob = jnp.where(mask3, prob, 0.0)
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(prob, axis=-1,
                                             keepdims=True)
        # [Hkv, QB*G, ps] x [Hkv, ps, D] -> [Hkv, QB*G, D]
        pv = jax.lax.dot_general(
            prob, vt, (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32)
        acc_ref[:] = acc_ref[:] * corr + pv
        m_ref[:] = m_new

    @pl.when(s == num_kv_steps - 1)
    def _finalize():
        m_fin = m_ref[:]
        l_fin = l_ref[:]
        acc_fin = acc_ref[:]
        if has_sinks:
            # GPT-OSS sinks: one per-head logit joins the denominator and
            # its probability mass is dropped — fold it as a final
            # single-position rescale of the accumulator.
            sk = sk_ref[:].astype(jnp.float32)               # [Hkv,QB*G,1]
            m_sk = jnp.maximum(m_fin, sk)
            corr = jnp.exp(m_fin - m_sk)
            l_fin = l_fin * corr + jnp.exp(sk - m_sk)
            acc_fin = acc_fin * corr
        denom = jnp.maximum(l_fin, 1e-30)
        # Written in the kernel's native [Hkv, QB*G, D] layout; the
        # caller transposes back in XLA (same hazard-avoidance as the
        # pre-relaid q input).
        o_ref[0, 0] = (acc_fin / denom).astype(o_ref.dtype)


def paged_prefill_attention_pallas(q: jnp.ndarray, k_fresh: jnp.ndarray,
                                   v_fresh: jnp.ndarray,
                                   k_pages: jnp.ndarray,
                                   v_pages: jnp.ndarray,
                                   page_table: jnp.ndarray,
                                   q_start: jnp.ndarray,
                                   lengths: jnp.ndarray,
                                   q_block: Optional[int] = None,
                                   interpret: bool = None,
                                   sliding_window=0,
                                   logits_soft_cap: float = 0.0,
                                   scale=None,
                                   sinks=None,
                                   layer=None,
                                   from_pool: bool = False) -> jnp.ndarray:
    """q/k_fresh/v_fresh: [B, T, H*, D] (this window, already roped);
    k/v_pages: [P, ps, Hkv, D] — or, with ``layer`` (a traced int32
    scalar), the FULL stacked [L, P, ps, Hkv, D] pools, whose page DMAs
    the kernel indexes at (layer, page) directly so no per-layer slice
    is ever materialized (the serving path always uses this form);
    page_table: [B, MP]; q_start: [B] cached
    prefix length; lengths: [B] true window length. Requires T % ps == 0
    (engine buckets are pow2 multiples of the page size — callers check).
    ``sliding_window`` is a static int OR a traced int32 scalar (per-layer
    window vectors riding the layer scan); 0 disables. ``logits_soft_cap``
    and ``scale`` are static floats (Gemma); ``sinks`` an optional [Hq]
    array (GPT-OSS). ``interpret=None`` → Pallas interpreter off TPU (so
    the gated serving path stays runnable in CPU tests), Mosaic on TPU.

    ``from_pool`` (static) — the write-then-attend form: the window's
    K/V was already written into the pool (ops/pallas/kv_update.py
    layered writers), so there is NO separate fresh-block stream —
    ``k_fresh``/``v_fresh`` are ignored (pass None), every kv step is a
    pool step, and positions are valid through q_start + length (the
    ragged window tail reads through the page table).
    Returns [B, T, Hq, D]."""
    if interpret is None:
        from xllm_service_tpu.ops import pallas
        interpret = pallas.default_interpret()
    if q_block is None:
        q_block = _QBLOCK_DEFAULT
    win = jnp.asarray(sliding_window, jnp.int32).reshape(1)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    if from_pool:
        k_fresh = v_fresh = None
    return _impl(q, k_fresh, v_fresh, k_pages, v_pages, page_table,
                 q_start, lengths, win, sinks, layer, q_block=q_block,
                 logits_soft_cap=float(logits_soft_cap),
                 scale=float(scale), interpret=interpret,
                 from_pool=from_pool)


@functools.partial(jax.jit, static_argnames=("q_block", "logits_soft_cap",
                                             "scale", "interpret",
                                             "from_pool"))
def _impl(q, k_fresh, v_fresh, k_pages, v_pages, page_table, q_start,
          lengths, win, sinks, layer=None, *, q_block: int,
          logits_soft_cap: float, scale: float, interpret: bool,
          from_pool: bool = False):
    B, T, Hq, D = q.shape
    layered = layer is not None
    if layered:
        _, _, page_size, Hkv, _ = k_pages.shape
    else:
        _, page_size, Hkv, _ = k_pages.shape
    MP = page_table.shape[1]
    if not from_pool and T % page_size != 0:
        raise ValueError(f"window {T} not a multiple of page {page_size}")
    # Largest block ≤ q_block that tiles T exactly — any window passing
    # the page-multiple check above gets a valid (if smaller) q block
    # rather than a trace-time crash on non-pow2 buckets.
    QB = math.gcd(T, min(q_block, T))
    nQ = T // QB
    nF = 0 if from_pool else T // page_size
    n_kv = MP + nF
    G = Hq // Hkv
    has_sinks = sinks is not None

    # ``layered``: the pools ride FULL as [L, P, ps, Hkv, D] and the
    # traced layer index (5th prefetch scalar) joins the page in the
    # block index — no per-layer pool slice for XLA to materialize
    # (the round-5 decode conviction applies to prefill identically).
    # One set of index maps for both arities: the layered form appends
    # the layer prefetch ref, which only pool_idx consumes (*_ swallows
    # it elsewhere — the decode kernel's adapter pattern).
    def fresh_idx(b, qi, s, qstart, lens, pt, w, *_):
        # Fresh steps DMA their T-block; pool steps block 0 (unused).
        return (b, jnp.maximum(s - MP, 0), 0, 0, 0)

    def fixed_idx(b, qi, s, qstart, lens, pt, w, *_):
        return (0, 0, 0)

    def q_idx(b, qi, s, qstart, lens, pt, w, *_):
        return (b, qi, 0, 0, 0)

    if layered:
        def pool_idx(b, qi, s, qstart, lens, pt, w, l):
            return (l[0],
                    jnp.where(s < MP, pt[b, jnp.minimum(s, MP - 1)], 0),
                    0, 0, 0)

        pool_block = (1, 1, page_size, Hkv, D)
        n_prefetch = 5
    else:
        def pool_idx(b, qi, s, qstart, lens, pt, w):
            # Pool steps DMA the mapped page; fresh steps page 0 (unused).
            return (jnp.where(s < MP, pt[b, jnp.minimum(s, MP - 1)], 0),
                    0, 0, 0)

        pool_block = (1, page_size, Hkv, D)
        n_prefetch = 4

    in_specs = [
        pl.BlockSpec((1, 1, Hkv, QB * G, D), q_idx),
        pl.BlockSpec(pool_block, pool_idx),
        pl.BlockSpec(pool_block, pool_idx),
    ]
    if not from_pool:
        in_specs += [
            pl.BlockSpec((1, 1, page_size, Hkv, D), fresh_idx),
            pl.BlockSpec((1, 1, page_size, Hkv, D), fresh_idx),
        ]
    in_specs.append(pl.BlockSpec((Hkv, QB * G, 1), fixed_idx))
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,  # q_start, lens, pt, win[, layer]
        grid=(B, nQ, n_kv),
        in_specs=in_specs,
        out_specs=pl.BlockSpec((1, 1, Hkv, QB * G, D), q_idx),
        scratch_shapes=[
            pltpu.VMEM((Hkv, QB * G, 1), jnp.float32),   # running max
            pltpu.VMEM((Hkv, QB * G, 1), jnp.float32),   # running denom
            pltpu.VMEM((Hkv, QB * G, D), jnp.float32),   # accumulator
        ],
    )
    # q is PRE-relaid to the kernel's [Hkv, QB*G, D] block layout (and
    # the output un-relaid below) in XLA, where these transposes are
    # fused and free — in-kernel 4D transposes are a Mosaic lowering
    # hazard on v5e (see the V3 decode kernel history).
    q6 = q.reshape(B, nQ, QB, Hkv, G, D).transpose(0, 1, 3, 2, 4, 5) \
        .reshape(B, nQ, Hkv, QB * G, D)
    if not from_pool:
        kf5 = k_fresh.reshape(B, nF, page_size, Hkv, D)
        vf5 = v_fresh.reshape(B, nF, page_size, Hkv, D)
    if has_sinks:
        # [Hq] → the kernel's [Hkv, QB*G, 1] block layout (replicated
        # over QB), pre-broadcast in XLA where the relayout is free.
        sk3 = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(Hkv, 1, G),
            (Hkv, QB, G)).reshape(Hkv, QB * G, 1)
    else:
        sk3 = jnp.zeros((Hkv, QB * G, 1), jnp.float32)
    if from_pool:
        body = _kernel_layered_pool if layered else _kernel_pool
    else:
        body = _kernel_layered if layered else _kernel
    out = pl.pallas_call(
        functools.partial(body,
                          page_size=page_size, q_block=QB,
                          num_pool_steps=MP, num_kv_steps=n_kv,
                          logits_soft_cap=logits_soft_cap, scale=scale,
                          has_sinks=has_sinks),
        out_shape=jax.ShapeDtypeStruct((B, nQ, Hkv, QB * G, D), q.dtype),
        grid_spec=grid_spec,
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary", "arbitrary")),
        interpret=interpret,
    )(q_start.astype(jnp.int32), lengths.astype(jnp.int32),
      page_table, win,
      *((layer.reshape(1).astype(jnp.int32),) if layered else ()),
      q6, k_pages, v_pages,
      *(() if from_pool else (kf5, vf5)), sk3)
    out = out.reshape(B, nQ, Hkv, QB, G, D).transpose(0, 1, 3, 2, 4, 5)
    return out.reshape(B, T, Hq, D)
