"""Pallas TPU kernel: ragged paged attention for mixed prefill+decode.

One kernel, one dispatch, for an arbitrary mix of prefill windows
(new_tokens > 1) and decode rows (new_tokens = 1). Each row of the ragged
batch is described by ``(q_start, length)`` — ``q_start`` cached-prefix
tokens already in the pool, ``length`` new tokens whose K/V the engine has
ALSO already written to the pool (write-then-attend) — plus the shared
page table. A decode row is just the degenerate ``length = 1``
continuation window, so the same grid serves both phases and the engine's
interleaved step needs a single program launch instead of one prefill
dispatch plus one decode dispatch (the Ragged Paged Attention framing:
chunked prefill and decode share one ragged kernel).

Layout and masking are the write-then-attend pool form of the prefill
kernel (ops/pallas/prefill_attention.py): every kv step streams one pool
page HBM→VMEM via the scalar-prefetched page table, folding it into a
flash-style online-softmax accumulator in VMEM scratch. Positions are
valid through ``q_start + length`` (the ragged tail reads through the
table); causality masks ``kv_pos > q_pos`` within each row's new-token
span; ``sliding_window`` clamps ``kv_pos > q_pos − W``. Rows whose pages
end early (decode rows in a batch bucketed for a long prefill window)
skip the dead kv steps' MXU work AND their DMA-fold via ``pl.when`` —
that per-row early-out is what makes the shared grid cheap for ragged
mixes. ``length = 0`` rows are fully masked (the denominator clamp keeps
the padded output finite; the engine never reads those rows).

Model deltas (same surface as the prefill kernel, so no model family
falls back): traced per-layer ``sliding_window`` scalars, Gemma
``logits_soft_cap`` and ``scale``, GPT-OSS ``sinks`` folded into the
denominator at finalize. The ``layer`` scalar routes page DMAs into the
FULL stacked [L, P, ps, Hkv, D] pools so no per-layer slice ever
materializes.
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

# Read ONCE at import (the PR-10 QBLOCK convention): this feeds a jit
# static, and an env read per call is hot-path overhead plus a recompile
# hazard if the variable changes mid-run (xlint recompile-hazard). 64 is
# the shape-safe default from the prefill kernel's offline v5e AOT
# envelope (q_block=128 blows the default scoped-VMEM budget at several
# serving shapes); override for on-chip A/Bs.
try:
    _QBLOCK_DEFAULT = int(os.environ.get("XLLM_RAGGED_QBLOCK", "64"))
except ValueError:
    _QBLOCK_DEFAULT = 64
# Window-disabled sentinel: plain int, not a jnp constant (module-level
# jax arrays are rejected as pallas closure constants).
_FULL = FULL_WINDOW


def _kernel(qstart_ref, lens_ref, pt_ref, win_ref, q_ref, kp_ref, vp_ref,
            sk_ref, o_ref, m_ref, l_ref, acc_ref, *, page_size: int,
            q_block: int, num_kv_steps: int, logits_soft_cap: float,
            scale: float, has_sinks: bool, layered: bool = False):
    b = pl.program_id(0)
    qi = pl.program_id(1)
    s = pl.program_id(2)

    # q arrives PRE-relaid as [Hkv, QB*G, D] (the caller does the 4D
    # transpose in XLA where it is free — in-kernel 4D transposes are a
    # Mosaic lowering hazard on v5e).
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

    # Every kv step is a pool step (write-then-attend): global position
    # of this block's first kv token.
    base = s * page_size
    # Query rows of this block sit at global positions q_start + qi*QB + t
    # (padded rows past ``length`` produce garbage the engine never
    # reads — sampling selects the last valid row downstream).
    q_lo = q_start + qi * q_block

    # A kv step is live while some (q, kv) pair survives all three masks:
    # the source bound (kv < q_start + length), causality (kv ≤ some q in
    # the block), and the window (block's last kv above the FIRST query
    # row's window floor). Decode rows (length = 1) keep only the steps
    # covering [max(0, q_start − W), q_start] — the rest skip.
    in_win = base + page_size - 1 > q_lo - w_eff
    live = (base < q_start + length) & (base <= q_lo + q_block - 1) & in_win

    @pl.when(live)
    def _fold():
        kp_blk = kp_ref[0, 0] if layered else kp_ref[0]
        vp_blk = vp_ref[0, 0] if layered else vp_ref[0]
        kb = kp_blk.astype(jnp.float32)                      # [ps, Hkv, D]
        vb = vp_blk.astype(jnp.float32)
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
        # Compare against the scalar THRESHOLD, not boolean vectors: i1
        # vector selects are unlegalizable for Mosaic (v5e AOT probe).
        src_ok = kv_pos < q_start + length
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
            # its probability mass is dropped — a final single-position
            # rescale of the accumulator.
            sk = sk_ref[:].astype(jnp.float32)               # [Hkv,QB*G,1]
            m_sk = jnp.maximum(m_fin, sk)
            corr = jnp.exp(m_fin - m_sk)
            l_fin = l_fin * corr + jnp.exp(sk - m_sk)
            acc_fin = acc_fin * corr
        # Clamp: a fully-masked row (length = 0 padding) has l == 0; its
        # output is garbage the engine never reads, but must stay finite.
        denom = jnp.maximum(l_fin, 1e-30)
        o_ref[0, 0] = (acc_fin / denom).astype(o_ref.dtype)


def _kernel_layered(qstart_ref, lens_ref, pt_ref, win_ref, lyr_ref,
                    *rest, **kw):
    """Layered-pool entry: the 5th scalar-prefetch ref (layer) is
    consumed by the BLOCK INDEX MAPS only."""
    return _kernel(qstart_ref, lens_ref, pt_ref, win_ref, *rest,
                   layered=True, **kw)


def ragged_paged_attention_pallas(q: jnp.ndarray, k_pages: jnp.ndarray,
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
                                  layer=None) -> jnp.ndarray:
    """q: [B, T, Hq, D] — the ragged batch's new tokens, row i holding
    ``lengths[i]`` real rows (prefill window or a single decode token)
    left-aligned in the T bucket, already roped; k/v_pages:
    [P, ps, Hkv, D] — or, with ``layer`` (traced int32 scalar), the FULL
    stacked [L, P, ps, Hkv, D] pools; page_table: [B, MP]; q_start: [B]
    cached prefix length (tokens already in the pool BEFORE this batch's
    new tokens — for a decode row, len(tokens) − 1); lengths: [B] true
    new-token count (1 for decode rows, 0 for padding rows). The new
    tokens' K/V must ALREADY be in the pool (write-then-attend) — there
    is no fresh-block stream and no T-page alignment requirement, so
    decode rows may start mid-page. ``sliding_window`` is a static int OR
    a traced int32 scalar; ``logits_soft_cap``/``scale`` static floats;
    ``sinks`` an optional [Hq] array. ``interpret=None`` → Pallas
    interpreter off TPU, Mosaic on TPU. Returns [B, T, Hq, D]."""
    if interpret is None:
        from xllm_service_tpu.ops import pallas
        interpret = pallas.default_interpret()
    if q_block is None:
        q_block = _QBLOCK_DEFAULT
    win = jnp.asarray(sliding_window, jnp.int32).reshape(1)
    if scale is None:
        scale = 1.0 / math.sqrt(q.shape[-1])
    return _impl(q, k_pages, v_pages, page_table, q_start, lengths, win,
                 sinks, layer, q_block=q_block,
                 logits_soft_cap=float(logits_soft_cap),
                 scale=float(scale), interpret=interpret)


@functools.partial(jax.jit, static_argnames=("q_block", "logits_soft_cap",
                                             "scale", "interpret"))
def _impl(q, k_pages, v_pages, page_table, q_start, lengths, win, sinks,
          layer=None, *, q_block: int, logits_soft_cap: float,
          scale: float, interpret: bool):
    B, T, Hq, D = q.shape
    layered = layer is not None
    if layered:
        _, _, page_size, Hkv, _ = k_pages.shape
    else:
        _, page_size, Hkv, _ = k_pages.shape
    MP = page_table.shape[1]
    # Largest block ≤ q_block that tiles T exactly (T is an engine bucket,
    # not necessarily a page multiple — decode-only mixes use T = 1).
    QB = math.gcd(T, min(q_block, T))
    nQ = T // QB
    G = Hq // Hkv
    has_sinks = sinks is not None

    # One set of index maps for both arities: the layered form appends
    # the layer prefetch ref, which only pool_idx consumes (*_ swallows
    # it elsewhere).
    def fixed_idx(b, qi, s, qstart, lens, pt, w, *_):
        return (0, 0, 0)

    def q_idx(b, qi, s, qstart, lens, pt, w, *_):
        return (b, qi, 0, 0, 0)

    if layered:
        def pool_idx(b, qi, s, qstart, lens, pt, w, l):
            return (l[0], pt[b, s], 0, 0, 0)

        pool_block = (1, 1, page_size, Hkv, D)
        n_prefetch = 5
    else:
        def pool_idx(b, qi, s, qstart, lens, pt, w):
            return (pt[b, s], 0, 0, 0)

        pool_block = (1, page_size, Hkv, D)
        n_prefetch = 4

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=n_prefetch,  # q_start, lens, pt, win[, layer]
        grid=(B, nQ, MP),
        in_specs=[
            pl.BlockSpec((1, 1, Hkv, QB * G, D), q_idx),
            pl.BlockSpec(pool_block, pool_idx),
            pl.BlockSpec(pool_block, pool_idx),
            pl.BlockSpec((Hkv, QB * G, 1), fixed_idx),
        ],
        out_specs=pl.BlockSpec((1, 1, Hkv, QB * G, D), q_idx),
        scratch_shapes=[
            pltpu.VMEM((Hkv, QB * G, 1), jnp.float32),   # running max
            pltpu.VMEM((Hkv, QB * G, 1), jnp.float32),   # running denom
            pltpu.VMEM((Hkv, QB * G, D), jnp.float32),   # accumulator
        ],
    )
    # q PRE-relaid to the kernel's [Hkv, QB*G, D] block layout (and the
    # output un-relaid below) in XLA, where the transposes fuse for free.
    q6 = q.reshape(B, nQ, QB, Hkv, G, D).transpose(0, 1, 3, 2, 4, 5) \
        .reshape(B, nQ, Hkv, QB * G, D)
    if has_sinks:
        sk3 = jnp.broadcast_to(
            sinks.astype(jnp.float32).reshape(Hkv, 1, G),
            (Hkv, QB, G)).reshape(Hkv, QB * G, 1)
    else:
        sk3 = jnp.zeros((Hkv, QB * G, 1), jnp.float32)
    body = _kernel_layered if layered else _kernel
    out = pl.pallas_call(
        functools.partial(body,
                          page_size=page_size, q_block=QB,
                          num_kv_steps=MP,
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
      q6, k_pages, v_pages, sk3)
    out = out.reshape(B, nQ, Hkv, QB, G, D).transpose(0, 1, 3, 2, 4, 5)
    return out.reshape(B, T, Hq, D)
