"""Pallas TPU kernel: single-token GQA attention over the paged KV cache.

Replaces the reference XLA path (ops/attention.py ``paged_decode_attention``)
which gathers every referenced page into a dense [B, S, Hkv, D] tensor
before attending — 2× the HBM traffic and a full materialization per layer
per decode step. Here each batch program streams its sequence's pages
HBM→VMEM via a **scalar-prefetched page table** (the BlockSpec index maps
read the table before the kernel body runs, so the pipeline DMAs exactly
the right pages), folding them into a flash-style online-softmax
accumulator in VMEM scratch.

Grid: (B, ceil(walk / K)), blocks fastest → the scratch accumulator
carries across the page walk of one batch row (standard TPU flash
pattern). A grid step folds a BLOCK of K pages of its row (PR 46): each
pool is passed K times with K one-page blocks, which Pallas double-buffers
page by page; operand j of step p reads column ``p * K + j`` of the folded
table. A block is a whole page with all KV heads ([ps, Hkv, D] — Pallas
TPU wants the trailing two block dims full or (8,128)-aligned, so heads
stay in the block and the GQA grouping happens in-kernel; or the same
page flat, "The flat page" below). K comes from
shapes (``ops/plan.py`` ``paged_fold_pages``: the largest power of two
whose double-buffered block of key and value pages stays under 4 MiB, no
more than the walk has columns; 4 / 2 / 8 / 8 in the benchmark's cells; 1,
today's page a step from the same body, where two page pairs do not fit).

The fold. ONE online-softmax update a block: the K pages' logits meet
lane by lane, one max, one exp pass, one rescale of the accumulator, then
the K ``prob x V`` products summed, so the pages' transposes and matmuls
overlap instead of waiting on K serial max/exp/rescale chains. Operands
go to the MXU in the pool's own type and are summed in float32 (the MXU
rounds a float32 operand to bfloat16 anyway: at K = 1 the output is the
float32-copy body's bit for bit, on the chip); statistics, accumulator
and output are float32, kept grouped ``[Hkv, G, .]`` as the products give
them (regrouping a group of 5 to ``[Hq, .]`` was a relayout a page).
Positions ≥ context_len, below the window's floor, or of a NULL page
(id 0) under it are masked by position; a block wholly out of range
skips its compute via ``pl.when``. On a v5e at the Mistral cell's shapes
the copies alone take 0.74 us a page however the grid is cut, the parent's
page-a-step body 0.98 and this one 0.79 (PERF.md section 6, PR 46: the
body, not the grid, set the pace); a block's padding columns are computed,
so a wider block is slower where it overshoots the walk.

The flat page (PR 51). A page whose head axis fills a fraction of a
packed tile (``ops/plan.py`` ``paged_flat_positions`` > 1: under 8
key-value heads over whole 128-lane rows; 4 positions a tile at 4 heads
of bfloat16, which is both the hybrid and the fifth cell; 1, the page by
heads as above, in the Mistral, looped and sixth cells) is read as ONE
matrix ``[ps * Hkv, D]``, row ``pos * Hkv + h``: a reshape of the pool
outside the ``pallas_call`` that XLA lowers to a bitcast (the pool's
``(4, 128)`` tiles of two rows a word hold a position's heads in 1 KB,
and the view's ``(8, 128)`` tiles pair the same rows), so a block is
whole tiles. ALL query heads meet it in one plain product,
``[Hq, D] x [ps * Hkv, D]^T``; a logit whose row's head ``r mod Hkv`` is
not the query's own is masked with the dead positions before the max, its
probability is an exact zero, and ``[Hq, ps * Hkv] x [ps * Hkv, D]`` sums
every live position once. No transpose; the same weight tiles a page as
the body by heads, with all the query rows streamed through each; Hkv
times the ``exp`` lanes; statistics ``[Hq, 1]``. By heads a ``[ps, 4, D]``
page arrived as 128 quarter-dense vregs and the transpose to heads first
paid a vreg a position: on a v5e 0.59 us a page folded against copies of
0.36, the flat body 0.39 (2,796 -> 1,864 us a call at the hybrid cell's
shapes, 252 -> 149 at the fifth's; the output is the body by heads' but
for one value in ten thousand, a bfloat16 unit off). NOT kept, from the
same sizing run (PERF.md section 6, PR 51): s positions seen as one
sublane group ``[ps / s, Hkv * s, D]``, transposed once, the products
batched over ``Hkv * s`` classes whose softmax states merge in
``_finalize`` (1,933 us at s = 2, 1,942 at s = 4: one dense transpose and
s times as many smaller products); the flat page with an update a page
(2,183) or every 2 or 4 pages (1,914 / 1,879).

The folded table (``_fold_table``), built ONCE outside the kernel: the
index maps do no arithmetic. ``walk`` is the table's width MP, except
under a STATIC sliding window W (``ops/plan.py``
``decode_walk_columns``): a window spans at most ceil(W / ps) + 1 pages,
so the grid covers that many columns and folded column c of row b is
table column ``first[b] + c``, where ``first`` is the page of the oldest
position the window keeps (added in the table, and handed to the body as
a prefetched scalar for its positions). A column past the row's context
or past the table names the page its operand read LAST, in this row or
the rows before, so Pallas issues no copy for it. The pages folded are
those of the full walk. A traced window (per-layer window vectors) cannot
shape a grid and walks all MP columns, as does full attention.

The V2–V5 experiment variants (transpose-free fold, whole-row manual-DMA
walk, multi-row cells, wide block-diagonal) were deleted when the ragged
kernel (ops/pallas/ragged_attention.py) subsumed the mixed-step decode
path — none of them beat this base kernel on hardware, and their flag
matrix fragmented the bench slots and xlint pins (docs/PERF_NOTES.md
keeps the post-mortems). The flat page is neither: the wide
block-diagonal form merged heads into LANES (``[ps, Hkv * D]``, a
relayout) under a grid of rows alone behind hand-issued copies; here heads
merge into ROWS, which is free under the pool's tiling, and the grid, the
pipeline and the block fold stay PR 46's.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas._compat import (
    CompilerParams as _CompilerParams)
from xllm_service_tpu.ops.plan import (
    decode_walk_columns, paged_flat_positions, paged_fold_pages)

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


def _kernel(ctx_ref, first_ref, pt_ref, win_ref, *refs, fold: int,
            page_size: int, num_kv_heads: int, has_current: bool,
            logits_soft_cap: float, scale: float, has_sinks: bool,
            layered: bool, flat: bool):
    """One grid step folds a BLOCK of ``fold`` pages of row ``b``: one
    online-softmax update over the block's logits, [Hkv, G, fold * ps]
    page by heads or, ``flat``, [Hq, fold * ps * Hkv] (module docstring,
    "The flat page")."""
    if layered:
        # the layer is consumed by the block index maps alone
        refs = refs[1:]
    q_ref = refs[0]
    k_refs, v_refs = refs[1:1 + fold], refs[1 + fold:1 + 2 * fold]
    kc_ref, vc_ref, sk_ref, o_ref, m_ref, l_ref, acc_ref = refs[1 + 2 * fold:]
    b = pl.program_id(0)
    p = pl.program_id(1)
    hq, d = q_ref.shape[1], q_ref.shape[2]
    g = hq // num_kv_heads

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[b]
    w = win_ref[0]
    w_eff = jnp.where(w > 0, w, _FULL)
    # The window keeps cache slot j > q_pos − W (slot j holds position j).
    win_floor = _query_pos(ctx, has_current) - w_eff
    # Folded column c of row b is table column first[b] + c (0 + c on a
    # full walk), UNclamped here: one past the table lies past the
    # context (ctx <= MP * ps), so the mask below drops it like any other.
    block_start = (first_ref[b] + p * fold) * page_size

    def soft_cap(lg):
        if logits_soft_cap > 0.0:
            return logits_soft_cap * jnp.tanh(lg / logits_soft_cap)
        return lg

    def update(logits, masks, prob_x_v):
        """ONE update a block: the pages' logits meet lane by lane, then
        one reduce across lanes for the max and one for the sum."""
        m_prev = m_ref[:]                       # [Hkv, G, 1] | [Hq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(
            functools.reduce(jnp.maximum, logits), axis=-1, keepdims=True))
        # (a block may hold no live position at all: a window of 1 under
        # an in-register token; exp(-1e30 - -1e30) is 1, so mask again)
        probs = [jnp.where(mk, jnp.exp(lg - m_new), 0.0)
                 for lg, mk in zip(logits, masks)]
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(
            functools.reduce(jnp.add, probs), axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + functools.reduce(jnp.add, [
            prob_x_v(pr, v_ref) for pr, v_ref in zip(probs, v_refs)])
        m_ref[:] = m_new

    def page(ref):
        """A block's page in the pool's own type. ``layered``: the pool
        rides FULL as [L, P, ...] and the block is [1, 1, ...] (no
        per-layer slice for XLA to materialize in front of this custom
        call)."""
        return ref[0, 0] if layered else ref[0]

    # Operands in the pool's own type, products summed in f32: the MXU
    # rounds an f32 operand to bf16 anyway (the same bits as from f32
    # copies, on the chip: PERF.md, PR 46). Statistics, accumulator and
    # output stay f32, in the shape the products give them.
    def _fold_by_heads():
        """A page [ps, Hkv, D] transposed to [Hkv, ps, D], the products
        batched over Hkv, everything grouped [Hkv, G, .]: no regrouping
        of heads in the fold."""
        def heads_first(ref):
            return jnp.transpose(page(ref), (1, 0, 2))

        qg = q_ref[0].astype(k_refs[0].dtype).reshape(num_kv_heads, g, d)
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, 1, page_size), 2)
        logits, masks = [], []
        for j, k_ref in enumerate(k_refs):
            # Batched over Hkv: [Hkv, G, D] x [Hkv, ps, D] -> [Hkv, G, ps]
            lg = soft_cap(jax.lax.dot_general(
                qg, heads_first(k_ref), (((2,), (2,)), ((0,), (0,))),
                preferred_element_type=jnp.float32) * scale)
            # A dead column holds a page read before (``_fold_table``):
            # masked by its position, like the NULL page under a window.
            pos = block_start + j * page_size + lane
            masks.append((pos < ctx) & (pos > win_floor))    # [1, 1, ps]
            logits.append(jnp.where(masks[-1], lg, _NEG_INF))
        # [Hkv, G, ps] x [Hkv, ps, D] -> [Hkv, G, D], summed over pages
        update(logits, masks, lambda pr, v_ref: jax.lax.dot_general(
            pr.astype(v_ref.dtype), heads_first(v_ref),
            (((2,), (1,)), ((0,), (0,))),
            preferred_element_type=jnp.float32))

    def _fold_flat():
        """A page as ONE matrix [ps * Hkv, D], row ``pos * Hkv + h``
        (whole packed tiles: no transpose, no quarter-dense vreg): ALL
        query heads against it in one plain product, and a logit whose
        row's head is not the query's own masked like a dead position, so
        its probability is an exact zero in ``prob x V``."""
        rows = page_size * num_kv_heads
        q = q_ref[0].astype(k_refs[0].dtype)                 # [Hq, D]
        col = jax.lax.broadcasted_iota(jnp.int32, (hq, rows), 1)
        head = jax.lax.broadcasted_iota(jnp.int32, (hq, rows), 0)
        first_q = (col % num_kv_heads) * g      # the row's head's queries
        own = (head >= first_q) & (head < first_q + g)       # [Hq, rows]
        in_page = jax.lax.broadcasted_iota(
            jnp.int32, (1, rows), 1) // num_kv_heads
        logits, masks = [], []
        for j, k_ref in enumerate(k_refs):
            # [Hq, D] x [ps * Hkv, D] -> [Hq, ps * Hkv]
            lg = soft_cap(jax.lax.dot_general(
                q, page(k_ref), (((1,), (1,)), ((), ())),
                preferred_element_type=jnp.float32) * scale)
            pos = block_start + j * page_size + in_page
            masks.append(own & (pos < ctx) & (pos > win_floor))
            logits.append(jnp.where(masks[-1], lg, _NEG_INF))
        # [Hq, ps * Hkv] x [ps * Hkv, D] -> [Hq, D], summed over pages
        update(logits, masks, lambda pr, v_ref: jax.lax.dot_general(
            pr.astype(v_ref.dtype), page(v_ref), (((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32))

    pl.when((block_start < ctx)
            & (block_start + fold * page_size - 1 > win_floor))(
                _fold_flat if flat else _fold_by_heads)

    @pl.when(p == pl.num_programs(1) - 1)
    def _finalize():
        m_fin = m_ref[:].reshape(hq, 1)
        l_fin = l_ref[:].reshape(hq, 1)
        acc_fin = acc_ref[:].reshape(hq, d)
        if has_current:
            # Fold the current token's K/V (held in-registers, not yet in
            # the pool) as a final always-valid single-position block
            # (soft-capped like any cache logit; inside its own window).
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
                                  layer=None,
                                  name: str = None) -> jnp.ndarray:
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
    exercises the kernel path in tests instead of crashing in Mosaic).
    ``name``: the call's name in the device trace, for a model that
    calls the kernel over two pools and has to tell the calls apart
    (None: ``_paged_decode_attention_impl``, as every other model's)."""
    if interpret is None:
        from xllm_service_tpu.ops import pallas
        interpret = pallas.default_interpret()
    win = jnp.asarray(sliding_window, jnp.int32).reshape(1)
    if scale is None:
        scale = 1.0 / (q.shape[-1] ** 0.5)
    return _impl_named(name)(
        q, k_pages, v_pages, page_table, context_lens, k_cur, v_cur, win,
        sinks, interpret=interpret,
        logits_soft_cap=float(logits_soft_cap), scale=float(scale),
        layer=layer,
        walk=decode_walk_columns(page_table.shape[1], k_pages.shape[-3],
                                 sliding_window))


@functools.lru_cache(maxsize=None)
def _impl_named(name):
    """``_paged_decode_attention_impl`` jitted under another name: the
    device trace names a kernel's operation after the jitted function
    that encloses it, and so do the harness's readers
    (chipbench/readers/op_share_of_program.py)."""
    if name is None:
        return _paged_decode_attention_impl
    inner = _paged_decode_attention_impl.__wrapped__

    @functools.wraps(inner)
    def call(*args, **kw):
        return inner(*args, **kw)
    call.__name__ = call.__qualname__ = name
    return jax.jit(call, static_argnames=_IMPL_STATICS)


def _fold_table(page_table: jnp.ndarray, live: jnp.ndarray,
                first: jnp.ndarray, walk: int, fold: int) -> jnp.ndarray:
    """The table as the grid reads it, ``[B, ceil(walk / fold) * fold]``:
    operand j of grid step p takes column ``p * fold + j``, which is
    table column ``first[b] + p * fold + j``. A dead column (at or past
    ``live[b]``, the row's pages that hold context, or past the table)
    names the page its operand read LAST, in this row or the rows before
    it: the same block index as the grid step before, for which Pallas
    issues no copy. The row's first live column is added HERE and the
    index maps do no arithmetic (in the latent kernel arithmetic inside
    them cost more than the copies it saved: PERF.md, PR 41)."""
    B, MP = page_table.shape
    steps = pl.cdiv(walk, fold)
    col = first[:, None] + jnp.arange(steps * fold, dtype=jnp.int32)[None]
    pages = jnp.take_along_axis(page_table, jnp.minimum(col, MP - 1),
                                axis=1).reshape(B * steps, fold)
    alive = ((col < live[:, None]) & (col < MP)).reshape(B * steps, fold)
    # operand j's grid steps in the order the grid runs them: a dead one
    # takes the last live one before it (step 0 where there is none: the
    # first grid step copies whatever it names)
    step = jnp.arange(B * steps, dtype=jnp.int32)[:, None]
    last = jax.lax.cummax(jnp.where(alive, step, 0), axis=0)
    return jnp.take_along_axis(pages, last, axis=0).reshape(
        B, steps * fold)


_IMPL_STATICS = ("interpret", "logits_soft_cap", "scale", "walk", "fold")


@functools.partial(jax.jit, static_argnames=_IMPL_STATICS)
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
                                 walk: int = None,
                                 fold: int = None) -> jnp.ndarray:
    """``walk``: grid columns a row (``decode_walk_columns`` of the
    caller's STATIC window; None or MP walks the whole table). Under a
    shorter walk ``win`` must hold that window. ``fold``: pages a grid
    step folds (None: ``ops/plan.py`` ``paged_fold_pages``, from
    shapes)."""
    B, Hq, D = q.shape
    layered = layer is not None
    page_size, Hkv = k_pages.shape[-3:-1]
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
    if fold is None:
        fold = paged_fold_pages(page_size, Hkv, D, k_pages.dtype.itemsize,
                                walk)
    # A page as the kernel's blocks see it: by heads, or flat where its
    # head axis fills a fraction of a tile (from shapes: ops/plan.py).
    # Flat names the same bytes in the same order under the pool's
    # tiling, so the reshape lowers to a bitcast
    # (tests/test_copy_census.py holds the cells' pools at no copy).
    flat = paged_flat_positions(Hkv, D, k_pages.dtype.itemsize) > 1
    page_shape = (page_size * Hkv, D) if flat else (page_size, Hkv, D)
    stat_shape = (Hq,) if flat else (Hkv, Hq // Hkv)
    if flat:
        k_pages = k_pages.reshape(*k_pages.shape[:-3], *page_shape)
        v_pages = v_pages.reshape(*v_pages.shape[:-3], *page_shape)
    ctx = context_lens.astype(jnp.int32)
    if walk < MP:
        # Table column of the oldest position the window keeps for the
        # row's query: max(0, (q_pos − W + 1) // ps).
        first = jnp.maximum(
            _query_pos(ctx, has_current) - win[0] + 1, 0) // page_size
    else:
        first = jnp.zeros_like(ctx)
    table = _fold_table(page_table, pl.cdiv(ctx, page_size), first, walk,
                        fold)

    def row(ix):
        return lambda b, p, *prefetched: ix(b)

    def page(j):
        """Operand j: ONE page a block, so that Pallas double-buffers
        page by page; straight out of the FULL [L, P, ps, Hkv, D] pool
        where it is layered (no per-layer slice exists for XLA to
        materialize: 134 MB x layers x 2 pools per decode step)."""
        return pl.BlockSpec(
            (1,) * (k_pages.ndim - len(page_shape)) + page_shape,
            lambda b, p, ctx, fst, pt, w, *lyr: (
                *(l[0] for l in lyr), pt[b, p * fold + j],
                *(0,) * len(page_shape)))

    pages = [page(j) for j in range(fold)]
    # ctx, first, folded table, win[, layer]
    prefetch = (ctx, first, table, win) + (
        (layer.reshape(1).astype(jnp.int32),) if layered else ())
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=len(prefetch),
        grid=(B, pl.cdiv(walk, fold)),
        in_specs=[
            pl.BlockSpec((1, Hq, D), row(lambda b: (b, 0, 0))),
            # each pool ``fold`` times: the block's pages of keys, then
            # of values
            *pages, *pages,
            pl.BlockSpec((1, Hkv, D), row(lambda b: (b, 0, 0))),
            pl.BlockSpec((1, Hkv, D), row(lambda b: (b, 0, 0))),
            pl.BlockSpec((Hq, 1), row(lambda b: (0, 0))),
        ],
        out_specs=pl.BlockSpec((1, Hq, D), row(lambda b: (b, 0, 0))),
        scratch_shapes=[
            pltpu.VMEM(stat_shape + (1,), jnp.float32),      # running max
            pltpu.VMEM(stat_shape + (1,), jnp.float32),      # running denom
            pltpu.VMEM(stat_shape + (D,), jnp.float32),      # accumulator
        ],
    )
    return pl.pallas_call(
        functools.partial(_kernel, fold=fold, page_size=page_size,
                          num_kv_heads=Hkv, has_current=has_current,
                          logits_soft_cap=logits_soft_cap, scale=scale,
                          has_sinks=has_sinks, layered=layered, flat=flat),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        grid_spec=grid_spec,
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(*prefetch, q, *([k_pages] * fold), *([v_pages] * fold), k_cur, v_cur,
      sk2)
