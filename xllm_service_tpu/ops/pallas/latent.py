"""Latent attention's decode kernels over the ONE latent pool.

A model under latent attention caches one row a token a layer (``D`` =
``kv_lora_rank + qk_rope_head_dim`` values: key and value both), in a
pool ``[L, P, ps, 1, D]``. These kernels take it reshaped to
``[L, P, ps, D]``: a page is then one ``[ps, D]`` block, tiled over
(ps, D), where the five-dimensional block ``[ps, 1, D]`` is tiled over
(1, D) and costs 2.2 times its bytes in HBM and in VMEM. The engine pins
the pool so that the reshape moves nothing
(``runtime/engine.py`` ``latent_pool_format``).

``latent_kv_update_layer`` writes a decode step's new rows in place
(the pool aliased to the output), ``latent_decode_attention`` then
attends from the pool, the new row included: the write-then-attend
layer body of ``models/transformer.py`` ``_mla_forward_decode``.

A grid step of ``latent_decode_attention`` folds a BLOCK of K pages of
one row (PR 41): the pool is passed K times with K one-page blocks, which
Pallas double-buffers, operand j of step p reading table column
``p * K + j``. No transpose and no f32 copy: ``q [Hq, D] x page [ps, D]``
gives a page's logits and ``prob [Hq, ps] x page`` its weighted rows (the
caller keeps their first ``kv_lora_rank`` columns), the operands in the
pool's own type, summed in f32; ONE online-softmax update a block, so the
K pages' matmuls overlap and hide under the block's DMA. K comes from
shapes (``ops/plan.py`` ``latent_fold_pages``: 8 at pages of 128 rows of
576 bfloat16 values). On the chip at the benchmark cell's shapes a grid
step costs 0.36 us that no copy hides, so one page a step ran at 0.59 us
a page against a DMA of 0.20; 8 pages a step run at 0.26 (PERF.md, PR 41).
"""

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas._compat import (
    CompilerParams as _CompilerParams)
from xllm_service_tpu.ops.plan import latent_fold_pages

_NEG_INF = -1e30
_DROP = -1


def _attend_kernel(ctx_ref, pt_ref, lyr_ref, q_ref, *refs, pages: int,
                   page_size: int, scale: float):
    """One grid step folds a BLOCK of ``pages`` pages of row ``b``: one
    online-softmax update over the block's [Hq, pages * ps] logits."""
    page_refs = refs[:pages]
    o_ref, m_ref, l_ref, acc_ref = refs[pages:]
    b = pl.program_id(0)
    p = pl.program_id(1)

    @pl.when(p == 0)
    def _init():
        m_ref[:] = jnp.full_like(m_ref, _NEG_INF)
        l_ref[:] = jnp.zeros_like(l_ref)
        acc_ref[:] = jnp.zeros_like(acc_ref)

    ctx = ctx_ref[b]
    block_start = p * pages * page_size

    @pl.when(block_start < ctx)
    def _fold():
        # Operands in the pool's own type, products summed in f32: the
        # MXU rounds an f32 operand to bf16 anyway.
        rows = [r[0, 0] for r in page_refs]                  # [ps, D] each
        q = q_ref[0].astype(rows[0].dtype)                   # [Hq, D]
        # A column past the context or past the table holds a page read
        # before (``_fold_table``): masked, weight 0.
        lane = jax.lax.broadcasted_iota(jnp.int32, (1, page_size), 1)
        logits = [jnp.where(
            block_start + j * page_size + lane < ctx,
            jax.lax.dot_general(q, r, (((1,), (1,)), ((), ())),
                                preferred_element_type=jnp.float32) * scale,
            _NEG_INF) for j, r in enumerate(rows)]           # [Hq, ps] each
        # ONE update a block: the pages' logits meet lane by lane, then
        # one reduce across lanes for the max and one for the sum.
        m_prev = m_ref[:]                                    # [Hq, 1]
        m_new = jnp.maximum(m_prev, jnp.max(
            functools.reduce(jnp.maximum, logits), axis=-1, keepdims=True))
        # the block holds a live position, so a masked one gives exp = 0
        probs = [jnp.exp(lg - m_new) for lg in logits]
        corr = jnp.exp(m_prev - m_new)
        l_ref[:] = l_ref[:] * corr + jnp.sum(
            functools.reduce(jnp.add, probs), axis=-1, keepdims=True)
        acc_ref[:] = acc_ref[:] * corr + functools.reduce(jnp.add, [
            jax.lax.dot_general(pr.astype(r.dtype), r,
                                (((1,), (0,)), ((), ())),
                                preferred_element_type=jnp.float32)
            for pr, r in zip(probs, rows)])                  # [Hq, D]
        m_ref[:] = m_new

    @pl.when(p == pl.num_programs(1) - 1)
    def _finalize():
        o_ref[0] = (acc_ref[:] / jnp.maximum(l_ref[:], 1e-30)
                    ).astype(o_ref.dtype)


def _fold_table(page_table: jnp.ndarray, context_lens: jnp.ndarray,
                page_size: int, pages: int) -> jnp.ndarray:
    """The table as the grid reads it: operand j of grid step p takes
    column ``p * pages + j``, so the width is padded to whole blocks, and
    a column past a row's context (``context_lens``: no more than the
    table holds) names the page its operand read LAST: the same block
    index as the step before, for which Pallas issues no copy. A dead
    column costs no DMA and the index maps no arithmetic (on the chip,
    PERF.md PR 41: 3.07 ms a step of the cell against 3.12 with dead
    columns fetched and 3.32 with this arithmetic inside the index
    maps)."""
    MP = page_table.shape[1]
    cols = jnp.arange(pl.cdiv(MP, pages) * pages, dtype=jnp.int32)[None]
    live = pl.cdiv(context_lens, page_size)[:, None]
    j, p = cols % pages, cols // pages
    last = jnp.maximum((live - 1 - j) // pages, 0)
    return jnp.take_along_axis(page_table,
                               jnp.minimum(p, last) * pages + j, axis=1)


@functools.partial(jax.jit, static_argnames=("scale", "interpret"))
def latent_decode_attention(q: jnp.ndarray, pool: jnp.ndarray,
                            page_table: jnp.ndarray,
                            context_lens: jnp.ndarray, layer: jnp.ndarray,
                            *, scale: float, interpret: bool = False
                            ) -> jnp.ndarray:
    """q [B, Hq, D] (the absorbed query); pool [L, P, ps, D]; page_table
    [B, MP]; context_lens [B]: cached rows to attend, the current one
    among them; layer: traced int32 scalar. Returns [B, Hq, D]: softmax
    weights over the rows times the rows. A row with no context gives
    zeros."""
    B, Hq, D = q.shape
    page_size = pool.shape[2]
    MP = page_table.shape[1]
    K = latent_fold_pages(page_size, D, pool.dtype.itemsize, MP)
    # no position lies past the table, whatever a row's length says
    ctx = jnp.minimum(context_lens.astype(jnp.int32), MP * page_size)

    def row(ix):
        return lambda b, p, ctx, pt, lyr: ix(b)

    def page(j):
        # straight out of the FULL pool: no per-layer slice exists for
        # XLA to materialize
        return pl.BlockSpec(
            (1, 1, page_size, D),
            lambda b, p, ctx, pt, lyr: (lyr[0], pt[b, p * K + j], 0, 0))

    return pl.pallas_call(
        functools.partial(_attend_kernel, pages=K, page_size=page_size,
                          scale=scale),
        out_shape=jax.ShapeDtypeStruct((B, Hq, D), q.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=3,              # ctx, page table, layer
            grid=(B, pl.cdiv(MP, K)),
            in_specs=[pl.BlockSpec((1, Hq, D), row(lambda b: (b, 0, 0))),
                      # the pool K times: Pallas double-buffers each page
                      *(page(j) for j in range(K))],
            out_specs=pl.BlockSpec((1, Hq, D), row(lambda b: (b, 0, 0))),
            scratch_shapes=[
                pltpu.VMEM((Hq, 1), jnp.float32),    # running max
                pltpu.VMEM((Hq, 1), jnp.float32),    # running denom
                pltpu.VMEM((Hq, D), jnp.float32),    # accumulator
            ]),
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
    )(ctx, _fold_table(page_table, ctx, page_size, K),
      jnp.asarray(layer, jnp.int32).reshape(1), q, *([pool] * K))


def _update_kernel(slot_ref, lyr_ref, new_ref, in_ref, out_ref, *,
                   page_size: int):
    """Read-modify-write of the 8-row tile that holds row ``b``'s slot
    (the select in f32: this toolchain's Mosaic lowers 32-bit vector
    selects only). A dropped row rewrites page 0's first tile with
    itself."""
    slot = slot_ref[pl.program_id(0)]
    off = (jnp.maximum(slot, 0) % page_size) % 8
    d = out_ref.shape[3]
    row_mask = (jax.lax.broadcasted_iota(jnp.int32, (1, 1, 8, d), 2)
                == off) & (slot >= 0)
    out_ref[...] = jnp.where(
        row_mask, new_ref[0][None, None].astype(jnp.float32),
        in_ref[...].astype(jnp.float32)).astype(out_ref.dtype)


@functools.partial(jax.jit, static_argnames=("interpret",))
def latent_kv_update_layer(pool: jnp.ndarray, new: jnp.ndarray,
                           page_table: jnp.ndarray, positions: jnp.ndarray,
                           active: jnp.ndarray, layer: jnp.ndarray, *,
                           interpret: bool = False) -> jnp.ndarray:
    """In-place write of one decode step's latent rows for ONE (traced)
    layer. pool [L, P, ps, D] (aliased to the output); new [B, D];
    semantics per row as ``kv_update.paged_kv_update_layer``: an
    inactive row, the NULL page and a position off the table write
    nothing."""
    L, P, ps, D = pool.shape
    B = new.shape[0]
    page_idx = positions // ps
    in_range = (page_idx < page_table.shape[1]) & active
    page = jnp.where(
        in_range,
        jnp.take_along_axis(
            page_table, jnp.minimum(page_idx, page_table.shape[1] - 1)
            [:, None], axis=1)[:, 0], 0)
    slot = jnp.where(in_range & (page > 0), page * ps + positions % ps,
                     _DROP).astype(jnp.int32)
    lyr = jnp.asarray(layer, jnp.int32).reshape(1)

    def tile(b, slot_ref, lyr_ref):
        s = jnp.maximum(slot_ref[b], 0)
        return (lyr_ref[0], s // ps, (s % ps) // 8, 0)

    pool_spec = pl.BlockSpec((1, 1, 8, D), tile)
    return pl.pallas_call(
        functools.partial(_update_kernel, page_size=ps),
        out_shape=jax.ShapeDtypeStruct(pool.shape, pool.dtype),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,                    # slot, layer
            grid=(B,),
            # the new rows ride [B, 1, D] so that a row's block has full
            # trailing dims
            in_specs=[pl.BlockSpec((1, 1, D),
                                   lambda b, slot_ref, lyr_ref: (b, 0, 0)),
                      pool_spec],
            out_specs=pool_spec),
        # flat operands: 0=slot 1=layer 2=new 3=pool -> output 0, in
        # place: the pool never moves while it rides the layer scan
        input_output_aliases={3: 0},
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(slot, lyr, new[:, None, :], pool)
