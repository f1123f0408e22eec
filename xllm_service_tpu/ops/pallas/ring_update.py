"""Pallas TPU kernel: a state layer's filter ring stepped in place in
the pool of tails (models/transformer.py, "A mixer beside attention").

A decode step writes, for every row b, the ring of the page that holds
the row's position: ``tails[layer, pid[b]] = rows[b]``, a ring being
``[K, C]`` (the last K filter inputs, the one at position t in ring row
t mod K; 196 KB over a delta-rule layer's q | k | v, 40 KB over a
mixer's x | B | C). XLA's scatter does that one row after another, a
``dynamic-update-slice`` and a select against the pool's old row each
(8.2 + 4.3 us a row where the bytes take 0.3: 2.6 ms of a 17.7 ms step
of 64 rows x 3 layers, on the chip, PERF.md, PR 50).

Here the page ids are scalars prefetched before the body runs, the pool
is declared to alias its output and never enters VMEM: the body starts
one copy a live row straight from the rows' array (where the compiler
left it) to the row's page in HBM, and then waits for them all, so the
copy engines run side by side and no grid step is paid a row. A row whose page id is negative
(an inactive lane) starts no copy: ``mode="drop"`` exactly, the null
page included. Rows own disjoint pages, so no two copies meet, and a
copy writes only the row's own page and shifts nothing: a step that was
launched ahead, discarded and run again writes the same ring to the
same page before anything reads it (``Engine._discard_ahead``).

Why the pool is ``[n, P, K, C]`` and not flat rows ``[n, P, K * C]``: a
TPU tiles an array's last two axes, and bfloat16 packs two rows to a
word, so in a flat pool a page's row is 768 pieces interleaved with its
neighbours' at two bytes' grain, which no copy can address (Mosaic
refuses a block or a slice of one row of it); with the ring's K rows as
the tiled axis (tiles of 4 x 128, no padding: the same bytes) a page's
ring is one contiguous piece.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas._compat import (
    HBM as _HBM, CompilerParams as _CompilerParams)


def _kernel(pid_ref, layer_ref, rows_ref, pool_in_ref, pool_ref, sem):
    del pool_in_ref                             # aliased to ``pool_ref``

    def each(do):
        def body(b, carry):
            @pl.when(pid_ref[b] >= 0)
            def _():
                do(pltpu.make_async_copy(
                    rows_ref.at[b], pool_ref.at[layer_ref[0], pid_ref[b]],
                    sem))
            return carry
        jax.lax.fori_loop(0, rows_ref.shape[0], body, 0)

    each(lambda copy: copy.start())
    # every copy moves the same bytes and signals the one semaphore, so
    # as many waits as starts see them all done
    each(lambda copy: copy.wait())


def ring_write(tails: jnp.ndarray, layer, pid: jnp.ndarray,
               rows: jnp.ndarray, *, interpret: bool = None) -> jnp.ndarray:
    """tails [n, P, K, C] (DONATED through the caller's jit: aliased to
    the output); layer a scalar; pid [B] the page a row's ring goes to
    (negative: the row writes nothing); rows [B, K, C] in the pool's
    type. Returns the pool."""
    if interpret is None:
        from xllm_service_tpu.ops import pallas
        interpret = pallas.default_interpret()
    assert rows.shape[1:] == tails.shape[2:] and rows.dtype == tails.dtype, (
        rows.shape, rows.dtype, tails.shape, tails.dtype)
    in_hbm = pl.BlockSpec(memory_space=_HBM)
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,                  # pid, layer
        grid=(1,),
        in_specs=[in_hbm, in_hbm],
        out_specs=in_hbm,
        scratch_shapes=[pltpu.SemaphoreType.DMA(())],
    )
    return pl.pallas_call(
        _kernel,
        # The result typed as HBM's: the compiler may then not stage a
        # pool small enough for VMEM through it (it did, the mixer's 31 MB
        # in and out around every layer's call: compiled for a described
        # v5e, PR 50); the aliased operand follows its result.
        out_shape=_HBM(tails.shape, tails.dtype),
        grid_spec=grid_spec,
        # flat operand order INCLUDING the scalar prefetch: 0 pid,
        # 1 layer, 2 the rows, 3 the pool -> output 0.
        input_output_aliases={3: 0},
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
        name="ring_write",
    )(pid.astype(jnp.int32), jnp.asarray(layer, jnp.int32).reshape(1),
      rows, tails)
