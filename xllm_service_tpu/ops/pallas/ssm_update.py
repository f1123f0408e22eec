"""Pallas TPU kernel: a mixer's one-token state update, in place in the
pool of states by slot (models/transformer.py, "A mixer beside
attention").

For every row b of a decode step, layer ``layer`` of the pool
``state [L, S, H, N, P]`` (float32; a head's matrix is stored [state,
head width]) moves from the row's read slot to its write slot:

    S'   = dA[b, h] * S[read[b], h] + B[b, g(h), n] * (dt x)[b, h, p]
    y    = sum_n S'[h, n, p] * C[b, g(h), n]
    S[write[b], h] = S'

XLA's form gathers the rows' states out of the pool (a [B, H, N, P]
temporary), updates them and scatters them back; here the slot ids are
scalars prefetched before the body runs, each grid cell's block is
mapped BY ITS SLOT (read on the way in, write on the way out), and the
pool is declared to alias its output, so a step reads and writes
2 x H x P x N x 4 bytes a row a layer and little else (the row's
operands: under a tenth of that).

Grid (B, H / hb): a block is ``hb`` heads of one group of one row (hb
divides the heads a group serves, so a cell needs ONE column pair of B
and C). The head width rides the lanes in the pool as in the row's x
and y, so dA, dt x and y are [hb, 1, P] rows that broadcast along the
state's sublanes, and B and C come as ONE [N, 2] column pair a group
that broadcasts along its lanes: no relayout in the body and no operand
the size of the state (a first form kept the pool [.., P, N] and fed
dA and dt x as [.., P, 1] columns, each padded to 128 lanes, 67 MB a
layer a step of them: 1.8 ms of XLA fusions in front of the kernel and
54% of its roofline, on the chip, PR 45). An inactive row reads and
writes the null slot with dA = 1 and dt x = 0: it writes back what it
read. Read and write slot of a row differ (the position's parity), rows
own disjoint slots, and the cells run one after another
(``arbitrary``), so no cell reads a block another has yet to write.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas._compat import (
    CompilerParams as _CompilerParams)


def _kernel(read_ref, write_ref, layer_ref, da_ref, dtx_ref, bc_ref,
            s_in_ref, s_out_ref, y_ref):
    del read_ref, write_ref, layer_ref          # the index maps' alone
    bc = bc_ref[0, 0]                                           # [N, 2]
    s = da_ref[0] * s_in_ref[0, 0] + bc[:, 0:1] * dtx_ref[0]
    s_out_ref[0, 0] = s
    y_ref[0] = jnp.sum(s * bc[:, 1:2], axis=1, keepdims=True)


def head_block(heads_per_group: int) -> int:
    """Heads a grid cell updates: as many as divide the heads one group
    of B and C serves, up to 8 (a block of 8 x 256 x 128 float32 is
    1 MiB: in and out, double-buffered, 4 of the 16 MiB a kernel may
    use)."""
    return max(d for d in (8, 4, 2, 1) if heads_per_group % d == 0)


def ssm_decode_update(state: jnp.ndarray, layer, read: jnp.ndarray,
                      write: jnp.ndarray, x: jnp.ndarray, dt: jnp.ndarray,
                      A: jnp.ndarray, Bm: jnp.ndarray, Cm: jnp.ndarray, *,
                      interpret: bool = None):
    """state [L, S, H, N, P] float32 (DONATED through the caller's jit:
    aliased to the output); layer a scalar; read / write [B] slot ids; x
    [B, H, P], dt [B, H] (0 on an inactive row), A [H], Bm / Cm
    [B, G, N]. Returns ``(y [B, H, P] without the D term, state)``."""
    if interpret is None:
        from xllm_service_tpu.ops import pallas
        interpret = pallas.default_interpret()
    L, S, H, N, P = state.shape
    B, G = x.shape[0], Bm.shape[1]
    J = H // G
    hb = head_block(J)
    f32 = jnp.float32
    da = jnp.broadcast_to(jnp.exp(dt * A).astype(f32)[:, :, None, None],
                          (B, H, 1, P))
    dtx = (dt[:, :, None] * x).astype(f32)[:, :, None, :]    # [B, H, 1, P]
    bc = jnp.stack([Bm.astype(f32), Cm.astype(f32)], axis=-1)  # [B,G,N,2]

    def row(b, j, *_):
        return (b, j, 0, 0)

    def group(b, j, *_):
        return (b, (j * hb) // J, 0, 0)

    def pool(slots):
        def index(b, j, read_ref, write_ref, layer_ref):
            ref = read_ref if slots == "read" else write_ref
            return (layer_ref[0], ref[b], j, 0, 0)
        return pl.BlockSpec((1, 1, hb, N, P), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                  # read, write, layer
        grid=(B, H // hb),
        in_specs=[pl.BlockSpec((1, hb, 1, P), row),
                  pl.BlockSpec((1, hb, 1, P), row),
                  pl.BlockSpec((1, 1, N, 2), group),
                  pool("read")],
        out_specs=[pool("write"), pl.BlockSpec((1, hb, 1, P), row)],
    )
    state, y = pl.pallas_call(
        _kernel,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, 1, P), f32)],
        grid_spec=grid_spec,
        # flat operand order INCLUDING the scalar prefetch: 0-2 the
        # scalars, 3 dA, 4 dt x, 5 B | C, 6 the pool -> output 0.
        input_output_aliases={6: 0},
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="ssm_decode_update",
    )(read.astype(jnp.int32), write.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), da, dtx, bc, state)
    return y[:, :, 0, :], state
