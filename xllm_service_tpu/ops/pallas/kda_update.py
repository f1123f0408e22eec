"""Pallas TPU kernel: a delta-rule layer's one-token state update, in
place in the pool of states by slot (models/transformer.py, "A
delta-rule linear-attention layer").

For every row b of a decode step, layer ``layer`` of the pool
``state [L, S, H, Dk, Dv]`` (float32; a head's matrix is [key channel,
value channel]) moves from the row's read slot to its write slot:

    S'  = alpha[b, h, :, None] * S[read[b], h]        (a decay a channel)
    r   = v[b, h] - sum_k S'[k, :] * k[b, h, k]
    S'' = S' + (beta[b, h] k[b, h])[:, None] * r[None, :]
    o   = sum_k S''[k, :] * q[b, h, k]
    S[write[b], h] = S''

In ``ssm_update.py``'s form: the slot ids are scalars prefetched before
the body runs, each grid cell's block is mapped BY ITS SLOT (read on the
way in, write on the way out), and the pool is declared to alias its
output, so a step reads and writes 2 x H x Dk x Dv x 4 bytes a row a
layer and little else. Grid (B, H / hb): a block is ``hb`` heads of one
row.

The value channel rides the lanes, so v, r and o are [1, Dv] rows that
broadcast along the state's sublanes, and the sums over key channels
are sums over sublanes. What a head has a KEY CHANNEL (alpha, k, q and
beta k) must broadcast along the lanes: a [Dk, 1] column each. A column
alone is padded to 128 lanes wherever it lies, as large as the head's
state; so the four columns of a block's ``hb`` heads are packed side by
side, ``cols [B, H / hb, Dk, 4 hb]`` (32 lanes at hb = 8: a quarter of
a tile used, 64 KB a block beside the block's 512 KB of state in and as
much out), and the body slices a head's four columns at fixed lanes. An
inactive row reads and writes the null slot with alpha = 1 and beta = 0:
it writes back what it read. Read and write slot of a row differ (the
position's parity), rows own disjoint slots, and the cells run one after
another (``arbitrary``), so no cell reads a block another has yet to
write.
"""

from __future__ import annotations

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas._compat import (
    CompilerParams as _CompilerParams)

_COLS = 4                       # alpha, k, q, beta k


def _kernel(read_ref, write_ref, layer_ref, cols_ref, v_ref, s_in_ref,
            s_out_ref, o_ref):
    del read_ref, write_ref, layer_ref          # the index maps' alone
    cols = cols_ref[0, 0]                                   # [Dk, 4 hb]
    for i in range(v_ref.shape[2]):
        alpha, k, q, bk = (cols[:, _COLS * i + j:_COLS * i + j + 1]
                           for j in range(_COLS))           # [Dk, 1] each
        s = alpha * s_in_ref[0, 0, i]                       # [Dk, Dv]
        r = v_ref[0, 0, i:i + 1, :] - jnp.sum(s * k, axis=0, keepdims=True)
        s = s + bk * r
        s_out_ref[0, 0, i] = s
        o_ref[0, 0, i:i + 1, :] = jnp.sum(s * q, axis=0, keepdims=True)


def head_block(heads: int) -> int:
    """Heads a grid cell updates: up to 8 (a block of 8 x 128 x 128
    float32 is 512 KiB: in and out, double-buffered, 2 of the 16 MiB a
    kernel may use; 8 rows of v and of o fill a tile's sublanes)."""
    return max(d for d in (8, 4, 2, 1) if heads % d == 0)


def kda_decode_update(state: jnp.ndarray, layer, read: jnp.ndarray,
                      write: jnp.ndarray, q: jnp.ndarray, k: jnp.ndarray,
                      v: jnp.ndarray, alpha: jnp.ndarray,
                      beta: jnp.ndarray, *, interpret: bool = None):
    """state [L, S, H, Dk, Dv] float32 (DONATED through the caller's jit:
    aliased to the output); layer a scalar; read / write [B] slot ids; q,
    k, alpha [B, H, Dk], v [B, H, Dv], beta [B, H] (alpha 1 and beta 0
    on an inactive row). Returns ``(o [B, H, Dv], state)``."""
    if interpret is None:
        from xllm_service_tpu.ops import pallas
        interpret = pallas.default_interpret()
    L, S, H, Dk, Dv = state.shape
    B = q.shape[0]
    hb = head_block(H)
    nb = H // hb
    f32 = jnp.float32
    cols = jnp.stack([alpha, k, q, beta[..., None] * k], axis=-1)
    # [B, H, Dk, 4] -> [B, nb, Dk, hb * 4]: head i of a block at lanes 4i..
    cols = jnp.moveaxis(cols.astype(f32).reshape(B, nb, hb, Dk, _COLS), 2, 3
                        ).reshape(B, nb, Dk, hb * _COLS)
    v = v.astype(f32).reshape(B, nb, hb, Dv)

    def row(b, j, *_):
        return (b, j, 0, 0)

    def pool(slots):
        def index(b, j, read_ref, write_ref, layer_ref):
            ref = read_ref if slots == "read" else write_ref
            return (layer_ref[0], ref[b], j, 0, 0)
        return pl.BlockSpec((1, 1, hb, Dk, Dv), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                  # read, write, layer
        grid=(B, nb),
        in_specs=[pl.BlockSpec((1, 1, Dk, hb * _COLS), row),
                  pl.BlockSpec((1, 1, hb, Dv), row),
                  pool("read")],
        out_specs=[pool("write"), pl.BlockSpec((1, 1, hb, Dv), row)],
    )
    state, o = pl.pallas_call(
        _kernel,
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, nb, hb, Dv), f32)],
        grid_spec=grid_spec,
        # flat operand order INCLUDING the scalar prefetch: 0-2 the
        # scalars, 3 the columns, 4 v, 5 the pool -> output 0.
        input_output_aliases={5: 0},
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary")),
        interpret=interpret,
        name="kda_decode_update",
    )(read.astype(jnp.int32), write.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), cols, v, state)
    return o.reshape(B, H, Dv), state
