"""Pallas TPU kernel: a power-retention layer's one-token state update,
in place in the pool of states by slot (models/transformer.py, "A
power-retention layer").

For every row b of a decode step, key-value head h of layer ``layer`` of
the pool ``state [L, S, H, R, Dh]`` (float32) moves from the row's read
slot to its write slot, and the head's ``G`` query heads read it:

    S'   = gamma[b, h] * S[read[b], h] + phi(k[b, h]) (outer) v[b, h]
    z'   = gamma[b, h] * z[read[b], h] + phi(k[b, h])
    o_a  = S'^T phi(q[b, h, a]) / (z' . phi(q[b, h, a]))       a < G
    S[write[b], h], z[write[b], h] = S', z'

``phi(u)`` holds ``u_i^2`` and ``sqrt(2) u_i u_j`` (i < j): the
symmetric half of the degree-2 products of the ``Dh`` key channels, so
that ``phi(a) . phi(b) = (a . b)^2``. It is never in HBM: the kernel is
handed k, v, gamma and the G queries as ONE tile of rows a head and
expands them itself.

**The layout** (``ModelConfig.ret_state_rows``; stated in PERF.md). A
head's ``R`` rows are ``nb = Dh / 2 + 1`` blocks of ``Dh`` rows and the
normaliser's ``nb`` rows behind them (to a tile's 8). Block ``d`` is
``[value channel c, key channel i]`` and holds the products of key
channels ``i`` and ``(i - d) mod Dh``: ``d = 0`` the squares, ``0 < d <
Dh / 2`` every pair that far apart once (times sqrt 2), ``d = Dh / 2``
each pair twice, so its lanes ``i >= Dh / 2`` stay zero. 65 x 128 =
8,320 rows a head at Dh = 128, 8,256 of them the symmetric half; not
the 16,384 of the full product. With the key channel on the LANES a
token's expanded key of block ``d`` is one row, ``k * roll(k, d)``: all
blocks at once are ONE strided lane rotation of a [nb, Dh] tile (9
vregs), where key channels down the sublanes would cost a lane
broadcast a vreg of state. v rides the sublanes, broadcast along the
lanes once a head (a transpose of its row), and o_a comes out a column
a tile of 8 value channels: summed over lanes, parked in lane ``a`` of
a [Dh, Dh] scratch, transposed once at the end.

In ``ssm_update.py``'s form: the slot ids are scalars prefetched before
the body runs, each grid cell's block is mapped BY ITS SLOT (read on the
way in, write on the way out), and the pool is declared to alias its
output. Grid (B, H): a block is ONE head of one row, 4.3 MB at Dh = 128
(64 times a delta-rule head's), in and out double-buffered 17.2 MB: the
kernel asks for the VMEM it needs (``vmem_limit_bytes``). An inactive
row reads and writes the null slot with gamma = 1 and k = v = q = 0: it
writes back what it read, and its output is 0 (a normaliser of exactly
0 divides nothing). Read and write slot of a row differ (the position's
parity), rows own disjoint slots, and the cells run one after another
(``arbitrary``), so no cell reads a block another has yet to write.
"""

from __future__ import annotations

import math

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from xllm_service_tpu.ops.pallas._compat import (
    CompilerParams as _CompilerParams)

_K, _V, _GAMMA, _Q = 0, 1, 2, 3         # rows of a head's operand tile


def _kernel(read_ref, write_ref, layer_ref, rows_ref, s_in_ref, s_out_ref,
            o_ref, p_ref, vt_ref, acc_ref, *, group: int):
    del read_ref, write_ref, layer_ref          # the index maps' alone
    Dh = s_in_ref.shape[-1]
    nb = Dh // 2 + 1
    zr = p_ref.shape[1]
    f32 = jnp.float32
    rows = rows_ref[0, 0]                                   # [NR, Dh]
    d_i = jax.lax.broadcasted_iota(jnp.int32, (zr, Dh), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (zr, Dh), 1)
    root2 = jnp.asarray(math.sqrt(2.0), f32)
    coef = jnp.where(
        d_i == 0, jnp.asarray(1.0, f32), jnp.where(
            (d_i < Dh // 2) | ((d_i == Dh // 2) & (lane < Dh // 2)),
            root2, jnp.asarray(0.0, f32)))

    def expand(r):
        """phi of a row [1, Dh] as [zr, Dh]: row d = coef * r * roll(r, d)."""
        x = jnp.broadcast_to(r, (zr, Dh))
        return coef * x * pltpu.roll(x, 0, 1, stride=1, stride_axis=0)

    gamma = rows[_GAMMA:_GAMMA + 1]                         # [1, Dh]
    pk = expand(rows[_K:_K + 1])
    p_ref[0] = pk
    for a in range(group):
        p_ref[1 + a] = expand(rows[_Q + a:_Q + a + 1])
    z0 = nb * Dh
    z = gamma * s_in_ref[0, 0, 0, z0:z0 + zr, :] + pk
    s_out_ref[0, 0, 0, z0:z0 + zr, :] = z
    # v down the sublanes, the same in every lane
    vt_ref[...] = jnp.broadcast_to(rows[_V:_V + 1], (Dh, Dh)).T
    col = jax.lax.broadcasted_iota(jnp.int32, (8, Dh), 1)

    def tile(t, carry):
        r0 = pl.multiple_of(t * 8, 8)
        vb = vt_ref[pl.ds(r0, 8), :]                        # [8, Dh]
        acc = [jnp.zeros((8, Dh), f32) for _ in range(group)]
        for d in range(nb):
            at = pl.ds(pl.multiple_of(d * Dh + r0, 8), 8)
            s = gamma * s_in_ref[0, 0, 0, at, :] + vb * p_ref[0, d:d + 1, :]
            s_out_ref[0, 0, 0, at, :] = s
            for a in range(group):
                acc[a] = acc[a] + s * p_ref[1 + a, d:d + 1, :]
        out = jnp.zeros((8, Dh), f32)
        for a in range(group):
            out = jnp.where(col == a,
                            jnp.sum(acc[a], axis=1, keepdims=True), out)
        acc_ref[pl.ds(r0, 8), :] = out
        return carry

    jax.lax.fori_loop(0, Dh // 8, tile, 0)
    num = acc_ref[...].T                            # row a: o_a [Dh]
    for a in range(group):
        den = jnp.sum(z * p_ref[1 + a])
        o_ref[0, 0, a:a + 1, :] = num[a:a + 1] / jnp.where(den == 0.0, 1.0,
                                                           den)


def retention_decode_update(state: jnp.ndarray, layer, read: jnp.ndarray,
                            write: jnp.ndarray, q: jnp.ndarray,
                            k: jnp.ndarray, v: jnp.ndarray,
                            gamma: jnp.ndarray, *, interpret: bool = None):
    """state [L, S, H, R, Dh] float32 (DONATED through the caller's jit:
    aliased to the output); layer a scalar; read / write [B] slot ids; q
    [B, H * G, Dh] (query head a of key-value head h at h * G + a), k and
    v [B, H, Dh], gamma [B, H] (1, and k = v = q = 0, on an inactive
    row). Returns ``(o [B, H * G, Dh], state)``."""
    if interpret is None:
        from xllm_service_tpu.ops import pallas
        interpret = pallas.default_interpret()
    L, S, H, R, Dh = state.shape
    B = q.shape[0]
    G = q.shape[1] // H
    nb = Dh // 2 + 1
    zr = R - nb * Dh
    if Dh % 8 or zr != -(-nb // 8) * 8:
        raise ValueError(
            f"a pool of {state.shape} is not a retention state's: {nb} "
            f"blocks of {Dh} rows and the normaliser's to a tile's 8 at "
            f"a head width that is whole tiles")
    f32 = jnp.float32
    NR = -(-(_Q + G) // 8) * 8      # k, v, gamma and the G queries, to 8
    rows = jnp.concatenate([
        k.astype(f32)[:, :, None], v.astype(f32)[:, :, None],
        jnp.broadcast_to(gamma.astype(f32)[:, :, None, None], (B, H, 1, Dh)),
        q.astype(f32).reshape(B, H, G, Dh),
        jnp.zeros((B, H, NR - _Q - G, Dh), f32)], axis=2)    # [B, H, NR, Dh]
    NO = -(-G // 8) * 8

    def row(b, h, *_):
        return (b, h, 0, 0)

    def pool(slots):
        def index(b, h, read_ref, write_ref, layer_ref):
            ref = read_ref if slots == "read" else write_ref
            return (layer_ref[0], ref[b], h, 0, 0)
        return pl.BlockSpec((1, 1, 1, R, Dh), index)

    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=3,                  # read, write, layer
        grid=(B, H),
        in_specs=[pl.BlockSpec((1, 1, NR, Dh), row), pool("read")],
        out_specs=[pool("write"), pl.BlockSpec((1, 1, NO, Dh), row)],
        scratch_shapes=[pltpu.VMEM((1 + G, zr, Dh), f32),
                        pltpu.VMEM((Dh, Dh), f32),
                        pltpu.VMEM((Dh, Dh), f32)],
    )
    block = R * Dh * 4
    state, o = pl.pallas_call(
        lambda *refs: _kernel(*refs, group=G),
        out_shape=[jax.ShapeDtypeStruct(state.shape, state.dtype),
                   jax.ShapeDtypeStruct((B, H, NO, Dh), f32)],
        grid_spec=grid_spec,
        # flat operand order INCLUDING the scalar prefetch: 0-2 the
        # scalars, 3 the rows, 4 the pool -> output 0.
        input_output_aliases={4: 0},
        compiler_params=_CompilerParams(
            dimension_semantics=("arbitrary", "arbitrary"),
            # a head's block in and out, each double-buffered, and room
            # for the scratch and the body's temporaries
            vmem_limit_bytes=max(4 * block + (8 << 20), 16 << 20)),
        interpret=interpret,
        name="retention_decode_update",
    )(read.astype(jnp.int32), write.astype(jnp.int32),
      jnp.asarray(layer, jnp.int32).reshape(1), rows, state)
    return o[:, :, :G].reshape(B, H * G, Dh), state
