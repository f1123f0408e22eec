"""Grouped matmul for the dropless expert layer: rows sorted by group,
one weight matrix a group, only the groups that have rows are visited.

The kernel is JAX's own Pallas grouped matmul (``jax.experimental.
pallas.ops.tpu.megablox``); this module chooses its tiles and pads the
rows to them. Chosen on the chip against ``jax.lax.ragged_dot`` at the
shapes of 256 experts of 2048 x 768 (PERF.md, PR 36): a decode step's 256
assignments over 164 experts in 2.12 ms a layer against 4.03 (the bytes
of the experts touched allow 1.89), a 2048-token window's 16,384 in 4.85
against 10.75. In a device trace the operations are named ``gmm.<n>``.
"""

from __future__ import annotations

import jax.numpy as jnp

# Rows a tile: an expert's rows start anywhere, so a tile is visited once
# for every group that has rows in it, and each visit streams that
# group's weight tile whatever the rows; 128 fills the MXU's height.
_TM = 128
# Elements of one weight tile (tk x tn): 3 MB in bfloat16, double
# buffered inside the default scoped VMEM.
_TILE_ELEMS = 2048 * 768


def grouped_matmul(lhs: jnp.ndarray, rhs: jnp.ndarray,
                   group_sizes: jnp.ndarray, interpret: bool = False
                   ) -> jnp.ndarray:
    """lhs [M, K], its rows sorted by group; rhs [G, K, N]; group_sizes
    [G] int32 → [M, N] in lhs's type. Rows past the last group's end
    belong to no group: what comes back there is unspecified."""
    from jax.experimental.pallas.ops.tpu.megablox.gmm import gmm
    M, K = lhs.shape
    N = rhs.shape[-1]
    tm = _TM if M >= _TM else -(-M // 16) * 16
    pad = -M % tm
    if pad:
        lhs = jnp.pad(lhs, ((0, pad), (0, 0)))
    tk = min(K, 2048)
    tn = min(N, max(128, _TILE_ELEMS // tk // 128 * 128))
    out = gmm(lhs, rhs, group_sizes, preferred_element_type=lhs.dtype,
              tiling=(tm, tk, tn), interpret=interpret)
    return out[:M] if pad else out
