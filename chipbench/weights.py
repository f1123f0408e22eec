"""What every family's weight generator shares: the key from ``--seed``
and the two draws.

The benchmark makes its own weights on the device from the seed. The
program is handed them (it makes none of its own), and the plain
reference regenerates the very same values layer by layer after the
window, so neither side takes anything the other has made. WHICH tensors
a configuration has is its own business: ``weights.py`` beside its
``config.json`` (``spec.load_weights``; a body of its own, or a binding
to its family's under ``chipbench/weight_families/``) gives

- ``layer_kinds(cfg) -> list[str]``: one name per layer as run, in order;
- ``layer_params(cfg, key, i, kind) -> dict``: layer ``i``'s leaves as
  stored; shapes depend on ``(cfg, kind)`` alone, so ``i`` may be traced
  within a kind;
- ``head_params(cfg, key) -> dict`` with ``embed``, ``final_norm`` and
  ``lm_head``;
- ``program_tree(cfg, seed)``: the tree that the program's loader seam
  takes for this family, born on the device in the served type.

Values are drawn in float32 and stored in the type the configuration
serves; the reference reads those stored values up into float32.
"""

from __future__ import annotations

import math
from typing import Any, Dict

import jax
import jax.numpy as jnp


def root_key(seed: int) -> jax.Array:
    """A key from any whole number up to well past 2**31."""
    seed = int(seed)
    return jax.random.fold_in(jax.random.PRNGKey(seed & 0x7FFFFFFF),
                              (seed >> 31) & 0x7FFFFFFF)


def served_dtype(cfg: Dict[str, Any]):
    return jnp.dtype(cfg.get("torch_dtype") or "bfloat16")


def scaled_normal(key, shape, fan_in, dtype):
    # The scale is the power of two nearest 1/sqrt(fan_in): scaling by a
    # power of two commutes with rounding, so the value is the same bit
    # for bit however the compiler folds it into the draw (a scale of
    # 1/sqrt(14336) was folded differently under ``lax.map`` and alone,
    # and one weight in some thousands then rounded the other way).
    scale = 2.0 ** round(math.log2(1.0 / math.sqrt(fan_in)))
    return (jax.random.normal(key, shape, jnp.float32) * scale).astype(dtype)


def norm_weight(key, shape, dtype):
    # Not all ones: a norm weight that is dropped or applied twice must
    # change the answer.
    return (1.0 + 0.125 * jax.random.normal(key, shape, jnp.float32)
            ).astype(dtype)
