"""The one spelling site for version-sensitive pallas/jax API names.

Kernel modules import ``CompilerParams`` / ``HBM`` / ``shard_map_unchecked``
from here and never from jax directly (xlint rule ``mosaic-compat``), so
the next rename in jax is a one-file change. Only the installed jax is
supported: a name that is missing fails at import.
"""

import functools

import jax
from jax.experimental.pallas import tpu as _pltpu

CompilerParams = _pltpu.CompilerParams

# "Leave the operand in HBM, the kernel DMAs it itself."
HBM = _pltpu.HBM


def shard_map_unchecked():
    """``jax.shard_map`` with replication checking off; takes the usual
    (f, mesh=..., in_specs=..., out_specs=...) arguments."""
    return functools.partial(jax.shard_map, check_vma=False)
