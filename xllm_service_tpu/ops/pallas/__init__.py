"""Fused Pallas TPU kernels for the serving hot path.

``ops/attention.py`` holds the XLA reference implementations (gather →
attend); these kernels replace them where it pays: paged decode attention
reads KV pages HBM→VMEM directly via a scalar-prefetched page table, so
the per-layer, per-step dense gather of the whole page table disappears
(half the HBM traffic of gather-then-attend, and no [B, S, Hkv, D]
materialization).

Selection: ``enabled()`` — on for TPU backends, off elsewhere, overridable
with XLLM_PALLAS=0/1. On CPU the kernels still run under the Pallas
interpreter for tests (``interpret=True``).
"""

import contextlib
import os
import threading

import jax

# Per thread: a program is traced by the thread that calls it.
_tracing = threading.local()


@contextlib.contextmanager
def reference_path():
    """While tracing inside this context every kernel gate answers off
    and the XLA reference implementations of ``ops/attention.py`` serve.
    For programs partitioned over a mesh: a Mosaic kernel cannot be
    partitioned automatically ("Please wrap the call in a shard_map" —
    the v5e compiler, PR 22), and none of these kernels is wrapped yet."""
    before = reference_only()
    _tracing.reference_only = True
    try:
        yield
    finally:
        _tracing.reference_only = before


def reference_only() -> bool:
    return getattr(_tracing, "reference_only", False)


def enabled() -> bool:
    if reference_only():
        return False
    env = os.environ.get("XLLM_PALLAS", "").strip()
    if env in ("0", "false", "no"):
        return False
    if env in ("1", "true", "yes"):
        return True
    return _on_tpu()


def _on_tpu() -> bool:
    # A backend that fails to initialise raises here: "no TPU" must not
    # be how a broken TPU run looks (it would switch the kernels off and
    # the interpreter on, and serve from the reference path unnoticed).
    return jax.devices()[0].platform == "tpu"


def mla_kernel_enabled() -> bool:
    """Opt-in gate for routing absorbed-MLA decode (Hkv=1, D=r+rope —
    e.g. 576 for DeepSeek, not 128-lane-aligned) through the paged
    decode kernel. Off by default: the MLA shape compiles for v5e
    (tests/test_chip_compile.py) but has no result checked on a chip;
    the XLA gather reference serves MLA otherwise."""
    return os.environ.get("XLLM_PALLAS_MLA", "0") == "1" and enabled()


def default_interpret() -> bool:
    """Kernel ``interpret=None`` resolution, shared by every kernel: run
    under the Pallas interpreter anywhere but a real TPU (so XLLM_PALLAS=1
    on CPU exercises kernel paths in tests instead of crashing in
    Mosaic). ``XLLM_PALLAS_INTERPRET=0`` forces REAL Mosaic lowering
    regardless of the runtime platform — required by the offline v5e
    AOT checks (tools/aot_engine_check.py), whose runtime backend is the
    pinned CPU while the compile target is the libtpu topology (without
    the override every kernel silently lowers as interpreter ops and
    the 'TPU' program under analysis contains no Mosaic at all)."""
    env = os.environ.get("XLLM_PALLAS_INTERPRET", "").strip()
    if env in ("0", "false", "no"):
        return False
    if env in ("1", "true", "yes"):
        return True
    return not _on_tpu()


from xllm_service_tpu.ops.pallas.paged_attention import (  # noqa: E402,F401
    paged_decode_attention_pallas)
from xllm_service_tpu.ops.pallas.prefill_attention import (  # noqa: E402,F401
    paged_prefill_attention_pallas, prefill_kernel_enabled)
from xllm_service_tpu.ops.pallas.ragged_attention import (  # noqa: E402,F401
    ragged_attn_enabled, ragged_paged_attention_pallas)
