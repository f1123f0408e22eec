"""Fused Pallas TPU kernels for the serving hot path.

``ops/attention.py`` holds the XLA reference implementations (gather →
attend); these kernels replace them where it pays: paged decode attention
reads KV pages HBM→VMEM directly via a scalar-prefetched page table, so
the per-layer, per-step dense gather of the whole page table disappears
(half the HBM traffic of gather-then-attend, and no [B, S, Hkv, D]
materialization).

Selection: ``ops/plan.py`` — ``KernelPlan.from_env`` decides once per
engine which of these serve; the kernels themselves read nothing. On CPU
they still run under the Pallas interpreter for tests
(``interpret=True``).
"""

# The kernels' own ``interpret=None`` default, for direct callers.
from xllm_service_tpu.ops.plan import default_interpret  # noqa: F401
from xllm_service_tpu.ops.pallas.paged_attention import (  # noqa: F401
    paged_decode_attention_pallas)
from xllm_service_tpu.ops.pallas.prefill_attention import (  # noqa: F401
    paged_prefill_attention_pallas)
from xllm_service_tpu.ops.pallas.ragged_attention import (  # noqa: F401
    ragged_paged_attention_pallas)
