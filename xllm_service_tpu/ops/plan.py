"""Which attention path a step program takes: ONE decision per engine.

``ops/attention.py`` holds the XLA reference implementations and
``ops/pallas/`` the fused TPU kernels that replace them where it pays.
``KernelPlan.from_env`` chooses between them when an engine is built,
from the platform, the mesh, the two configurations and the
``XLLM_PALLAS*`` / ``XLLM_RAGGED_ATTN`` / ``XLLM_WRITE_THEN_ATTEND``
variables (read here and nowhere else). The result, a ``KernelPlan``,
rides every step program as a jit static; the layer bodies and the
dispatchers of ``ops/attention.py`` branch on its fields.

This module imports no kernel: ``ops/pallas`` (a second and a half of
``jax.experimental.pallas``) is loaded by the engine that needs it.
"""

import dataclasses
import os

import jax

_OFF = ("0", "false", "no")
_ON = ("1", "true", "yes")


def _on_off(value: str):
    """An on/off variable's value: True, False, or None when unset (or
    unreadable, which every gate has always treated as unset)."""
    value = value.strip()
    return False if value in _OFF else True if value in _ON else None


@dataclasses.dataclass(frozen=True)
class KernelPlan:
    """Everything a step program's layer body branches on. Frozen and
    hashable: a jit static, so two plans are two compiled programs and a
    traced program can never serve under another plan than its own.
    The default is the XLA reference throughout, attend-then-scatter."""
    # Pallas (True) or the XLA reference of ops/attention.py (False):
    # paged decode attention — and the mixed program's ragged attention,
    # whose rows are decode rows and windows read from the pool alike
    decode_attn: bool = False
    prefill_attn: bool = False     # flash prefill (windows that tile pages)
    # a latent model's decode write and attention (ops/pallas/latent.py)
    latent_decode: bool = False
    # the dropless expert layer's grouped matmuls: the Pallas one
    # (ops/pallas/grouped_matmul.py), else XLA's jax.lax.ragged_dot
    expert_gmm: bool = False
    # a state layer's one-token update, in place in the pool of states by
    # slot: a mixer's (ops/pallas/ssm_update.py) or a delta-rule layer's
    # (ops/pallas/kda_update.py), whichever the model has; else XLA's
    # gather and scatter of the rows' states. The layer's filter ring
    # with it: the step's ring written in place into its page of the
    # pool of tails (ops/pallas/ring_update.py), else XLA's scatter, a
    # row after another. (Its prefill is the chunked scan in XLA einsums
    # and the scatter under every plan: ``ssm_prefill`` below.)
    ssm_decode: bool = False
    kv_writers: bool = False       # in-place KV writers, else XLA scatter
    # The engine serves a mixed iteration as ONE ragged program ...
    mixed_step: bool = False
    # ... and THIS program is it: rows are prefill windows or single
    # decode continuations (``mixed_program`` sets it, nothing else).
    ragged_rows: bool = False
    # The pool rides the layer scan as a carry and each layer writes
    # before it attends; else attend first, one scatter after the scan.
    write_then_attend: bool = False
    # Every prefill window starts on a page boundary (all buckets are
    # page multiples): the in-place prefill writer's condition.
    page_aligned: bool = True
    # Kernels run under the Pallas interpreter (anywhere but a TPU).
    interpret: bool = False

    @property
    def uses_kernels(self) -> bool:
        return (self.decode_attn or self.prefill_attn
                or self.latent_decode or self.expert_gmm
                or self.ssm_decode or self.kv_writers)

    @property
    def ssm_prefill(self) -> str:
        """How a state layer's prefill window runs its recurrence (a
        mixer's, a delta-rule layer's): the chunked
        form in XLA einsums, under every plan (a Pallas scan only once a
        trace shows the XLA form as the prefill program's largest
        operation: PERF.md, PR 45)."""
        return "xla_chunked"

    def mixed_program(self) -> "KernelPlan":
        """The plan of the ragged mixed program: decode rows start
        mid-page, and every row's K/V must be in the pool before
        attention reads it."""
        return dataclasses.replace(self, ragged_rows=True,
                                   write_then_attend=True,
                                   page_aligned=False)

    @classmethod
    def from_env(cls, model_cfg, engine_cfg, mesh=None) -> "KernelPlan":
        """The plan of an engine for ``model_cfg`` / ``engine_cfg`` on
        ``mesh``, from what can be observed now: the platform and the
        environment. The environment wins over the configuration's
        fields, as it always has.

        A program partitioned over a mesh takes the XLA reference
        everywhere: a Mosaic kernel cannot be partitioned automatically
        ("Please wrap the call in a shard_map" — the v5e compiler,
        PR 22), and none of these kernels is wrapped yet."""
        environ = os.environ
        base = _on_off(environ.get("XLLM_PALLAS", ""))
        if base is None:
            base = _on_tpu()
        base = base and mesh is None
        # The writers follow the base gate; XLLM_PALLAS_KV=0 switches
        # them off on their own and =1 FORCES them on with the attention
        # kernels off: the aliased writers lower on Mosaic toolchains
        # whose attention-kernel relayouts do not, and XLA attention +
        # Pallas writers is what the copy census compiles
        # (tools/aot_copy_census.py).
        writers = _on_off(environ.get("XLLM_PALLAS_KV", ""))
        if writers is None:
            writers = base
        # Write-then-attend: auto is on wherever the kernels are on (the
        # aliased writers are what make the in-scan pool write free).
        wta = _on_off(environ.get("XLLM_WRITE_THEN_ATTEND", ""))
        if wta is None:
            wta = engine_cfg.write_then_attend
        if wta is None:
            wta = base
        mixed = _on_off(environ.get("XLLM_RAGGED_ATTN", ""))
        if mixed is None:
            mixed = engine_cfg.ragged_attn
        # A model whose layers differ in kind carries its pools through
        # the layer loop and writes before it attends, always
        # (models/transformer.py, "Layers that differ in kind").
        kinds = model_cfg.layer_kinds is not None
        # A model with NO layer that keeps keys and values (every layer a
        # retention layer) has nothing for the attention kernels or the
        # writers to serve, and its plan says so (``write_then_attend``
        # stays what a loop over kinds makes it: it orders nothing there
        # and still clamps a prefill table to the sequence's pages).
        attends = model_cfg.num_attn_layers > 0
        return cls(
            decode_attn=base and attends,
            # Opt-in; needs the base gate (no interpreter fallback on
            # the serving path). Checked on the chip for a model whose
            # layers differ in kind (PERF.md, PR 38: correct, a
            # follow-up window 17.6 ms against 28.9) and left opt-in
            # there too: the cell it speeds up spreads twice as widely.
            prefill_attn=base and attends
            and environ.get("XLLM_PALLAS_PREFILL", "0") == "1",
            # Decided on the chip (PERF.md, PR 36: batch 32, table width
            # 96, contexts of 8k-10.5k over a 576-wide row): the kernel
            # reads a row's pages once where the XLA reference gathers,
            # copies and reads the whole table's slab. The latent
            # kernels (ops/pallas/latent.py) are a write-then-attend
            # pair: without it a latent model decodes on the reference.
            latent_decode=base and model_cfg.mla and bool(wta),
            # Chosen on the chip over ragged_dot (PERF.md, PR 36); the
            # plan of every model whose sparse layers are the dropless
            # one says so, and no other's (ModelConfig.dropless_experts).
            expert_gmm=base and model_cfg.dropless_experts,
            # XLA's form gathers the rows' states out of the pool and
            # scatters them back: the kernel maps each row's block by its
            # slot and aliases the pool (PERF.md, PR 45; the delta
            # rule's twin, PR 49; the ring's writer, PR 50; a retention
            # layer's, whose block is 64 times a delta-rule head's,
            # PR 53).
            ssm_decode=base and model_cfg.num_state_layers > 0,
            kv_writers=writers and mesh is None and attends,
            # No ragged kernel for absorbed-MLA pools, no ragged rows in
            # the loop over layer kinds: they keep the split path.
            mixed_step=bool(mixed) and not model_cfg.mla and not kinds,
            write_then_attend=bool(wta) or kinds,
            # A window start is a sum of earlier bucket sizes. An engine
            # of a model whose state lives by slot ends a window inside
            # a page only where it ends the prompt
            # (Engine._window_cap), so every window STARTS on a page
            # boundary whatever the ladder holds: its windows of whole
            # pages take the in-place writer. (Under the scatter the
            # compiler relays pools of a few hundred pages around every
            # layer's write: 8 pool-sized copies in a prefill program of
            # this family, compiled for a described v5e; PERF.md, PR 45.)
            page_aligned=all(b % engine_cfg.page_size == 0
                             for b in engine_cfg.prefill_buckets)
            or model_cfg.num_state_layers > 0
            # ... and so does one with a second pool of window layers
            or model_cfg.num_swa_layers > 0,
            interpret=default_interpret())


def decode_walk_columns(table_width: int, page_size: int,
                        sliding_window) -> int:
    """Columns of a ``table_width``-wide page table that the decode
    attention kernel's grid walks for each row. A window of W positions
    spans at most ceil(W / page_size) + 1 pages wherever it starts, so
    under a STATIC positive window the walk is that many columns, from
    the row's own first live page (ops/pallas/paged_attention.py).
    Everything else walks the whole table: full attention (0), a traced
    window (the per-layer window vectors: a scan body has one grid), and
    a window whose span is no shorter than the table."""
    if isinstance(sliding_window, int) and sliding_window > 0:
        return min(table_width, -(-sliding_window // page_size) + 1)
    return table_width


# What the double-buffered block of pages that one grid step of a decode
# attention kernel folds may take of VMEM (of 16 MiB a kernel may use by
# default; decided on the chip: PERF.md, PR 41 for the latent kernel,
# PR 46 for the paged one).
_FOLD_VMEM_BYTES = 4 << 20


def _fold_pages(page_bytes: int, columns: int) -> int:
    """The largest power of two of pages whose double-buffered block
    stays under ``_FOLD_VMEM_BYTES``, and no more than ``columns``."""
    pages = 1
    while (2 * pages <= columns
           and 2 * (2 * pages) * page_bytes <= _FOLD_VMEM_BYTES):
        pages *= 2
    return pages


def latent_fold_pages(page_size: int, width: int, itemsize: int,
                      table_width: int) -> int:
    """Pages of a row that ONE grid step of the latent decode kernel
    folds (ops/pallas/latent.py): ``_fold_pages`` of a page's ``width``
    values riding whole 128-lane tiles, over the table's columns. From
    shapes alone: the kernel and the engine's plan line both read it
    here."""
    return _fold_pages(page_size * -(-width // 128) * 128 * itemsize,
                       table_width)


def paged_fold_pages(page_size: int, num_kv_heads: int, head_dim: int,
                     itemsize: int, walk: int) -> int:
    """Pages of a row that ONE grid step of the paged decode kernel folds
    (ops/pallas/paged_attention.py): ``_fold_pages`` of a page's keys
    and values together, over the ``walk`` columns the grid walks a row
    (``decode_walk_columns``). A page pair too large for two in the
    budget gives 1: a page a grid step. From shapes alone, as the latent
    kernel's: the kernel and the engine's plan line both read it here."""
    return _fold_pages(
        2 * page_size * num_kv_heads * -(-head_dim // 128) * 128 * itemsize,
        walk)


def paged_flat_positions(num_kv_heads: int, head_dim: int,
                         itemsize: int) -> int:
    """Positions of a page that ONE packed tile holds when the paged
    decode kernel reads the page flat, as the matrix
    ``[page_size * num_kv_heads, head_dim]`` (ops/pallas/
    paged_attention.py, "The flat page"); 1: the page is read by heads.
    A tile is 8 sublanes of 32-bit words, 16 rows of bfloat16. A head
    axis of 8 rows and more fills its vregs at least as far as the body
    by heads needs (84-86% of the roofline at 8 heads, PERF.md, PR 46);
    under that a position's heads are a fraction of a vreg each, and as
    many positions as fill a tile are read as one (4 at 4 heads of
    bfloat16, decided on the chip: PERF.md, PR 51). Only over whole
    128-lane rows, where the view names the pool's bytes in their order.
    From shapes alone: the kernel, the engine's plan line and the decode
    launch's span key all read it here."""
    tile_rows = 8 * max(4 // itemsize, 1)
    if head_dim % 128 or num_kv_heads >= 8 or tile_rows % num_kv_heads:
        return 1
    return tile_rows // num_kv_heads


def _on_tpu() -> bool:
    # A backend that fails to initialise raises here: "no TPU" must not
    # be how a broken TPU run looks (it would switch the kernels off and
    # the interpreter on, and serve from the reference path unnoticed).
    return jax.devices()[0].platform == "tpu"


def default_interpret() -> bool:
    """Kernel ``interpret=None`` resolution for callers that are not an
    engine (kernel tests, tools/aot_*; an engine passes its plan's):
    run under the Pallas interpreter anywhere but a real TPU (so
    XLLM_PALLAS=1 on CPU exercises kernel paths in tests instead of
    crashing in Mosaic). ``XLLM_PALLAS_INTERPRET=0`` forces REAL Mosaic
    lowering regardless of the runtime platform — required by the
    offline v5e AOT checks (tools/aot_engine_check.py), whose runtime
    backend is the pinned CPU while the compile target is the libtpu
    topology (without the override every kernel silently lowers as
    interpreter ops and the 'TPU' program under analysis contains no
    Mosaic at all)."""
    env = _on_off(os.environ.get("XLLM_PALLAS_INTERPRET", ""))
    return (not _on_tpu()) if env is None else env
