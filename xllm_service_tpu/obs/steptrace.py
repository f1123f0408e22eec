"""Device-plane step flight recorder, and the program's spans on the
profiler's clock.

The service plane got self-profiling in the hotpath-section catalog
(obs/profiler.py); the device plane still reported only aggregate
histograms — the ROADMAP's "unattributed ~17 ms/step decode debt" had
no per-step evidence trail. This module is that trail:

- **Step records**: every engine iteration appends one fixed-schema
  record (``STEP_FIELDS`` — a CLOSED catalog, machine-checked by xlint
  rule ``steptrace-schema`` exactly like the event/failpoint/section
  catalogs) into a bounded ring. The record carries the step kind, the
  per-phase ms delta from the engine's phase ledger (device_wait /
  host_copy splits included), the batch token mix, ragged/split
  dispatch counts, the speculation outcome delta, KV-page/cache
  deltas, and the request-id membership of the step.
- **Spans**: ``span(...)`` is the one helper behind every
  ``xllm.*`` span of the engine loop, the step's phases, the prefix
  index and admission (``SPAN_NAMES``, closed). While a device trace
  runs (``Worker.start_device_trace``) each is a
  ``jax.profiler.TraceAnnotation`` on the host plane of the same
  ``.xplane.pb`` as the device's operations; otherwise the helper tests
  one flag and returns a shared no-op.
- **Shipping**: the worker exposes the ring on ``GET /admin/steptrace``
  and ships a bounded tail on every heartbeat (sequence-baseline
  committed only on a delivered beat, so an undelivered tail is
  re-shipped — same discipline as the step-p99 bucket baseline); the
  master's ``StepBooks`` holds the last records per instance for the
  cluster-merged ``/admin/timeline`` export (obs/timeline.py).

``XLLM_STEPTRACE`` (default ON) and ``XLLM_STEPTRACE_RING`` (default
512) are read ONCE at import per the hot-path flag discipline; with the
flag off the recording path is a single ``if st.enabled:`` branch at
the call site — no record dict is ever built.
"""

from __future__ import annotations

import collections
import os
import threading
import time
from typing import Any, Deque, Dict, List, Optional, Tuple

from xllm_service_tpu.utils.locks import make_lock

# ---------------------------------------------------------------------------
# The closed step-record schema. xlint rule `steptrace-schema` pins every
# steptrace.record(<field>=...) keyword in the tree to this tuple — add
# the field HERE first, with a comment saying what it carries.
# ---------------------------------------------------------------------------
STEP_FIELDS: Tuple[str, ...] = (
    "seq",              # per-worker monotone step index (recorder-assigned)
    "t_wall",           # wall-clock END of the step (seconds, time.time)
    "model",            # model the iteration served
    "kind",             # prefill | decode | mixed | fault
    "step_ms",          # host wall time of the whole iteration
    "prefill_tokens",   # prompt tokens computed this step
    "decode_tokens",    # tokens sampled this step
    "prefill_windows",  # scheduled prefill window sizes (tuple of ints)
    "ragged",           # served by the one-dispatch ragged program (bool)
    "attn_dispatches",  # attention-bearing device dispatches this step
    "members",          # request ids in the step's batch (tuple)
    "phases",           # {phase: ms} DELTA of the engine ledger this step
    "spec",             # {ahead_dispatches, ahead_hits, ahead_discards}
                        # delta of the decode steps put on the device
                        # ahead of their iteration: launched ahead and
                        # dispatched at a tail, summed
    "kv_usage",         # KV page pool utilization [0,1] after the step
    "pages_delta",      # free-page delta across the step (+freed/-taken)
    "cache_hit_tokens", # prefix-cache hit-token delta this step
    "compiled",         # programs compiled AFTER warm-up in this step:
                        # tuple of "<program>:<shape key>" (0 is the contract)
    "moe",              # what the step's sparse layers counted on the
                        # device: {assignments, experts_touched, dropped,
                        # load_max_over_mean (busiest expert's rows over
                        # the mean of the touched ones)}; None where no
                        # layer counts its routing
    "passes",           # passes of the whole layer stack the step's
                        # programs ran, counted by the program's own
                        # loop; None for a model without a layer loop
    "exit_cdf",         # over the step's decode rows, the mean
                        # cumulative exit probability after each pass
                        # but the last; None where no row decoded or
                        # the model has no loop
    "state_restored",   # per row ADMITTED in this step, 1 where its first
                        # computed position read a cached page's
                        # convolution tails (a prefix hit), else 0; None
                        # for a model whose cached state is pages alone
    "state",            # a model whose state lives by slot (a matrix a
                        # head a layer): {live, snapshots} slots held
                        # after the step (rows with a live state,
                        # snapshots under the prefix index) and this
                        # step's {restored, snapshotted, evicted}
                        # (admissions begun from a snapshot's copy,
                        # snapshots attached to a page, snapshots
                        # dropped); None for every other model
)

# ---------------------------------------------------------------------------
# The closed span catalog. While a device trace runs (Worker.
# start_device_trace) every name below is written as a
# jax.profiler.TraceAnnotation into the host plane of the same .xplane.pb
# as the device's ``XLA Ops`` / ``XLA Modules`` lines, on the same clock,
# without the Python tracer. ``xllm.step.<phase>`` is every name
# Engine._phase is called with plus Engine._read_host's two splits.
# tests/test_devtrace.py holds every span site in the tree to this tuple.
# ---------------------------------------------------------------------------
STEP_PHASES: Tuple[str, ...] = (
    "sched", "kv_restore",
    "prefill.pack", "prefill.dispatch", "prefill.post",
    "prefill_ring.pack", "prefill_ring.dispatch",
    "ragged.pack", "ragged.dispatch", "ragged.post",
    "decode.pack", "decode.upload", "decode.dispatch",
    "decode.ahead_dispatch", "decode.tail_dispatch", "decode.post",
)
READ_HOST_PHASES: Tuple[str, ...] = (
    "prefill", "prefill_ring", "ragged", "decode",
    "kv_spill", "kv_export_blocks")

SPAN_NAMES: Tuple[str, ...] = (
    "xllm.loop.lock_wait",   # engine loop: wanting _engine_lock -> holding it
    "xllm.loop.step",        # around Engine.step(); arg seq
    "xllm.loop.emit",        # around Worker._dispatch_outputs; arg tokens
    "xllm.loop.obs_flush",   # around Worker._flush_engine_obs
    "xllm.loop.idle_wait",   # _work_event.wait when no runtime had work
    "xllm.kv.match_prefix",  # PrefixCacheIndex.match_prefix; arg tokens
    "xllm.kv.register_pages",  # PrefixCacheIndex.register_pages, where a
                             # full page lies past the row's watermark
                             # (no span where none does); args tokens,
                             # pages
    "xllm.kv.state_slots",   # Engine: a snapshot slot reserved (the
                             # least recently hit evicted for it) in
                             # prefill.pack, or attached to its page in
                             # prefill.post; arg snapshots
    "xllm.kv.window_trim",   # Engine._swa_trim: a row lets go of the
                             # window pool's pages behind its window (a
                             # model with a second pool of window layers);
                             # arg pages
    "xllm.kv.window_tail",   # the tails the prefix index keeps in the
                             # window pool: event attach (a finished
                             # prefill's deepest boundary; arg pages),
                             # restore (an admission takes its matched
                             # boundary's tail; arg pages), evict (the
                             # least recently hit makes room)
    "xllm.admit",            # handler thread: parsed request -> enqueued
    "xllm.admit.lock_wait",  # ... waiting for _engine_lock
    "xllm.admit.locked",     # ... holding it (Engine.add_request)
    "xllm.stream.token",     # the thread that writes a stream (the
                             # worker's ONE stream writer; the handler's
                             # own on the pull path): an output in hand
                             # -> its frames written
) + tuple("xllm.step." + p for p in STEP_PHASES) + tuple(
    f"xllm.step.{p}.{half}" for p in READ_HOST_PHASES
    for half in ("device_wait", "host_copy"))

_SPAN_SET = frozenset(SPAN_NAMES)


class _NullSpan:
    """The one shared no-op every span site gets while no trace runs."""

    __slots__ = ()

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        return False


_NULL_SPAN = _NullSpan()
_spans_on = False


def set_spans(on: bool) -> None:
    """The span switch. Process-wide, like the profiler session it
    follows: Worker.start_device_trace turns it on after the profiler
    has started and off before it stops."""
    global _spans_on
    _spans_on = bool(on)


def spans_on() -> bool:
    return _spans_on


def span(*parts: str, **args: Any):
    """A span on the profiler's clock: ``with steptrace.span(...)``.
    Off (the normal state) it tests one flag and returns the shared
    no-op; nothing is built or formatted. On it opens a
    ``jax.profiler.TraceAnnotation`` named by ``parts`` joined (a member
    of ``SPAN_NAMES``, or it raises) with ``args`` as the event's
    stats."""
    if not _spans_on:
        return _NULL_SPAN
    full = "".join(parts)
    if full not in _SPAN_SET:
        raise ValueError(
            f"unknown span {full!r}: add it to steptrace.SPAN_NAMES "
            f"first (closed catalog)")
    from jax.profiler import TraceAnnotation
    return TraceAnnotation(full, **args)
_FIELD_SET = frozenset(STEP_FIELDS)


def _enabled_from_env() -> bool:
    return os.environ.get("XLLM_STEPTRACE", "1").strip() not in (
        "0", "false", "no")


def _ring_from_env() -> int:
    try:
        return max(16, int(os.environ.get("XLLM_STEPTRACE_RING", "512")))
    except ValueError:
        return 512


ENABLED = _enabled_from_env()
RING = _ring_from_env()

class StepTrace:
    """Bounded per-worker ring of step records.

    ``record()`` assigns the monotone ``seq`` and validates field names
    against the closed catalog; readers get copies. The ring is shared
    between the engine-loop writer and the HTTP/heartbeat readers, so
    every access is under one low-rank lock — the writer takes it once
    per engine iteration, which is noise next to a device dispatch."""

    def __init__(self, enabled: Optional[bool] = None,
                 ring: Optional[int] = None) -> None:
        self.enabled = ENABLED if enabled is None else bool(enabled)
        self.capacity = RING if ring is None else max(16, int(ring))
        self._ring: Deque[Dict[str, Any]] = collections.deque(
            maxlen=self.capacity)
        self._seq = 0
        self._lock = make_lock("obs.steptrace", 85)

    def record(self, **fields: Any) -> int:
        """Append one step record; returns its ``seq``. Unknown field
        names raise — the schema is closed (xlint rule
        ``steptrace-schema`` enforces the same statically)."""
        unknown = set(fields) - _FIELD_SET
        if unknown:
            raise ValueError(
                f"unknown step-record fields {sorted(unknown)!r} — add "
                f"them to steptrace.STEP_FIELDS first (closed schema)")
        with self._lock:
            self._seq += 1
            fields["seq"] = self._seq
            fields.setdefault("t_wall", time.time())
            self._ring.append(fields)
            return self._seq

    def tail(self, n: int = 0, since_seq: int = 0,
             window_s: float = 0.0) -> List[Dict[str, Any]]:
        """Copies of the newest records, oldest-first — optionally only
        those with ``seq > since_seq`` (the heartbeat tail) and/or
        within ``window_s`` of the newest record (the timeline pull)."""
        with self._lock:
            recs = [dict(r) for r in self._ring]
        if since_seq > 0:
            recs = [r for r in recs if r.get("seq", 0) > since_seq]
        if window_s > 0 and recs:
            horizon = recs[-1].get("t_wall", 0.0) - window_s
            recs = [r for r in recs if r.get("t_wall", 0.0) >= horizon]
        if n > 0:
            recs = recs[-n:]
        return recs

    def last_seq(self) -> int:
        with self._lock:
            return self._seq

    @property
    def next_seq(self) -> int:
        """The ``seq`` the next record gets. Read without the lock: exact
        on the engine-loop thread, the only one that records."""
        return self._seq + 1

    def __len__(self) -> int:
        with self._lock:
            return len(self._ring)


class StepBooks:
    """Master-side per-instance step-record books, fed by heartbeat
    tails (``Heartbeat.steps``) — the fallback source for the merged
    timeline when a worker's ``/admin/steptrace`` pull fails. Bounded
    per instance; an instance's book is replaced record-by-record in
    seq order (re-shipped tails dedupe on seq)."""

    def __init__(self, per_instance: int = 256) -> None:
        self._cap = per_instance
        self._books: Dict[str, Deque[Dict[str, Any]]] = {}
        self._lock = make_lock("obs.stepbooks", 86)

    def ingest(self, instance: str, records: List[Dict[str, Any]]) -> None:
        if not records:
            return
        with self._lock:
            book = self._books.get(instance)
            if book is None:
                book = self._books[instance] = collections.deque(
                    maxlen=self._cap)
            have = {r.get("seq") for r in book}
            for r in records:
                if isinstance(r, dict) and r.get("seq") not in have:
                    book.append(r)

    def tail(self, instance: str, n: int = 0) -> List[Dict[str, Any]]:
        with self._lock:
            book = self._books.get(instance)
            recs = [dict(r) for r in book] if book else []
        recs.sort(key=lambda r: r.get("seq", 0))
        return recs[-n:] if n > 0 else recs

    def instances(self) -> List[str]:
        with self._lock:
            return sorted(self._books)
