"""Per-request span timelines: where did this request's latency go?

One span per ``service_request_id``: an ordered list of stage events,
each stamped with the recording process's monotonic clock (interval
arithmetic within a plane) and wall clock (cross-plane ordering — the
service and worker monotonic clocks share no epoch). The service plane
records accepted → received → admitted → scheduled → dispatched →
first_token → finished; the worker records its own received →
scheduled → first_token → finished, and between them the stamps of
``FIRST_TOKEN_STAMPS``, under the SAME correlation id (propagated as the
``x-xllm-request-id`` header on the forwarded request) and ships
finished spans back on the heartbeat path, where the service merges
them in with ``plane="worker"``. The merged timeline is queryable at
``GET /admin/trace/<request_id>`` on the service plane.

Storage is a bounded ring: the oldest span is evicted when ``capacity``
is exceeded, so tracing is always on without growing without bound
(size the ring via ``XLLM_SPAN_RING`` at the call site that builds the
store). Thread-safe; rank ``obs.spans`` in the utils/locks.py table.
"""

from __future__ import annotations

import collections
import time
from typing import Any, Dict, List, Optional

from xllm_service_tpu.obs import profiler
from xllm_service_tpu.utils.locks import make_lock


def _deep_copy(v: Any) -> Any:
    """Deep-enough copy for span/event payloads (dict/list/tuple of
    JSON-ish values). The read side copies; writers stay cheap. Shallow
    ``dict(...)`` is NOT enough: ``merge_remote`` nests per-plane attr
    dicts (and remote events can carry dict/list attr values) that
    would stay shared with the live span and mutate mid-render."""
    if isinstance(v, dict):
        return {k: _deep_copy(x) for k, x in v.items()}
    if isinstance(v, (list, tuple)):
        return [_deep_copy(x) for x in v]
    return v


# Canonical service-plane stage order (docs/OBSERVABILITY.md); extra
# stages (e.g. "redispatch"/"redispatched") may interleave — the first
# occurrence per (stage, plane) wins (see record()).
SERVICE_STAGES = ("accepted", "received", "admitted", "scheduled",
                  "dispatched", "first_token", "finished")
# "encoded" appears only on multimodal requests: the prefill worker
# records it once the EPD encode stage resolved (attrs say whether a
# remote ENCODE instance, a cache hit, or local fallback produced the
# embeddings — docs/EPD.md). "faulted" appears only on requests the
# engine-step fault boundary blamed and evicted (docs/ROBUSTNESS.md
# device-plane fault contract).
WORKER_STAGES = ("received", "encoded", "parsed", "locked", "scheduled",
                 "slotted", "launched", "ready", "first_token",
                 "first_frame", "faulted", "finished")

# Where a request's time to its first token goes, on the worker: each
# stamp (a worker-plane stage above, taken where docs/OBSERVABILITY.md
# says) and the interval that ENDS at it, the ``stage`` label of
# ``xllm_worker_first_token_stage_ms``. The stamps are contiguous, so a
# request's stage observations sum to its ``total`` (``received`` to
# ``first_frame``). Closed: every stamp site in the tree names an entry
# (tests/test_first_token_stages.py).
FIRST_TOKEN_STAMPS = (
    ("received", None),
    ("parsed", "parse"),
    ("locked", "lock_wait"),
    ("slotted", "queue"),
    ("launched", "prefill_host"),
    ("ready", "prefill_device"),
    ("first_token", "post_emit"),
    ("first_frame", "stream_out"),
)
# Beside them: the master's share, which rides the forward as a duration
# (FRONT_MS_HEADER), and the whole of the worker's.
FIRST_TOKEN_STAGES = ("master_in",) + tuple(
    stage for _, stage in FIRST_TOKEN_STAMPS if stage) + ("total",)


def first_token_stages(stamps: Dict[str, float]) -> Dict[str, float]:
    """Milliseconds of each stage whose stamp and the stamp before it in
    the table are both there; ``total`` where the chain has both ends. A
    path that lacks a stamp gives the stages it has."""
    out: Dict[str, float] = {}
    prev = None
    for name, stage in FIRST_TOKEN_STAMPS:
        t = stamps.get(name)
        if stage and t is not None and prev is not None:
            out[stage] = 1000.0 * (t - prev)
        prev = t
    first, last = FIRST_TOKEN_STAMPS[0][0], FIRST_TOKEN_STAMPS[-1][0]
    if first in stamps and last in stamps:
        out["total"] = 1000.0 * (stamps[last] - stamps[first])
    return out

DEFAULT_CAPACITY = 2048

# The correlation header the service stamps on every forwarded request;
# the worker tags its span stages with this id (defined here, not in
# http_service, so the worker doesn't import the whole service plane
# for one constant).
REQUEST_ID_HEADER = "x-xllm-request-id"
# The master's share of a request's time to its first token, on the same
# forward: milliseconds on the master's monotonic clock from the entry of
# its completions handler to the building of the forward's headers, and
# the part of that inside ``scheduler.schedule()``. Durations, since the
# two planes' monotonic clocks share no epoch.
FRONT_MS_HEADER = "x-xllm-front-ms"
SCHEDULE_MS_HEADER = "x-xllm-schedule-ms"


class SpanStore:
    """Ring buffer of span timelines keyed by correlation id."""

    def __init__(self, capacity: int = DEFAULT_CAPACITY) -> None:
        self.capacity = max(1, int(capacity))
        self._lock = make_lock("obs.spans", 94)
        # rid → {"request_id", "attrs", "events": [event...]}; insertion
        # order is eviction order.
        self._spans: "collections.OrderedDict[str, Dict[str, Any]]" = \
            collections.OrderedDict()
        # drain_finished queue. Always ⊆ the ring's keys (eviction
        # discards the mark too) so a plane that never drains — the
        # service, which drains nothing; only workers export — stays
        # bounded by ``capacity`` instead of leaking one id per request.
        self._finished: set = set()
        # The ids drained lately (an LRU set): an event recorded for one
        # AFTER its span left (a first-token stamp folded by the handler's
        # thread once the engine's had already finished the request)
        # queues what it starts for the next drain by itself, and the
        # importer merges the two parts.
        self._drained: "collections.OrderedDict[str, None]" = \
            collections.OrderedDict()
        # Eviction visibility: a counter (exported as
        # ``xllm_span_evictions_total`` on both planes) plus a small
        # tombstone ring of evicted rids, so ``GET /admin/trace/<id>``
        # can answer "this id existed but fell off the ring" (HTTP 410)
        # instead of an indistinguishable 404.
        self._evictions = 0
        self._tombstones: "collections.deque[str]" = collections.deque(
            maxlen=max(64, self.capacity // 8))
        self._tombstone_set: set = set()

    # -- recording ------------------------------------------------------
    def _evict_overflow_locked(self) -> None:
        while len(self._spans) > self.capacity:
            old_rid, _old = self._spans.popitem(last=False)
            self._finished.discard(old_rid)
            self._evictions += 1
            if len(self._tombstones) == self._tombstones.maxlen:
                dead = self._tombstones.popleft()
                self._tombstone_set.discard(dead)
            self._tombstones.append(old_rid)
            self._tombstone_set.add(old_rid)

    def _span_locked(self, rid: str) -> Dict[str, Any]:
        span = self._spans.get(rid)
        if span is None:
            span = {"request_id": rid, "attrs": {}, "events": []}
            self._spans[rid] = span
            # A tombstoned rid coming back to life is live again, not
            # evicted (e.g. a worker requeue landing after an eviction).
            self._revive_tombstone_locked(rid)
            self._evict_overflow_locked()
        return span

    def _revive_tombstone_locked(self, rid: str) -> None:
        """Clear a tombstone for an rid that is live again — from BOTH
        structures: a stale deque copy left behind would, on its
        eventual popleft, discard the set entry backing a NEWER
        tombstone of the same rid."""
        if rid in self._tombstone_set:
            self._tombstone_set.discard(rid)
            try:
                self._tombstones.remove(rid)
            except ValueError:
                pass

    def annotate(self, rid: str, **attrs: Any) -> None:
        with self._lock:
            self._span_locked(rid)["attrs"].update(attrs)

    def record(self, rid: str, stage: str, plane: str = "service",
               t_mono: Optional[float] = None,
               t_wall: Optional[float] = None, **attrs: Any) -> None:
        """Record one stage event. Idempotent per (stage, plane): retry
        paths (redispatch, on_close backstops) may reach the same stage
        twice, and the FIRST occurrence is the truthful timestamp."""
        now = time.monotonic()
        if t_mono is None:
            t_mono = now
        if t_wall is None:
            # A stamp taken earlier than it is recorded keeps its place
            # in the wall-clock order too.
            t_wall = time.time() - (now - t_mono)
        event = {"stage": stage, "plane": plane, "t_mono": t_mono,
                 "t_wall": t_wall}
        event.update(attrs)
        with profiler.section("span.write"):
            with self._lock:
                span = self._span_locked(rid)
                if any(e["stage"] == stage and e["plane"] == plane
                       for e in span["events"]):
                    return
                span["events"].append(event)
                if stage == "finished" or rid in self._drained:
                    self._finished.add(rid)

    def merge_remote(self, rid: str, plane: str,
                     events: List[Dict[str, Any]],
                     source: str = "",
                     attrs: Optional[Dict[str, Any]] = None) -> None:
        """Fold another plane's exported events into this store (the
        heartbeat merge path). Remote monotonic stamps are meaningful
        only relative to each other; the wall stamps order them against
        local stages. Remote attrs land under ``attrs[<plane>]`` so the
        worker's view (e.g. the correlation header it actually read)
        never clobbers local keys."""
        with self._lock:
            span = self._span_locked(rid)
            if attrs:
                span["attrs"].setdefault(plane, {}).update(attrs)
            for e in events:
                ev = dict(e)
                ev["plane"] = plane
                if source:
                    ev.setdefault("source", source)
                if any(x["stage"] == ev.get("stage")
                       and x["plane"] == plane
                       and x.get("source") == ev.get("source")
                       for x in span["events"]):
                    continue
                span["events"].append(ev)

    # -- querying -------------------------------------------------------
    def get(self, rid: str) -> Optional[Dict[str, Any]]:
        """A deep-enough copy of one span, events sorted by wall clock
        (cross-plane safe; stable for same-stamp events)."""
        with self._lock:
            span = self._spans.get(rid)
            if span is None:
                return None
            events = [_deep_copy(e) for e in span["events"]]
            attrs = _deep_copy(span["attrs"])
        events.sort(key=lambda e: e.get("t_wall", 0.0))
        return {"request_id": rid, "attrs": attrs, "events": events}

    def interval_ms(self, rid: str, a: str, b: str,
                    plane: str = "service") -> Optional[float]:
        """Monotonic-clock interval between two stages recorded by the
        SAME plane (None when either is missing)."""
        with self._lock:
            span = self._spans.get(rid)
            if span is None:
                return None
            ts = {e["stage"]: e["t_mono"] for e in span["events"]
                  if e["plane"] == plane and "t_mono" in e}
        if a not in ts or b not in ts:
            return None
        return 1000.0 * (ts[b] - ts[a])

    def eviction_count(self) -> int:
        """Spans dropped by ring overflow since construction (the
        ``xllm_span_evictions_total`` scrape-time mirror source)."""
        with self._lock:
            return self._evictions

    def was_evicted(self, rid: str) -> bool:
        """True when ``rid`` once held a span that the ring evicted (and
        it has not been re-created since). Bounded memory: only the most
        recent evictions are remembered — beyond the tombstone ring an
        evicted id degrades back to an honest 404."""
        with self._lock:
            return rid in self._tombstone_set and rid not in self._spans

    def tail(self, n: int, finished_only: bool = False
             ) -> List[Dict[str, Any]]:
        """Deep-enough copies of the newest ``n`` spans (insertion
        order), optionally only those that reached ``finished`` on some
        plane — the debug bundle's recent-request evidence. Copies are
        taken UNDER the lock (like ``get``): live spans mutate
        concurrently, and the incident-debug path must not 500 on a
        dict-changed-during-iteration race."""
        out: List[Dict[str, Any]] = []
        with self._lock:
            for span in reversed(self._spans.values()):
                if finished_only and not any(
                        e.get("stage") == "finished"
                        for e in span["events"]):
                    continue
                out.append({"request_id": span["request_id"],
                            "attrs": _deep_copy(span["attrs"]),
                            "events": [_deep_copy(e)
                                       for e in span["events"]]})
                if len(out) >= n:
                    break
        out.reverse()
        return out

    def __len__(self) -> int:
        with self._lock:
            return len(self._spans)

    # -- worker-side export (heartbeat path) ----------------------------
    def drain_finished(self) -> List[Dict[str, Any]]:
        """Pop every span that reached ``finished`` since the last
        drain, removing them from the ring (the exporter owns them now).
        On a failed ship, hand the batch back via ``requeue``."""
        out: List[Dict[str, Any]] = []
        with self._lock:
            rids, self._finished = sorted(self._finished), set()
            for rid in rids:
                span = self._spans.pop(rid, None)
                if span is not None:
                    self._drained[rid] = None
                    self._drained.move_to_end(rid)
                    out.append({"request_id": rid,
                                "attrs": _deep_copy(span["attrs"]),
                                "events": [_deep_copy(e)
                                           for e in span["events"]]})
            while len(self._drained) > 256:
                self._drained.popitem(last=False)
        return out

    def requeue(self, drained: List[Dict[str, Any]]) -> None:
        """Return an undeliverable drained batch so the next heartbeat
        retries it (ring bounds still apply)."""
        with self._lock:
            for rec in drained:
                rid = rec["request_id"]
                if rid in self._spans:
                    continue
                self._spans[rid] = {
                    "request_id": rid,
                    "attrs": _deep_copy(rec.get("attrs", {})),
                    "events": [_deep_copy(e)
                               for e in rec.get("events", [])]}
                self._revive_tombstone_locked(rid)
                self._finished.add(rid)
                self._evict_overflow_locked()
