"""Metrics core: Counter / Gauge / Histogram families with label sets.

The single place this repo turns numbers into Prometheus text exposition.
Every ``/metrics`` line on both planes renders through a ``Registry``
(enforced by the ``metrics-registry`` xlint rule: hand-rolled
``name{...} value`` f-strings outside ``xllm_service_tpu/obs/`` are
findings), so series names, label escaping, and histogram consistency
(``_bucket`` cumulative/monotone, ``_count`` == the ``+Inf`` bucket,
``_sum`` present) are structural properties instead of per-call-site
conventions. Dependency-free (stdlib only) and thread-safe: one lock per
registry, rank ``obs.registry`` in the utils/locks.py table — registry
methods never call out, so it nests safely under every serving-path
lock.

Two kinds of write path coexist deliberately:

- live instrumentation (``Counter.inc`` / ``Histogram.observe``) for
  values that are events — request counts, latency samples;
- scrape-time mirroring (``Counter.set_total`` / ``Gauge.set``) for
  totals another subsystem already owns (engine phase ledgers, the
  keep-alive pool counters, per-instance load) — the ``/metrics``
  handler refreshes them from the live objects, then renders, so the
  registry never caches stale copies of state it doesn't own.

Deployment note: one serving process hosts one plane, so a plane's
registry is process-global there. The test harness co-locates several
masters/workers in one process; each plane instance therefore OWNS its
registry (``Worker.obs`` / ``HttpService.obs``) to keep attribution
per-instance, and ``default_registry()`` serves single-plane callers.
"""

from __future__ import annotations

import math
from typing import Any, Dict, List, Optional, Sequence, Tuple

from xllm_service_tpu.utils.locks import make_lock

# Log-spaced latency buckets (milliseconds): 1-2-5 per decade from 1 ms
# to 2 minutes. Wide enough for a TTFT that paid a lazy compile (tens of
# seconds; minutes land in +Inf, which is itself a signal) and fine
# enough that
# p50/p90/p99 interpolation stays meaningful at CPU-test speeds.
DEFAULT_LATENCY_BUCKETS_MS: Tuple[float, ...] = (
    1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0, 200.0, 500.0,
    1000.0, 2000.0, 5000.0, 10000.0, 20000.0, 50000.0, 120000.0)

_NAME_OK = "abcdefghijklmnopqrstuvwxyz" \
           "ABCDEFGHIJKLMNOPQRSTUVWXYZ0123456789_:"


def _fmt(v: float) -> str:
    """Prometheus sample value: integers without a trailing .0 (existing
    series like ``xllm_service_instances 1`` are grepped as substrings by
    tests and ops scripts), shortest-repr floats otherwise. NaN renders
    as ``NaN`` (valid exposition) — one NaN sample (e.g. a heartbeat
    shipping a NaN load value through JSON) must poison its own series,
    not 500 every future /metrics render via ``int(nan)``."""
    f = float(v)
    if math.isnan(f):
        return "NaN"
    if math.isinf(f):
        return "+Inf" if f > 0 else "-Inf"
    if f == int(f) and abs(f) < 1e15:
        return str(int(f))
    return repr(f)


def _escape_label(v: Any) -> str:
    return str(v).replace("\\", "\\\\").replace('"', '\\"') \
        .replace("\n", "\\n")


class _Family:
    """One metric family: a name, a fixed labelname tuple, and a value
    per label set. Subclasses define the value semantics."""

    kind = "untyped"

    def __init__(self, registry: "Registry", name: str, help: str = "",
                 labelnames: Sequence[str] = ()) -> None:
        if not name or any(c not in _NAME_OK for c in name) \
                or name[0].isdigit():
            raise ValueError(f"bad metric name {name!r}")
        self._registry = registry
        self._lock = registry._lock
        self.name = name
        self.help = help
        self.labelnames = tuple(labelnames)
        self._series: Dict[Tuple[str, ...], Any] = {}

    def _key(self, labels: Dict[str, Any]) -> Tuple[str, ...]:
        if set(labels) != set(self.labelnames):
            raise ValueError(
                f"{self.name}: labels {sorted(labels)} != declared "
                f"{sorted(self.labelnames)}")
        return tuple(str(labels[k]) for k in self.labelnames)

    def _label_str(self, key: Tuple[str, ...],
                   extra: Tuple[Tuple[str, str], ...] = ()) -> str:
        pairs = list(zip(self.labelnames, key)) + list(extra)
        if not pairs:
            return ""
        inner = ",".join(f'{k}="{_escape_label(v)}"' for k, v in pairs)
        return "{" + inner + "}"

    def clear(self) -> None:
        """Drop every label set (scrape-time rebuilders: per-instance
        gauges whose members come and go with the cluster)."""
        with self._lock:
            self._series.clear()

    def remove(self, **labels: Any) -> None:
        with self._lock:
            self._series.pop(self._key(labels), None)

    def render(self, out: List[str]) -> None:
        raise NotImplementedError


class Counter(_Family):
    kind = "counter"

    def inc(self, amount: float = 1.0, **labels: Any) -> None:
        if amount < 0:
            raise ValueError(f"{self.name}: counter increment {amount} < 0")
        key = self._key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def set_total(self, total: float, **labels: Any) -> None:
        """Scrape-time mirror of a monotonic total another object owns
        (engine phase ledger, keep-alive pool counters). The caller is
        responsible for monotonicity — this is a refresh, not an event."""
        key = self._key(labels)
        with self._lock:
            self._series[key] = float(total)

    def value(self, **labels: Any) -> float:
        with self._lock:
            return float(self._series.get(self._key(labels), 0.0))

    def render(self, out: List[str]) -> None:
        with self._lock:
            items = sorted(self._series.items())
        for key, v in items:
            out.append(f"{self.name}{self._label_str(key)} {_fmt(v)}")


class Gauge(_Family):
    kind = "gauge"

    def set(self, value: float, **labels: Any) -> None:
        key = self._key(labels)
        with self._lock:
            self._series[key] = float(value)

    def inc(self, amount: float = 1.0, **labels: Any) -> None:
        key = self._key(labels)
        with self._lock:
            self._series[key] = self._series.get(key, 0.0) + amount

    def dec(self, amount: float = 1.0, **labels: Any) -> None:
        self.inc(-amount, **labels)

    def value(self, **labels: Any) -> float:
        with self._lock:
            return float(self._series.get(self._key(labels), 0.0))

    def render(self, out: List[str]) -> None:
        with self._lock:
            items = sorted(self._series.items())
        for key, v in items:
            out.append(f"{self.name}{self._label_str(key)} {_fmt(v)}")


class _HistogramSeries:
    __slots__ = ("counts", "total", "sum")

    def __init__(self, n_buckets: int) -> None:
        self.counts = [0] * n_buckets   # per-bucket (non-cumulative)
        self.total = 0
        self.sum = 0.0


class Histogram(_Family):
    kind = "histogram"

    def __init__(self, registry: "Registry", name: str, help: str = "",
                 labelnames: Sequence[str] = (),
                 buckets: Optional[Sequence[float]] = None) -> None:
        super().__init__(registry, name, help, labelnames)
        if "le" in self.labelnames:
            raise ValueError(f"{name}: 'le' is reserved for buckets")
        bs = tuple(float(b) for b in
                   (buckets or DEFAULT_LATENCY_BUCKETS_MS))
        if list(bs) != sorted(bs) or len(set(bs)) != len(bs):
            raise ValueError(f"{name}: buckets must strictly increase")
        self.buckets = bs

    def observe(self, value: float, **labels: Any) -> None:
        key = self._key(labels)
        v = float(value)
        with self._lock:
            s = self._series.get(key)
            if s is None:
                s = self._series[key] = _HistogramSeries(len(self.buckets))
            for i, edge in enumerate(self.buckets):
                if v <= edge:
                    s.counts[i] += 1
                    break
            s.total += 1
            s.sum += v

    def count(self, **labels: Any) -> int:
        with self._lock:
            s = self._series.get(self._key(labels))
            return s.total if s is not None else 0

    def set_counts(self, counts: Sequence[int], sum_value: float,
                   total: Optional[int] = None, **labels: Any) -> None:
        """Scrape-time mirror of a full bucket distribution another
        object owns (the profiler's section books, the lock-contention
        wait books) — the histogram analogue of ``Counter.set_total``.
        ``counts`` are per-bucket (non-cumulative) and must match this
        family's bucket count; ``total`` covers overflow samples past
        the last finite edge (defaults to ``sum(counts)``); the caller
        owns monotonicity."""
        if len(counts) != len(self.buckets):
            raise ValueError(
                f"{self.name}: set_counts got {len(counts)} buckets, "
                f"family has {len(self.buckets)}")
        key = self._key(labels)
        n_total = int(sum(counts)) if total is None else int(total)
        with self._lock:
            s = self._series.get(key)
            if s is None:
                s = self._series[key] = _HistogramSeries(len(self.buckets))
            s.counts = [int(c) for c in counts]
            s.total = n_total
            s.sum = float(sum_value)

    def cumulative(self, **labels: Any
                   ) -> Optional[List[Tuple[float, float]]]:
        """Snapshot of one label set's cumulative bucket counts as
        ``[(le, cum), ...]`` ending with the ``+Inf`` bucket — the exact
        shape ``expfmt``'s bucket arithmetic consumes, so the SLO
        engine's window deltas and a scraped dashboard read the SAME
        numbers. None when the series has never been observed."""
        with self._lock:
            s = self._series.get(self._key(labels))
            if s is None:
                return None
            counts = list(s.counts)
            total = s.total
        bs: List[Tuple[float, float]] = []
        cum = 0
        for edge, c in zip(self.buckets, counts):
            cum += c
            bs.append((edge, float(cum)))
        bs.append((math.inf, float(total)))
        return bs

    def quantile(self, q: float, **labels: Any) -> Optional[float]:
        """Estimated q-quantile of one label set — the same
        ``le``-bucket interpolation the scrape side runs
        (``expfmt.quantile_from_buckets``: one copy of the arithmetic,
        so in-memory and scraped quantiles cannot drift). None with no
        observations; samples past the last finite edge clamp to it."""
        if not 0.0 <= q <= 1.0:
            raise ValueError(f"quantile {q} outside [0, 1]")
        from xllm_service_tpu.obs.expfmt import quantile_from_buckets
        bs = self.cumulative(**labels)
        if bs is None or bs[-1][1] == 0:
            return None
        return quantile_from_buckets(bs, q)

    def render(self, out: List[str]) -> None:
        with self._lock:
            items = [(k, list(s.counts), s.total, s.sum)
                     for k, s in sorted(self._series.items())]
        for key, counts, total, ssum in items:
            cum = 0
            for edge, c in zip(self.buckets, counts):
                cum += c
                out.append(
                    f"{self.name}_bucket"
                    f"{self._label_str(key, (('le', _fmt(edge)),))} "
                    f"{cum}")
            out.append(
                f"{self.name}_bucket"
                f"{self._label_str(key, (('le', '+Inf'),))} {total}")
            out.append(f"{self.name}_sum{self._label_str(key)} "
                       f"{_fmt(ssum)}")
            out.append(f"{self.name}_count{self._label_str(key)} {total}")


class Registry:
    """A named, ordered set of metric families sharing one lock.

    Get-or-create accessors are idempotent (same name → same family) and
    raise on a kind or labelname conflict, so two call sites can't
    silently fork one series into incompatible shapes."""

    def __init__(self) -> None:
        self._lock = make_lock("obs.registry", 93)
        self._families: Dict[str, _Family] = {}

    def _get_or_create(self, cls, name: str, help: str,
                       labelnames: Sequence[str], **kwargs) -> _Family:
        with self._lock:
            fam = self._families.get(name)
        if fam is not None:
            if not isinstance(fam, cls) or \
                    fam.labelnames != tuple(labelnames):
                raise ValueError(
                    f"metric {name!r} re-declared as {cls.kind} "
                    f"labels={tuple(labelnames)} (was {fam.kind} "
                    f"labels={fam.labelnames})")
            return fam
        fam = cls(self, name, help, labelnames, **kwargs)
        with self._lock:
            return self._families.setdefault(name, fam)

    def counter(self, name: str, help: str = "",
                labelnames: Sequence[str] = ()) -> Counter:
        return self._get_or_create(Counter, name, help, labelnames)

    def gauge(self, name: str, help: str = "",
              labelnames: Sequence[str] = ()) -> Gauge:
        return self._get_or_create(Gauge, name, help, labelnames)

    def histogram(self, name: str, help: str = "",
                  labelnames: Sequence[str] = (),
                  buckets: Optional[Sequence[float]] = None) -> Histogram:
        fam = self._get_or_create(Histogram, name, help, labelnames,
                                  buckets=buckets)
        if buckets is not None and fam.buckets != tuple(
                float(b) for b in buckets):
            # The kind/labelname checks already refuse silent series
            # forks; differing bucket edges are the same class of bug.
            raise ValueError(
                f"histogram {name!r} re-declared with buckets "
                f"{tuple(buckets)} (was {fam.buckets})")
        return fam

    def render(self) -> str:
        """Prometheus text exposition (version 0.0.4): ``# HELP`` /
        ``# TYPE`` headers per family, then its samples."""
        with self._lock:
            fams = list(self._families.values())
        out: List[str] = []
        for fam in fams:
            if fam.help:
                out.append(f"# HELP {fam.name} {fam.help}")
            out.append(f"# TYPE {fam.name} {fam.kind}")
            fam.render(out)
        return "\n".join(out) + "\n"


_DEFAULT: Optional[Registry] = None


def default_registry() -> Registry:
    """The process-default registry for single-plane processes (ad-hoc
    tools). Plane objects own their registries — see module
    docstring."""
    global _DEFAULT
    if _DEFAULT is None:
        _DEFAULT = Registry()
    return _DEFAULT
