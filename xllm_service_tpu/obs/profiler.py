"""Service-plane self-profiling: the master watching itself.

Every scale item left in ROADMAP.md lands on a single Python master
whose own cost had never been measured — the relay parses every SSE
frame, ``schedule()`` runs a prefix-walk plus multi-policy audit per
request, and spans/events/metrics all take locks on the hot path. This
module is the always-on accounting layer that makes that cost a metric
instead of a guess:

- **Hot-path sections**: a CLOSED catalog (``SECTIONS``) of named timed
  regions recorded via ``with profiler.section("schedule"):`` into
  per-thread books (no shared lock on the record path) and mirrored at
  scrape time into ``xllm_service_hotpath_ms{section}`` histograms plus
  ``xllm_service_hotpath_ops_total{section}`` counters. The catalog is
  machine-checked: xlint rule ``hotpath-section-catalog`` pins every
  ``section("<name>")`` literal in the tree to this tuple, exactly like
  the event-type and failpoint catalogs.
- **Lock contention**: ``utils/locks.py`` samples 1-in-N acquisitions
  (``XLLM_LOCK_PROFILE_SAMPLE``) into its own book; ``flush_metrics``
  mirrors it here as ``xllm_lock_wait_ms{lock,rank}`` /
  ``xllm_lock_contended_total{lock}`` (locks.py never imports obs).
- **Per-thread-root CPU and run-queue wait**: every thread of the
  process has a root. Supervised threads register their native tid
  under their root name (utils/threads.py calls
  ``register_thread_root``: a worker's ``worker.engine_loop``, its
  ``worker.stream_writer``, which writes every stream the native front
  door serves, ``worker.hb_loop``, ``worker.encode_loop``), an HTTP
  handler's thread registers as ``httpd.handler`` once a connection
  (service/httpd.py; under the native front door it admits a streamed
  request and then parks until the writer ends the stream), the main
  thread is ``main``, and whatever no root claims (the runtime's native
  threads) is ``unregistered``. Scrape-time reads of
  ``/proc/self/task/<tid>/schedstat`` (nanoseconds on a core, and
  nanoseconds runnable and waiting for one; ``stat``'s ticks where the
  kernel keeps no schedstats, ``xllm_thread_clock{source}`` says which)
  become ``xllm_thread_cpu_seconds_total{root}`` and
  ``xllm_thread_runq_wait_seconds_total{root}``.
  ``time.thread_time_ns`` only measures the *calling* thread, so /proc
  is the only way to account someone else's CPU.
- **Self-gauges**: RSS, process CPU% (delta between scrapes), live
  thread count, and GC pauses via ``gc.callbacks`` →
  ``xllm_gc_pause_ms`` + ``xllm_gc_collections_total{generation}``.
- **Stack sampler**: ``sample_stacks(seconds)`` drives
  ``sys._current_frames`` at a fixed rate and returns collapsed-stack /
  top-function tables — served by ``GET /admin/profile?seconds=N`` and
  embedded in ``/admin/debug_bundle``.

``XLLM_HOTPATH_PROFILE`` (default ON, read at import per the hot-path
flag discipline) gates the section timers; everything else is
scrape-time-only cost. With the flag off, ``section()`` returns one
shared no-op context manager — the disabled path is a dict lookup and
an attribute load, nothing else.

State is process-global on purpose: one serving process hosts one
plane, and the co-located test harness tolerates shared books because
every series is labelled. Books only grow (a dead thread's totals are
retained), keeping the mirrored counters monotonic.
"""

from __future__ import annotations

import gc
import os
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Tuple

from xllm_service_tpu.utils import locks as _locks

# ---------------------------------------------------------------------------
# The closed section catalog. xlint rule `hotpath-section-catalog` pins
# every profiler.section("<name>") literal in the tree to this tuple —
# add the name HERE first, with a comment saying what the section spans.
# ---------------------------------------------------------------------------
SECTIONS: Tuple[str, ...] = (
    "schedule",       # Scheduler.schedule(): policy walk + audit + plan
    "relay.frame",    # per-SSE-frame ledger work in _recoverable_relay
    "span.write",     # SpanStore.record(): one stage write
    "event.emit",     # EventLog.emit(): one cluster event
    "store.call",     # one coordination-store RPC from the master loop
    "sse.assemble",   # building one outbound SSE frame from a delta
    "tokenize",       # chat-template apply + tokenizer encode
)

_SECTION_SET = frozenset(SECTIONS)

# Section bucket edges (ms): hot-path units of work are typically
# 10 µs – 10 ms on the master; the default latency buckets would fold
# everything into their first bucket.
HOTPATH_BUCKETS_MS: Tuple[float, ...] = (
    0.005, 0.01, 0.02, 0.05, 0.1, 0.2, 0.5, 1.0, 2.0, 5.0,
    10.0, 20.0, 50.0, 100.0, 500.0, 2000.0)

GC_PAUSE_BUCKETS_MS: Tuple[float, ...] = (
    0.1, 0.2, 0.5, 1.0, 2.0, 5.0, 10.0, 20.0, 50.0, 100.0,
    200.0, 500.0, 1000.0)


def _enabled_from_env() -> bool:
    return os.environ.get("XLLM_HOTPATH_PROFILE", "1").strip() not in (
        "0", "false", "no")


ENABLED = _enabled_from_env()


def _events_enabled_from_env() -> bool:
    # The timed-event tail (per-section slices for the merged timeline,
    # obs/timeline.py) rides the step-trace flag: XLLM_STEPTRACE=0
    # turns the per-exit wall-clock read + tail append off together
    # with the worker's step recorder. Read once at import, like every
    # hot-path flag.
    return ENABLED and os.environ.get(
        "XLLM_STEPTRACE", "1").strip() not in ("0", "false", "no")


EVENTS_ENABLED = _events_enabled_from_env()

# Per-thread bounded tail of timed section events (newest EVENT_TAIL
# per thread) — the raw material for the timeline's hotpath tracks.
EVENT_TAIL = 256

try:
    _CLK_TCK = float(os.sysconf("SC_CLK_TCK"))
except (AttributeError, ValueError, OSError):
    _CLK_TCK = 100.0

try:
    _PAGE_SIZE = float(os.sysconf("SC_PAGE_SIZE"))
except (AttributeError, ValueError, OSError):
    _PAGE_SIZE = 4096.0


# ---------------------------------------------------------------------------
# Section books: one dict per thread, registered once in a global list.
# The record path touches only thread-local state — no shared lock.
# ---------------------------------------------------------------------------

class _Sect:
    __slots__ = ("counts", "sum_ms", "ops")

    def __init__(self) -> None:
        self.counts = [0] * len(HOTPATH_BUCKETS_MS)
        self.sum_ms = 0.0
        self.ops = 0


_tls = threading.local()
_all_books: List[Dict[str, _Sect]] = []
# Raw threading.Lock: guards the book list only, never calls out, and
# stays invisible to the rank checker (the profiler sits under locks.py
# in the import graph).
_books_lock = threading.Lock()


def _thread_book() -> Dict[str, _Sect]:
    book = getattr(_tls, "book", None)
    if book is None:
        book = _tls.book = {}
        with _books_lock:
            _all_books.append(book)
    return book


# (thread name, bounded deque of (section, t_wall_end, dur_ms)) — one
# tail per thread, registered like the books. Appends are thread-local;
# readers copy under _books_lock.
_all_event_tails: List[Tuple[str, Any]] = []


def _thread_events():
    tail = getattr(_tls, "events", None)
    if tail is None:
        import collections
        tail = _tls.events = collections.deque(maxlen=EVENT_TAIL)
        with _books_lock:
            _all_event_tails.append(
                (threading.current_thread().name, tail))
    return tail


class _NullSection:
    __slots__ = ()

    def __enter__(self) -> "_NullSection":
        return self

    def __exit__(self, *exc: object) -> bool:
        return False


_NULL = _NullSection()


class _Timer:
    __slots__ = ("name", "t0")

    def __init__(self, name: str) -> None:
        self.name = name
        self.t0 = 0.0

    def __enter__(self) -> "_Timer":
        self.t0 = time.perf_counter()
        return self

    def __exit__(self, *exc: object) -> bool:
        dt_ms = (time.perf_counter() - self.t0) * 1000.0
        book = _thread_book()
        s = book.get(self.name)
        if s is None:
            s = book[self.name] = _Sect()
        for i, edge in enumerate(HOTPATH_BUCKETS_MS):
            if dt_ms <= edge:
                s.counts[i] += 1
                break
        s.sum_ms += dt_ms
        s.ops += 1
        if EVENTS_ENABLED:
            _thread_events().append((self.name, time.time(), dt_ms))
        return False


def section(name: str):
    """Context manager timing one hot-path section. ``name`` MUST be a
    member of the closed ``SECTIONS`` catalog (enforced here at runtime
    and by xlint rule ``hotpath-section-catalog`` statically)."""
    if name not in _SECTION_SET:
        raise ValueError(
            f"unknown hot-path section {name!r} — add it to "
            f"profiler.SECTIONS first (closed catalog)")
    if not ENABLED:
        return _NULL
    return _Timer(name)


def section_snapshot() -> Dict[str, Dict[str, Any]]:
    """Merged per-section totals across every thread book:
    ``{name: {ops, sum_ms, counts}}`` (counts align with
    HOTPATH_BUCKETS_MS; overflow samples count in ops only)."""
    with _books_lock:
        books = list(_all_books)
    merged: Dict[str, Dict[str, Any]] = {}
    for book in books:
        for name, s in list(book.items()):
            m = merged.get(name)
            if m is None:
                m = merged[name] = {
                    "ops": 0, "sum_ms": 0.0,
                    "counts": [0] * len(HOTPATH_BUCKETS_MS)}
            m["ops"] += s.ops
            m["sum_ms"] += s.sum_ms
            for i, c in enumerate(s.counts):
                m["counts"][i] += c
    return merged


def reset_sections() -> None:
    """Test helper: forget every thread book (process-global state)."""
    with _books_lock:
        _all_books.clear()
        _all_event_tails.clear()
    _tls.book = None
    _tls.events = None


def recent_events(window_s: float = 0.0,
                  limit: int = 2048) -> List[Dict[str, Any]]:
    """Merged copy of every thread's timed-event tail, oldest-first:
    ``[{name, t_wall, dur_ms, thread}]`` — the timeline's hotpath
    tracks. ``window_s`` clips to the newest event minus the window."""
    with _books_lock:
        tails = [(tname, list(tail))
                 for tname, tail in _all_event_tails]
    out: List[Dict[str, Any]] = []
    for tname, tail in tails:
        for name, t_wall, dur_ms in tail:
            out.append({"name": name, "t_wall": t_wall,
                        "dur_ms": dur_ms, "thread": tname})
    out.sort(key=lambda e: (e["t_wall"], e["thread"], e["name"]))
    if window_s > 0 and out:
        horizon = out[-1]["t_wall"] - window_s
        out = [e for e in out if e["t_wall"] >= horizon]
    return out[-limit:]


# ---------------------------------------------------------------------------
# Per-thread-root accounting: CPU and run-queue wait of EVERY thread of
# the process (/proc/self/task/<tid>/schedstat, or stat's ticks)
# ---------------------------------------------------------------------------

# Where the threads' files are read. A test points it at a directory of
# fixture files (``<tid>/schedstat``, ``<tid>/stat``).
_TASK_DIR = "/proc/self/task"
# Every tid no root claims: the runtime's native threads (PJRT, XLA's
# pools, the device profiler's) and any bare Python thread.
UNREGISTERED = "unregistered"
MAIN_ROOT = "main"
# Every thread that runs a request's handler, under either front door
# (service/httpd.py, service/native_httpd.py).
HANDLER_ROOT = "httpd.handler"

_roots_lock = threading.Lock()
_tid_root: Dict[int, str] = {}        # native tid -> the root it claims
_fresh_tids: set = set()              # claimed since the last snapshot
# tid -> (cpu s, run-queue-wait s) as last read; a root's totals only
# ever gain the difference to it, so an exited thread's seconds stay and
# every series is monotonic whatever comes and goes.
_tid_last: Dict[int, Tuple[float, float]] = {}
_root_total: Dict[str, List[float]] = {}   # root -> [cpu s, runq s]
_clock_source: Optional[str] = None   # "schedstat" | "stat", probed once


def register_thread_root(root: str) -> None:
    """Bind the calling thread's native tid to ``root``, so that
    scrape-time /proc reads attribute its time there: the
    supervised-thread wrapper (utils/threads.py) at thread start, an
    HTTP handler's thread once a connection (service/httpd.py)."""
    try:
        tid = threading.get_native_id()
    except Exception:  # noqa: BLE001 — attribution is best-effort: on a
        return         # platform with no native tids the root simply
                       # reports no series, never fails to start
    with _roots_lock:
        _tid_root[tid] = root
        _fresh_tids.add(tid)
        _root_total.setdefault(root, [0.0, 0.0])


def retire_thread_root() -> None:
    """The calling thread is about to exit: book what it has run since
    the last scrape and let its tid go. A thread a connection lives
    between two scrapes more often than not, and no later read finds
    it."""
    try:
        tid = threading.get_native_id()
    except Exception:  # noqa: BLE001 — as in register_thread_root
        return
    with _roots_lock:
        root = _tid_root.pop(tid, None)
        if root is not None:
            _book(tid, root, _read_tid_clock(tid, _source()))
            _fresh_tids.discard(tid)
            _tid_last.pop(tid, None)


def _read_fields(tid: int, name: str) -> Optional[List[bytes]]:
    # os.open/os.read and not open(): three system calls a file where
    # the buffered reader makes a dozen, 49 us a thread against 223 on
    # a sandboxed kernel (PERF.md, PR 55), times a few hundred threads.
    try:
        fd = os.open(f"{_TASK_DIR}/{tid}/{name}", os.O_RDONLY)
    except OSError:
        return None
    try:
        data = os.read(fd, 1024)
    except OSError:
        return None
    finally:
        os.close(fd)
    # stat: comm may contain spaces/parens — fields resume after the
    # LAST ')'. schedstat has no ')' and is split whole.
    return data.rsplit(b")", 1)[-1].split()


def _read_tid_clock(tid: int, source: str
                    ) -> Optional[Tuple[float, float]]:
    """(seconds on a core, seconds runnable and waiting for one) of one
    thread, or None once it has exited. ``schedstat`` counts both in
    nanoseconds; ``stat`` counts the first in ticks of 10 ms and knows
    nothing of the second (0)."""
    try:
        if source == "schedstat":
            f = _read_fields(tid, "schedstat")
            return None if f is None else (int(f[0]) / 1e9,
                                           int(f[1]) / 1e9)
        f = _read_fields(tid, "stat")
        return None if f is None else (
            (int(f[11]) + int(f[12])) / _CLK_TCK, 0.0)
    except (IndexError, ValueError):
        return None


def _source() -> str:
    """Which file the clocks come from, settled once a process on the
    main thread's own (it has run by now: zeros mean the kernel keeps no
    schedstats), so that no series ever mixes nanoseconds with ticks."""
    global _clock_source
    if _clock_source is None:
        probe = _read_tid_clock(os.getpid(), "schedstat")
        _clock_source = "schedstat" if probe and probe[0] > 0 else "stat"
    return _clock_source


def _book(tid: int, root: str, cur: Optional[Tuple[float, float]]) -> None:
    """Add what ``tid`` ran since it was last read to ``root``'s totals
    (roots lock held)."""
    if cur is None:
        return
    last = _tid_last.get(tid, (0.0, 0.0))
    if cur[0] < last[0]:            # the tid was handed out again
        last = (0.0, 0.0)
    tot = _root_total.setdefault(root, [0.0, 0.0])
    tot[0] += cur[0] - last[0]
    tot[1] += max(0.0, cur[1] - last[1])
    _tid_last[tid] = cur


def thread_clock_snapshot() -> Dict[str, Tuple[float, float]]:
    """Cumulative (CPU seconds, run-queue-wait seconds) per root, over
    every thread the process has: one small file a live thread, read
    now. The main thread is ``main``; a tid no root claims goes to
    ``unregistered``."""
    with _roots_lock:
        source = _source()
        try:
            listed = {int(n) for n in os.listdir(_TASK_DIR) if n.isdigit()}
        except OSError:
            listed = set()
        for tid in listed:
            cur = _read_tid_clock(tid, source)
            if cur is not None and tid not in _fresh_tids \
                    and cur[0] < _tid_last.get(tid, cur)[0]:
                # The kernel handed the number to another thread, which
                # did not claim it: the claim is the dead one's.
                _tid_root.pop(tid, None)
            _book(tid, _tid_root.get(tid) or (
                MAIN_ROOT if tid == os.getpid() else UNREGISTERED), cur)
        for book in (_tid_root, _tid_last):     # exited: already booked
            for tid in set(book) - listed:
                del book[tid]
        _fresh_tids.clear()
        return {root: (t[0], t[1]) for root, t in _root_total.items()}


def thread_cpu_snapshot() -> Dict[str, float]:
    """Cumulative CPU seconds per root (``thread_clock_snapshot``'s
    first column)."""
    return {root: t[0] for root, t in thread_clock_snapshot().items()}


# ---------------------------------------------------------------------------
# Process self stats (/proc/self) + GC pause hook
# ---------------------------------------------------------------------------

def _proc_self_cpu_s() -> Optional[float]:
    try:
        with open("/proc/self/stat", "rb") as f:
            data = f.read()
    except OSError:
        return None
    rest = data.rsplit(b")", 1)[-1].split()
    try:
        return (int(rest[11]) + int(rest[12])) / _CLK_TCK
    except (IndexError, ValueError):
        return None


def process_rss_bytes() -> Optional[float]:
    try:
        with open("/proc/self/statm", "rb") as f:
            fields = f.read().split()
        return float(int(fields[1])) * _PAGE_SIZE
    except (OSError, IndexError, ValueError):
        return None


_cpu_lock = threading.Lock()
_cpu_last: Optional[Tuple[float, float]] = None  # (wall_s, cpu_s)


def process_cpu_percent() -> Optional[float]:
    """CPU% of this process over the window since the previous call
    (scrape-to-scrape delta). None on the first call or off-Linux."""
    global _cpu_last
    cpu = _proc_self_cpu_s()
    if cpu is None:
        return None
    now = time.monotonic()
    with _cpu_lock:
        last = _cpu_last
        _cpu_last = (now, cpu)
    if last is None or now <= last[0]:
        return None
    return 100.0 * (cpu - last[1]) / (now - last[0])


_gc_lock = threading.Lock()
_gc_t0: Optional[float] = None
_gc_pause_counts = [0] * len(GC_PAUSE_BUCKETS_MS)
_gc_pause_sum_ms = 0.0
_gc_pause_total = 0
_gc_collections: Dict[int, int] = {}
_gc_hook_installed = False


def _gc_callback(phase: str, info: Dict[str, Any]) -> None:
    # CPython GC is stop-the-world and non-reentrant, so one module
    # slot for the start time is enough.
    global _gc_t0, _gc_pause_sum_ms, _gc_pause_total
    if phase == "start":
        _gc_t0 = time.perf_counter()
        return
    if phase != "stop" or _gc_t0 is None:
        return
    dt_ms = (time.perf_counter() - _gc_t0) * 1000.0
    _gc_t0 = None
    gen = int(info.get("generation", -1))
    with _gc_lock:
        for i, edge in enumerate(GC_PAUSE_BUCKETS_MS):
            if dt_ms <= edge:
                _gc_pause_counts[i] += 1
                break
        _gc_pause_sum_ms += dt_ms
        _gc_pause_total += 1
        _gc_collections[gen] = _gc_collections.get(gen, 0) + 1


def install_gc_hook() -> None:
    global _gc_hook_installed
    with _gc_lock:
        if _gc_hook_installed:
            return
        _gc_hook_installed = True
    gc.callbacks.append(_gc_callback)


def gc_snapshot() -> Dict[str, Any]:
    with _gc_lock:
        return {
            "pause_counts": list(_gc_pause_counts),
            "pause_sum_ms": _gc_pause_sum_ms,
            "pause_total": _gc_pause_total,
            "collections": dict(_gc_collections),
        }


if ENABLED:
    install_gc_hook()


# ---------------------------------------------------------------------------
# Scrape-time flush: mirror every book into a Registry
# ---------------------------------------------------------------------------

def flush_metrics(registry) -> None:
    """Refresh the profiler's families in ``registry`` from the live
    books — the scrape-time-mirror pattern (``Counter.set_total`` /
    ``Histogram.set_counts``), called from each plane's /metrics
    handler. The registry never caches stale copies of state the books
    own."""
    hot_h = registry.histogram(
        "xllm_service_hotpath_ms",
        "per-section hot-path time (profiler catalog)",
        labelnames=("section",), buckets=HOTPATH_BUCKETS_MS)
    hot_c = registry.counter(
        "xllm_service_hotpath_ops_total",
        "per-section hot-path operations", labelnames=("section",))
    for name, m in section_snapshot().items():
        hot_h.set_counts(m["counts"], m["sum_ms"], total=m["ops"],
                         section=name)
        hot_c.set_total(m["ops"], section=name)

    contention = _locks.contention_snapshot()
    if contention:
        wait_h = registry.histogram(
            "xllm_lock_wait_ms",
            "sampled lock acquisition wait time "
            "(XLLM_LOCK_PROFILE_SAMPLE)",
            labelnames=("lock", "rank"),
            buckets=_locks.LOCK_WAIT_BUCKETS_MS)
        cont_c = registry.counter(
            "xllm_lock_contended_total",
            "sampled acquisitions that had to block",
            labelnames=("lock",))
        samp_c = registry.counter(
            "xllm_lock_sampled_total",
            "acquisitions sampled by the contention profiler",
            labelnames=("lock",))
        for name, b in contention.items():
            wait_h.set_counts(b["wait_counts"], b["wait_sum_ms"],
                              total=b["sampled"], lock=name,
                              rank=b["rank"])
            cont_c.set_total(b["contended"], lock=name)
            samp_c.set_total(b["sampled"], lock=name)

    cpu_c = registry.counter(
        "xllm_thread_cpu_seconds_total",
        "cumulative CPU seconds per thread root: every thread of the "
        "process has one (supervised roots, worker.stream_writer "
        "among them, httpd.handler, main, and unregistered for the "
        "runtime's native threads)",
        labelnames=("root",))
    runq_c = registry.counter(
        "xllm_thread_runq_wait_seconds_total",
        "cumulative seconds a root's threads were runnable and waiting "
        "for a core (schedstat's second field; stays 0 under "
        "xllm_thread_clock{source=\"stat\"})",
        labelnames=("root",))
    for root, (cpu_s, runq_s) in thread_clock_snapshot().items():
        cpu_c.set_total(cpu_s, root=root)
        runq_c.set_total(runq_s, root=root)
    clock_g = registry.gauge(
        "xllm_thread_clock",
        "1 on the file the per-root thread clocks are read from: "
        "schedstat (nanoseconds, with the run-queue wait) or stat "
        "(ticks of 10 ms, no wait)",
        labelnames=("source",))
    for source in ("schedstat", "stat"):
        clock_g.set(1.0 if source == _source() else 0.0, source=source)

    rss = process_rss_bytes()
    if rss is not None:
        registry.gauge("xllm_process_rss_bytes",
                       "resident set size").set(rss)
    pct = process_cpu_percent()
    if pct is not None:
        registry.gauge(
            "xllm_process_cpu_percent",
            "process CPU percent over the previous scrape window"
        ).set(pct)
    registry.gauge("xllm_process_threads",
                   "live thread count").set(threading.active_count())

    g = gc_snapshot()
    registry.histogram(
        "xllm_gc_pause_ms", "GC stop-the-world pause time",
        buckets=GC_PAUSE_BUCKETS_MS).set_counts(
            g["pause_counts"], g["pause_sum_ms"],
            total=g["pause_total"])
    gc_c = registry.counter("xllm_gc_collections_total",
                            "GC runs per generation",
                            labelnames=("generation",))
    for gen, n in g["collections"].items():
        gc_c.set_total(n, generation=gen)


# ---------------------------------------------------------------------------
# Snapshot (for /admin/profile and the debug bundle) + stack sampler
# ---------------------------------------------------------------------------

def _quantiles_from_counts(counts: List[int], total: int,
                           edges: Tuple[float, ...],
                           qs: Tuple[float, ...] = (0.5, 0.99)
                           ) -> Dict[str, Optional[float]]:
    from xllm_service_tpu.obs.expfmt import quantile_from_buckets
    if total <= 0:
        return {f"p{int(q * 100)}": None for q in qs}
    bs: List[Tuple[float, float]] = []
    cum = 0
    for edge, c in zip(edges, counts):
        cum += c
        bs.append((edge, float(cum)))
    bs.append((float("inf"), float(total)))
    return {f"p{int(q * 100)}": quantile_from_buckets(bs, q)
            for q in qs}


def snapshot() -> Dict[str, Any]:
    """The live section/contention/self tables as one JSON-ready dict —
    what /admin/profile returns alongside the sampled stacks and what
    the debug bundle embeds."""
    sections: Dict[str, Any] = {}
    for name, m in sorted(section_snapshot().items()):
        row = {"ops": m["ops"], "sum_ms": round(m["sum_ms"], 3)}
        row.update({
            k: (round(v, 4) if v is not None else None)
            for k, v in _quantiles_from_counts(
                m["counts"], m["ops"], HOTPATH_BUCKETS_MS).items()})
        sections[name] = row
    lock_rows: Dict[str, Any] = {}
    for name, b in sorted(_locks.contention_snapshot().items()):
        row = {"rank": b["rank"], "sampled": b["sampled"],
               "contended": b["contended"],
               "wait_sum_ms": round(b["wait_sum_ms"], 3)}
        row.update({
            k: (round(v, 4) if v is not None else None)
            for k, v in _quantiles_from_counts(
                b["wait_counts"], b["sampled"],
                _locks.LOCK_WAIT_BUCKETS_MS).items()})
        lock_rows[name] = row
    g = gc_snapshot()
    clocks = sorted(thread_clock_snapshot().items())
    return {
        "enabled": ENABLED,
        "lock_profile_sample": _locks.PROFILE_SAMPLE,
        "sections": sections,
        "locks": lock_rows,
        "thread_clock": _source(),
        "thread_cpu_s": {r: round(c, 3) for r, (c, _) in clocks},
        "thread_runq_wait_s": {r: round(w, 3) for r, (_, w) in clocks},
        "self": {
            "rss_bytes": process_rss_bytes(),
            "threads": threading.active_count(),
            "gc_collections": {str(k): v for k, v in
                               sorted(g["collections"].items())},
            "gc_pause_total": g["pause_total"],
            "gc_pause_sum_ms": round(g["pause_sum_ms"], 3),
        },
    }


def sample_stacks(seconds: float = 2.0, hz: float = 50.0,
                  top: int = 30) -> Dict[str, Any]:
    """On-demand wall-clock stack sampler: polls
    ``sys._current_frames`` at ``hz`` for ``seconds``, aggregating
    collapsed stacks (root;...;leaf) and leaf functions. The sampling
    thread excludes itself. Cost is borne only while a sampling request
    is in flight — nothing runs between requests."""
    seconds = max(0.05, min(float(seconds), 60.0))
    hz = max(1.0, min(float(hz), 250.0))
    interval = 1.0 / hz
    me = threading.get_ident()
    stack_counts: Dict[str, int] = {}
    func_counts: Dict[str, int] = {}
    samples = 0
    threads_seen = 0
    deadline = time.monotonic() + seconds
    while True:
        for tid, frame in sys._current_frames().items():
            if tid == me:
                continue
            names: List[str] = []
            f = frame
            depth = 0
            while f is not None and depth < 64:
                code = f.f_code
                names.append(
                    f"{code.co_name} "
                    f"({os.path.basename(code.co_filename)}"
                    f":{f.f_lineno})")
                f = f.f_back
                depth += 1
            if not names:
                continue
            threads_seen += 1
            collapsed = ";".join(reversed(names))
            stack_counts[collapsed] = stack_counts.get(collapsed, 0) + 1
            leaf = names[0]
            func_counts[leaf] = func_counts.get(leaf, 0) + 1
        samples += 1
        if time.monotonic() >= deadline:
            break
        time.sleep(interval)
    def _top(d: Dict[str, int], key: str) -> List[Dict[str, Any]]:
        rows = sorted(d.items(), key=lambda kv: (-kv[1], kv[0]))[:top]
        return [{key: k, "count": v,
                 "share": round(v / max(1, threads_seen), 4)}
                for k, v in rows]
    return {
        "seconds": seconds,
        "hz": hz,
        "samples": samples,
        "thread_samples": threads_seen,
        "top_functions": _top(func_counts, "function"),
        "stacks": _top(stack_counts, "stack"),
    }
