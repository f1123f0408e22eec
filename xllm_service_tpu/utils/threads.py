"""Supervised thread runtime — silent thread death made impossible.

Before this module every long-lived activity in both planes ran on a
bare ``threading.Thread``: an uncaught exception anywhere in a
heartbeat loop, a store watch dispatcher, or a fan-in worker killed
that thread *silently* — no log line, no metric, no restart — and the
cluster degraded with nothing for the watchdog, the SLO engine, or a
post-mortem to look at (the exact failure class P/D-Serve's fleet
experience calls out: disaggregated serving lives on *observable*
failure handling). ``spawn()`` is the one sanctioned way to start a
thread in ``xllm_service_tpu``:

- a top-level handler that **logs** the traceback and **counts** the
  crash (``xllm_thread_crashes_total{root}``, mirrored into both
  planes' ``/metrics`` at scrape time) and optionally emits a
  ``thread_crashed`` cluster event;
- optional **bounded-backoff restart** for loops that must outlive any
  single failure (heartbeat, store watches): pass ``restart=`` a
  ``RetryPolicy`` (utils/retry.py — jittered, capped); restarts are
  unbounded, only the backoff is bounded, and a run that stayed up
  longer than the backoff cap resets the backoff ladder;
- a ``stop`` event wired through so shutdown interrupts the restart
  backoff instead of waiting it out.

The whole-program ``thread-root-crash`` xlint rule (rule 14,
tools/xlint/lifecycle.py) recognizes ``spawn`` sites as supervised
roots and statically rejects bare ``threading.Thread`` targets whose
bodies can let an exception escape — crash-handling is proven, not
assumed (docs/ROBUSTNESS.md "Crash-safety contract").

``record_callback_error`` is the sibling for *pool* threads that must
swallow per-item failures to protect their siblings (watch-callback
dispatch, fan-in workers): it logs the traceback and counts
``xllm_callback_errors_total{root}`` so a broken callback is an alert,
not a silent drop (xlint rule 16, ``swallow-telemetry``, verifies the
handler path reaches it).

Both books are module-global (one process, one truth) and mirrored
into each plane's registry at scrape time via ``flush_metrics`` — in
co-located test deployments both planes report the same process-wide
totals, with the ``root`` label identifying the activity.
"""

from __future__ import annotations

import logging
import threading
import time
import traceback
from typing import Any, Callable, Dict, Optional, Tuple

from xllm_service_tpu.utils.locks import make_lock
from xllm_service_tpu.utils.retry import RetryPolicy

logger = logging.getLogger(__name__)

_book_lock = make_lock("threads.book", 94)
_crashes: Dict[str, int] = {}
_callback_errors: Dict[str, int] = {}

# A supervised run that survived longer than this is "healthy": the
# next crash starts the backoff ladder from the bottom instead of
# compounding backoff from crashes that happened hours apart.
_HEALTHY_RUN_S = 60.0

# The default restart policy for beat/watch loops: capped exponential
# with full jitter (a fleet of watch loops crashing on the same store
# hiccup must not restart in lockstep). Callers needing a different
# shape pass their own RetryPolicy.
RESTART_POLICY = RetryPolicy(max_attempts=0, base_delay_s=0.2,
                             max_delay_s=10.0, jitter=0.5)


def record_crash(root: str, exc: BaseException,
                 events: Any = None, restarting: bool = False) -> None:
    """The supervised top-level handler's body: LOG the traceback and
    COUNT the crash, then (best-effort) emit ``thread_crashed``."""
    logger.error(
        "supervised thread %r crashed%s: %r\n%s", root,
        " (restarting)" if restarting else " (NOT restarted)", exc,
        "".join(traceback.format_exception(
            type(exc), exc, exc.__traceback__)))
    with _book_lock:
        _crashes[root] = _crashes.get(root, 0) + 1
    try:
        if callable(events) and not hasattr(events, "emit"):
            events = events()     # lazy provider (late-attached logs)
        if events is not None:
            events.emit("thread_crashed", root=root, error=repr(exc),
                        restarting=restarting)
    except Exception as e:
        # The crash is already logged and counted above — a broken
        # event sink must not mask the original failure.
        logger.warning("thread_crashed event emit failed: %s", e)


def record_callback_error(root: str, exc: BaseException) -> None:
    """Telemetry for pool threads that deliberately swallow a bad
    callback to protect their siblings: log + count, never raise."""
    logger.error(
        "callback on %r raised (swallowed so the pool survives): %r\n%s",
        root, exc,
        "".join(traceback.format_exception(
            type(exc), exc, exc.__traceback__)))
    with _book_lock:
        _callback_errors[root] = _callback_errors.get(root, 0) + 1


def crash_counts() -> Dict[str, int]:
    with _book_lock:
        return dict(_crashes)


def callback_error_counts() -> Dict[str, int]:
    with _book_lock:
        return dict(_callback_errors)


def flush_metrics(registry: Any) -> None:
    """Scrape-time mirror of both books into a plane's registry:
    ``xllm_thread_crashes_total{root}`` /
    ``xllm_callback_errors_total{root}`` (absolute set from the book —
    idempotent, no double counting across scrapes)."""
    crashes = crash_counts()
    cb = callback_error_counts()
    if crashes:
        fam = registry.counter(
            "xllm_thread_crashes_total",
            "uncaught exceptions that escaped a supervised thread root",
            labelnames=("root",))
        for root, n in crashes.items():
            fam.set_total(n, root=root)
    if cb:
        fam = registry.counter(
            "xllm_callback_errors_total",
            "callback errors swallowed by pool/dispatcher threads "
            "(the pool survives; the error is counted here)",
            labelnames=("root",))
        for root, n in cb.items():
            fam.set_total(n, root=root)


class SupervisedThread(threading.Thread):
    """A ``threading.Thread`` whose run() is wrapped in the supervised
    handler. Construct via ``spawn()``."""

    def __init__(self, root: str, target: Callable[..., Any],
                 args: Tuple = (), kwargs: Optional[Dict] = None,
                 daemon: bool = True,
                 restart: Optional[RetryPolicy] = None,
                 events: Any = None,
                 stop: Optional[threading.Event] = None,
                 thread_name: Optional[str] = None) -> None:
        super().__init__(name=thread_name or root, daemon=daemon)
        self.root = root
        self._target_fn = target
        self._target_args = tuple(args)
        self._target_kwargs = dict(kwargs or {})
        self._restart = restart
        self._events = events
        self._stop_event = stop
        self.crashes = 0            # this thread's own crash count

    def _should_restart(self) -> bool:
        if self._restart is None:
            return False
        return not (self._stop_event is not None
                    and self._stop_event.is_set())

    def run(self) -> None:        # noqa: D102 — Thread contract
        profiler = None
        try:
            # Lazy import: threads.py sits below obs in the import
            # graph (obs.metrics imports utils.locks). The profiler
            # attributes /proc CPU time to this root by native tid.
            from xllm_service_tpu.obs import profiler
            profiler.register_thread_root(self.root)
        except Exception:  # noqa: BLE001 — best-effort CPU attribution;
            pass           # a root must start even if the profiler can't
                           # bind its tid (partial deploy, exotic libc)
        try:
            self._supervise()
        finally:
            if profiler is not None:
                try:
                    # what it ran since the last scrape, before the tid
                    # goes back to the kernel
                    profiler.retire_thread_root()
                except Exception:  # noqa: BLE001 — as above
                    pass

    def _supervise(self) -> None:
        attempt = 0
        while True:
            started = time.monotonic()
            try:
                self._target_fn(*self._target_args,
                                **self._target_kwargs)
                return              # clean exit: the loop chose to end
            except Exception as e:
                self.crashes += 1
                restarting = self._should_restart()
                record_crash(self.root, e, events=self._events,
                             restarting=restarting)
                if not restarting:
                    return
                if time.monotonic() - started >= _HEALTHY_RUN_S:
                    attempt = 0     # healthy run: backoff ladder resets
                if not self._restart.sleep(attempt,
                                           stop_event=self._stop_event):
                    return          # shutdown interrupted the backoff
                attempt += 1
            except BaseException as e:
                # SystemExit/KeyboardInterrupt are deliberate: record
                # (so the death is visible) but never restart through
                # them. SystemExit's whole effect IS thread exit —
                # swallow it like threading's own bootstrap does;
                # everything else propagates to threading.excepthook.
                record_crash(self.root, e, events=self._events,
                             restarting=False)
                if isinstance(e, SystemExit):
                    return
                raise


def spawn(name: str, target: Callable[..., Any], *,
          args: Tuple = (), kwargs: Optional[Dict] = None,
          daemon: bool = True,
          restart: Optional[RetryPolicy] = None,
          events: Any = None,
          stop: Optional[threading.Event] = None,
          thread_name: Optional[str] = None) -> SupervisedThread:
    """The one sanctioned thread constructor (module docstring).

    ``name`` is the STABLE root id — it becomes the ``root`` label on
    ``xllm_thread_crashes_total`` and the ``thread_crashed`` event, so
    keep it low-cardinality (``"worker.hb"``, not one name per
    address); pass the debugging-friendly per-instance string as
    ``thread_name``. ``events`` may be an EventLog or a zero-arg
    callable returning one (resolved at crash time — for owners whose
    event log is attached after construction). Like
    ``threading.Thread``, the caller ``.start()``s the result."""
    return SupervisedThread(name, target, args=args, kwargs=kwargs,
                            daemon=daemon, restart=restart,
                            events=events, stop=stop,
                            thread_name=thread_name)
