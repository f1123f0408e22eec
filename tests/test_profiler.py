"""The master watching itself: hot-path section accounting, lock
contention sampling, /proc-based attribution, the stack sampler — and
one end-to-end smoke of the saturation observatory
(benchmarks/service_bench.py --saturate) small enough for tier-1.
"""
import json
import threading
import time

import pytest

from xllm_service_tpu.obs import profiler
from xllm_service_tpu.obs.metrics import Registry
from xllm_service_tpu.utils import locks


@pytest.fixture(autouse=True)
def _fresh_books():
    """Profiler and contention books are process-global by design —
    isolate every test from its neighbors' residue."""
    profiler.reset_sections()
    locks.reset_contention()
    yield
    profiler.reset_sections()
    locks.reset_contention()


class TestSections:
    def test_catalog_is_closed(self):
        with pytest.raises(ValueError, match="closed catalog"):
            profiler.section("not.a.section")

    def test_section_times_into_thread_book(self):
        with profiler.section("schedule"):
            time.sleep(0.002)
        snap = profiler.section_snapshot()
        assert snap["schedule"]["ops"] == 1
        assert snap["schedule"]["sum_ms"] >= 1.0
        # The histogram bucket row holds exactly the one sample.
        assert sum(snap["schedule"]["counts"]) == 1

    def test_books_merge_across_threads(self):
        def work():
            for _ in range(5):
                with profiler.section("relay.frame"):
                    pass
        ts = [threading.Thread(target=work) for _ in range(3)]
        for t in ts:
            t.start()
        for t in ts:
            t.join()
        with profiler.section("relay.frame"):
            pass
        assert profiler.section_snapshot()["relay.frame"]["ops"] == 16

    def test_disabled_returns_shared_noop(self, monkeypatch):
        monkeypatch.setattr(profiler, "ENABLED", False)
        a = profiler.section("schedule")
        b = profiler.section("tokenize")
        assert a is b  # one shared null context manager, no allocation
        with a:
            pass
        assert profiler.section_snapshot() == {}

    def test_flush_metrics_mirrors_sections_into_registry(self):
        with profiler.section("span.write"):
            pass
        reg = Registry()
        profiler.flush_metrics(reg)
        text = reg.render()
        assert 'xllm_service_hotpath_ops_total{section="span.write"} 1' \
            in text
        assert 'xllm_service_hotpath_ms_count{section="span.write"} 1' \
            in text
        # Self-gauges ride the same flush.
        assert "xllm_process_rss_bytes" in text
        assert "xllm_process_threads" in text

    def test_snapshot_reports_quantiles_per_section(self):
        for _ in range(10):
            with profiler.section("sse.assemble"):
                pass
        snap = profiler.snapshot()
        row = snap["sections"]["sse.assemble"]
        assert row["ops"] == 10
        assert row["p50"] is not None and row["p99"] is not None
        assert row["p50"] <= row["p99"]


class TestLockContention:
    def test_sampled_contended_acquisition_is_booked(self, monkeypatch):
        monkeypatch.setattr(locks, "PROFILE_SAMPLE", 1)
        lk = locks.CheckedLock("obs.spans", 70)
        with lk:
            t = threading.Thread(target=lambda: (lk.acquire(),
                                                 lk.release()))
            t.start()
            time.sleep(0.02)  # the thread is now parked on the lock
        t.join()
        book = locks.contention_snapshot()["obs.spans"]
        assert book["sampled"] >= 1
        assert book["contended"] >= 1
        assert book["wait_sum_ms"] > 0
        assert book["rank"] == 70

    def test_uncontended_acquisition_books_zero_wait(self, monkeypatch):
        monkeypatch.setattr(locks, "PROFILE_SAMPLE", 1)
        lk = locks.CheckedLock("scheduler.req", 40)
        with lk:
            pass
        book = locks.contention_snapshot()["scheduler.req"]
        assert book["sampled"] == 1 and book["contended"] == 0

    def test_contention_mirrors_into_registry(self, monkeypatch):
        monkeypatch.setattr(locks, "PROFILE_SAMPLE", 1)
        # A name the Registry doesn't itself acquire mid-flush (its own
        # obs.registry lock keeps booking samples while we render).
        lk = locks.CheckedLock("instance_mgr", 30)
        with lk:
            pass
        reg = Registry()
        profiler.flush_metrics(reg)
        text = reg.render()
        assert 'xllm_lock_sampled_total{lock="instance_mgr"} 1' in text
        assert 'xllm_lock_contended_total{lock="instance_mgr"} 0' \
            in text


def _series(text, family, label="root"):
    import re
    return {m.group(1): float(m.group(2)) for m in re.finditer(
        r"^%s\{%s=\"([^\"]+)\"\} (\S+)$" % (family, label), text, re.M)}


@pytest.fixture
def task_dir(tmp_path, monkeypatch):
    """The per-root books emptied and pointed at a directory of fixture
    files (``<tid>/schedstat``, ``<tid>/stat``) in the place of
    ``/proc/self/task``; put back afterwards. ``write(tid, cpu_s,
    runq_s)`` lays a thread's files down as the kernel would, ``exit``
    takes them away, ``claim`` registers a tid as its thread would."""
    import os
    for name, fresh in (("_tid_root", {}), ("_fresh_tids", set()),
                        ("_tid_last", {}), ("_root_total", {}),
                        ("_clock_source", None),
                        ("_TASK_DIR", str(tmp_path))):
        monkeypatch.setattr(profiler, name, fresh)

    class Dir:
        schedstat = True

        def write(self, tid, cpu_s, runq_s=0.0):
            d = tmp_path / str(tid)
            d.mkdir(exist_ok=True)
            ticks = int(round(cpu_s * profiler._CLK_TCK))
            (d / "stat").write_text(
                f"{tid} (httpd-native (1)) S 1 1 1 0 -1 4194304 0 0 0 0 "
                f"{ticks - ticks // 4} {ticks // 4} 0 0 20 0 9 0 1 2 3\n")
            if self.schedstat:
                (d / "schedstat").write_text(
                    f"{int(cpu_s * 1e9)} {int(runq_s * 1e9)} 7\n")

        def exit(self, tid):
            import shutil
            shutil.rmtree(tmp_path / str(tid))

        def claim(self, tid, root):
            monkeypatch.setattr(threading, "get_native_id", lambda: tid)
            profiler.register_thread_root(root)

    d = Dir()
    d.pid = os.getpid()
    return d


@pytest.mark.parametrize("source", ["schedstat", "stat"])
def test_every_thread_has_a_root_and_no_series_ever_falls(task_dir, source):
    """Handlers come and go a request and the kernel hands their tids out
    again: an exited handler's seconds stay, a thread no root claims is
    ``unregistered``, the main thread is ``main``, and where the host
    keeps no schedstats the ticks of ``stat`` are read and no wait is
    known. One walk for both clocks."""
    task_dir.schedstat = source == "schedstat"
    wait = 1.0 if task_dir.schedstat else 0.0     # stat knows no wait
    task_dir.write(task_dir.pid, 3.0, 0.25)
    task_dir.write(501, 2.0, 0.5)
    task_dir.write(777, 40.0, 8.0)                # a native thread
    task_dir.claim(501, profiler.HANDLER_ROOT)
    snap = profiler.thread_clock_snapshot()
    assert profiler._source() == source
    assert snap == {"main": (3.0, 0.25 * wait),
                    "httpd.handler": (2.0, 0.5 * wait),
                    "unregistered": (40.0, 8.0 * wait)}
    seen = [snap]
    # it runs on; then it exits between two scrapes, having booked its
    # own last seconds as service/httpd.py's finish() does
    task_dir.write(501, 2.5, 0.75)
    seen.append(profiler.thread_clock_snapshot())
    assert seen[-1]["httpd.handler"] == (2.5, 0.75 * wait)
    task_dir.write(501, 2.75, 0.75)
    profiler.retire_thread_root()                 # (the tid is 501's)
    task_dir.exit(501)
    seen.append(profiler.thread_clock_snapshot())
    assert seen[-1]["httpd.handler"] == (2.75, 0.75 * wait)
    # the kernel hands 501 to the next connection's thread, which
    # starts from nothing: the root gains what THAT thread runs
    task_dir.write(501, 0.5, 0.125)
    task_dir.claim(501, profiler.HANDLER_ROOT)
    seen.append(profiler.thread_clock_snapshot())
    assert seen[-1]["httpd.handler"] == (3.25, 0.875 * wait)
    # that one dies unbooked (a crash) and, before the next scrape, its
    # tid goes to a native thread nobody claims: the dead claim is
    # dropped, not fed
    task_dir.write(501, 0.25, 0.0)
    task_dir.write(777, 41.0, 8.0)
    seen.append(profiler.thread_clock_snapshot())
    assert seen[-1]["httpd.handler"] == (3.25, 0.875 * wait)
    assert seen[-1]["unregistered"] == (41.25, 8.0 * wait)
    assert seen[-1]["main"] == (3.0, 0.25 * wait)
    for before, after in zip(seen, seen[1:]):
        for root, (cpu, runq) in before.items():
            assert after[root][0] >= cpu and after[root][1] >= runq
    reg = Registry()
    profiler.flush_metrics(reg)
    text = reg.render()
    assert _series(text, "xllm_thread_clock", label="source") == {
        "schedstat": float(source == "schedstat"),
        "stat": float(source == "stat")}
    assert _series(text, "xllm_thread_runq_wait_seconds_total")[
        "unregistered"] == 8.0 * wait
    assert profiler.thread_cpu_snapshot()["httpd.handler"] == 3.25


def test_threads_come_and_go_under_a_scraper_and_no_total_falls():
    """Handlers that register, burn a little and book themselves out,
    more of them than cores and under a shortened switch interval, while
    one thread scrapes: no read raises, no root's series ever falls, and
    what the handlers burned is all there at the end."""
    import os
    import sys
    errors, seen = [], []
    stop = threading.Event()
    burn_s, workers, rounds = 0.002, 4 * (os.cpu_count() or 2), 6

    def handler():
        try:
            for _ in range(rounds):
                t = threading.Thread(target=one_connection)
                t.start()
                t.join(10.0)
                assert not t.is_alive()
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(e)

    def one_connection():
        try:
            profiler.register_thread_root("test.stress")
            t0 = time.thread_time()
            while time.thread_time() - t0 < burn_s:
                pass
            profiler.retire_thread_root()
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    def scraper():
        try:
            while not stop.is_set():
                seen.append(profiler.thread_clock_snapshot())
        except Exception as e:  # noqa: BLE001
            errors.append(e)

    before = profiler.thread_clock_snapshot().get("test.stress", (0.0, 0.0))
    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        sc = threading.Thread(target=scraper)
        sc.start()
        ts = [threading.Thread(target=handler) for _ in range(workers)]
        for t in ts:
            t.start()
        for t in ts:
            t.join(60.0)
        stop.set()
        sc.join(10.0)
        assert not sc.is_alive() and not any(t.is_alive() for t in ts)
    finally:
        sys.setswitchinterval(old)
    assert errors == []
    seen.append(profiler.thread_clock_snapshot())
    for a, b in zip(seen, seen[1:]):
        for root, (cpu, runq) in a.items():
            assert b[root][0] >= cpu and b[root][1] >= runq, root
    burned = seen[-1]["test.stress"][0] - before[0]
    if profiler._source() == "schedstat":
        # every thread booked itself out: nothing between two scrapes
        # was lost (thread_time and schedstat agree to a fraction)
        assert burned >= 0.8 * workers * rounds * burn_s
    assert "test.stress" not in profiler._tid_root.values()


class TestSelfStats:
    def test_thread_cpu_attributed_per_root(self):
        done = threading.Event()

        def burn():
            profiler.register_thread_root("test.burner")
            t0 = time.process_time()
            while time.process_time() - t0 < 0.05:
                pass
            done.set()
        t = threading.Thread(target=burn)
        t.start()
        done.wait(5.0)
        snap = profiler.thread_cpu_snapshot()
        t.join()
        assert "test.burner" in snap
        assert snap["test.burner"] >= 0.0
        # After exit the root's total is retired, never dropped.
        assert "test.burner" in profiler.thread_cpu_snapshot()

    def test_a_handlers_thread_shows_under_both_families(self):
        """What service/httpd.py does once a connection: the thread
        takes the handlers' root, and the scrape carries its seconds on
        a core and its seconds waiting for one, beside ``main`` and the
        threads no root claims."""
        done, go = threading.Event(), threading.Event()

        def handle():
            profiler.register_thread_root(profiler.HANDLER_ROOT)
            t0 = time.process_time()
            while time.process_time() - t0 < 0.03:
                pass
            done.set()
            go.wait(5.0)
        t = threading.Thread(target=handle)
        t.start()
        assert done.wait(5.0)
        reg = Registry()
        profiler.flush_metrics(reg)
        go.set()
        t.join()
        text = reg.render()
        cpu = _series(text, "xllm_thread_cpu_seconds_total")
        runq = _series(text, "xllm_thread_runq_wait_seconds_total")
        assert {"httpd.handler", "main", "unregistered"} <= set(cpu)
        assert set(cpu) == set(runq)
        assert cpu["httpd.handler"] >= 0.02 and cpu["main"] > 0
        clock = _series(text, "xllm_thread_clock", label="source")
        assert sorted(clock.values()) == [0.0, 1.0]
        assert clock[profiler._source()] == 1.0

    def test_gc_pauses_are_booked(self):
        import gc
        profiler.install_gc_hook()
        before = profiler.gc_snapshot()["pause_total"]
        gc.collect()
        after = profiler.gc_snapshot()
        assert after["pause_total"] > before
        assert after["collections"].get(2, 0) >= 1

    def test_stack_sampler_sees_other_threads(self):
        stop = threading.Event()

        def marker_function_for_sampler():
            while not stop.is_set():
                time.sleep(0.001)
        t = threading.Thread(target=marker_function_for_sampler)
        t.start()
        try:
            out = profiler.sample_stacks(seconds=0.2, hz=100.0)
        finally:
            stop.set()
            t.join()
        assert out["samples"] > 0
        assert out["thread_samples"] > 0
        leaves = json.dumps(out["top_functions"])
        assert "marker_function_for_sampler" in leaves or \
            out["top_functions"]  # at minimum the table is populated


class TestSaturateSmoke:
    """End-to-end observatory smoke: a 2-step low-concurrency
    --saturate run must produce the full BENCH_SVC JSON schema, light
    up the profiler/contention series on /metrics, and answer
    /admin/profile — with measured profiler overhead inside the gate.
    """

    def test_saturate_run_schema_metrics_and_profile(self):
        from benchmarks.service_bench import (
            _SatCluster, _sat_step, _scrape_prom, http_stream,
            saturate_run)
        from xllm_service_tpu.service.coordination_net import \
            StoreServer

        out = saturate_run(
            steps=[4, 8], step_seconds=1.0, n_workers=1, gen_tokens=4,
            frame_interval_ms=5.0, lock_sample=2, shard_size=16,
            overhead_floor_ms=250.0)
        assert out["metric"] == "service_saturation_knee"
        assert out["value"] in (4, 8)
        assert out["unit"] == "streams"
        d = out["detail"]
        assert len(d["steps"]) == 2
        for step in d["steps"]:
            for key in ("concurrency", "completed", "errors",
                        "streams_per_s", "master_cpu_pct",
                        "schedule_ops_per_s", "relay_frames_per_s",
                        "p50_ms", "p99_ms", "p99_service_added_ms",
                        "dominant_section", "dominant_lock",
                        "sections_per_op_ms"):
                assert key in step, key
            assert step["completed"] > 0
            assert step["errors"] == 0
            assert step["dominant_section"]["name"] in \
                profiler.SECTIONS
        assert d["knee"]["concurrency"] == out["value"]
        # The overhead gate: measured, and inside floor-or-3% at this
        # scale (the r01 artifact records the 1k-step measurement).
        oh = d["profiler_overhead"]
        assert oh["p99_on_ms"] > 0 and oh["p99_off_ms"] > 0
        assert oh["ok"] is True
        spent = d["spent_finding"]
        assert spent["sections"]  # before/after per-op attribution
        assert any(v["after_ms"] is not None
                   for v in spent["sections"].values())

        # One more live cluster for the scrape-surface assertions.
        store_srv = StoreServer().start()
        try:
            cl = _SatCluster(
                store_srv.address, 1, 4, 5.0,
                {"XLLM_HOTPATH_PROFILE": "1",
                 "XLLM_LOCK_PROFILE_SAMPLE": "2",
                 "XLLM_MAX_CONCURRENCY": "64"})
            try:
                step = _sat_step([cl.http], cl.proc.pid, 8, 1.0, 4,
                                 5.0, shard_size=16)
                assert step["completed"] > 0
                prom = _scrape_prom(cl.http)
                hot = {k: v for k, v in prom.items()
                       if k.startswith("xllm_service_hotpath_ops_total")
                       and v > 0}
                assert hot, "no nonzero hot-path section series"
                assert any(k.startswith("xllm_lock_sampled_total")
                           and v > 0 for k, v in prom.items()), \
                    "no nonzero lock-sampling series"
                snap = json.loads(b"".join(http_stream(
                    "GET", cl.http, "/admin/profile?seconds=0.2",
                    timeout=60.0)).decode("utf-8"))
                assert snap["enabled"] is True
                assert snap["sections"]
                assert snap["stacks"]["samples"] > 0
            finally:
                cl.stop()
        finally:
            store_srv.stop()
