"""One run of one cell.

    python3 -m chipbench.run --workload <name> --seed <n> --seconds <s> --trace <0|1|2>

A new process each time. It needs a TPU and fails without one (exit 3,
no result line); ``--rehearse-cpu`` walks the same command at tiny
widths on the CPU and reports no device metric. The last line of
standard output is the result object; everything else is on earlier
lines, and detail goes to ``chiprun_out/chipbench/``.

``--trace 0`` measures and prints the end-to-end metrics. ``--trace 2``
is that same run, to the closing of its window and the taking of its
numbers, followed by a short stretch of the same traffic a few seconds
of which are traced: one line with both kinds of metric. ``--trace 1``
(a run of its own that traces inside its window and prints the
per-layer metrics alone) is what the driver used before it. Every trace
goes through the worker's own control (``Worker.start_device_trace``).
"""

from __future__ import annotations

import time

T_START = time.monotonic()

import argparse  # noqa: E402
import collections  # noqa: E402
import gc  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import statistics  # noqa: E402
import subprocess  # noqa: E402
import sys  # noqa: E402
import threading  # noqa: E402
from typing import Any, Dict, List, Optional  # noqa: E402

HERE = os.path.dirname(os.path.abspath(__file__))
ROOT = os.path.dirname(HERE)
if ROOT not in sys.path:
    sys.path.insert(0, ROOT)

from chipbench import spec, stats, traffic  # noqa: E402
from chipbench.cluster import log  # noqa: E402

def parse(argv: Optional[List[str]]) -> argparse.Namespace:
    p = argparse.ArgumentParser(description=__doc__)
    p.add_argument("--workload", required=True)
    p.add_argument("--seed", type=int, required=True)
    p.add_argument("--seconds", type=float, required=True)
    p.add_argument("--trace", type=int, choices=(0, 1, 2), default=0)
    p.add_argument("--rehearse-cpu", action="store_true",
                   help="tiny widths on the CPU; no device metric")
    p.add_argument("--control", default="",
                   help="also read the lower-precision control's gap "
                        "(int8); never part of a benchmark run")
    p.add_argument("--override", default="",
                   help="JSON merged over the mix's data, for a sweep or "
                        "a rehearsal; never part of a benchmark run")
    p.add_argument("--limit", type=float, default=None,
                   help="override the check's limit (rehearsals only)")
    return p.parse_args(argv)


def device_or_exit(chips: int, rehearse: bool):
    import jax
    devs = jax.devices()
    if rehearse:
        return devs[:1]
    if devs[0].platform != "tpu" or len(devs) < chips:
        print(f"chipbench: this cell needs {chips} TPU chip(s); JAX gives "
              f"{len(devs)} x {devs[0].platform}", file=sys.stderr)
        raise SystemExit(3)
    return devs[:chips]


def run_loadgen(procs, run_dir: str, name: str, schedule: Dict[str, Any],
                addr: str, model: str):
    """Start the generator's process on a schedule; returns (process,
    its start time on the shared monotonic clock, the records' path)."""
    sched_path = os.path.join(run_dir, name + ".schedule.json")
    out_path = os.path.join(run_dir, name + ".records.json")
    with open(sched_path, "w") as f:
        json.dump(schedule, f)
    p = procs.start(name, ["-m", "chipbench.loadgen", sched_path, addr,
                           model, out_path], capture=True,
                    env={"PYTHONPATH": ROOT + os.pathsep
                         + os.environ.get("PYTHONPATH", "")})
    line = p.stdout.readline()
    if not line.startswith("T0 "):
        raise RuntimeError(f"load generator did not start: {line!r}\n"
                           + procs.log_tail(name))
    return p, float(line.split()[1]), out_path


def finish_loadgen(p, out_path: str, timeout_s: float) -> List[Dict]:
    try:
        p.wait(timeout_s)
    except subprocess.TimeoutExpired:
        p.kill()
        p.wait()
        raise RuntimeError("the load generator did not finish in time")
    with open(out_path) as f:
        return json.load(f)["records"]


def sleep_until(t: float) -> None:
    while True:
        left = t - time.monotonic()
        if left <= 0:
            return
        time.sleep(min(left, 0.2))


def main(argv: Optional[List[str]] = None) -> int:
    args = parse(argv)
    if args.rehearse_cpu:
        os.environ["JAX_PLATFORMS"] = "cpu"
    cell = spec.load_cell(args.workload, ROOT)
    config = dict(cell.config)
    if args.override:
        cell.traffic.update(json.loads(args.override))
    if args.rehearse_cpu:
        # The configuration's own tiny widths (``meta.json``): every key
        # a size of its family, nothing else changes.
        config.update(cell.meta["rehearsal_widths"])
        cell.traffic.update(cell.traffic.get("rehearsal") or {})

    from chipbench import cluster
    run_dir = os.path.join(ROOT, ".chipbench_run", cell.name)
    os.makedirs(run_dir, exist_ok=True)
    procs = cluster.Procs(os.path.join(run_dir, "logs"))
    # Store and master come up while this process finds its chip.
    front_box: Dict[str, Any] = {}

    def bring_front():
        try:
            front_box["front"] = cluster.start_front(procs)
        except BaseException as e:  # noqa: BLE001 — re-raised below
            front_box["error"] = e

    front_thread = threading.Thread(target=bring_front)
    front_thread.start()
    import jax
    cache_dir = ""
    if not args.rehearse_cpu:
        # JAX's persistent cache at a fixed path inside the checkout,
        # whatever the environment says, and never trimmed: every run of
        # a cell after its first there finds all its programs.
        cache_dir = os.path.join(ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_compilation_cache_max_size", -1)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
        jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    try:
        devices = device_or_exit(cell.chips, args.rehearse_cpu)
    except BaseException:
        front_thread.join()
        procs.stop_all()        # no child outlives a run without a chip
        raise
    dev = devices[0]
    log(f"cell {cell.name} seed {args.seed} seconds {args.seconds} trace "
        f"{args.trace}; device {dev.device_kind} x{len(devices)}; compile "
        f"cache {cache_dir or 'off (CPU)'}")

    from chipbench import check
    wts = spec.load_weights(cell)
    out_dir = os.path.join(ROOT, "chiprun_out", "chipbench",
                           f"{cell.name}-seed{args.seed}-t{args.trace}")
    os.makedirs(out_dir, exist_ok=True)
    marks: Dict[str, float] = {}
    worker = None
    try:
        # ---- set-up: everything until the first timed request --------
        marks["device"] = time.monotonic() - T_START
        params = wts.program_tree(config, args.seed)
        jax.block_until_ready(params)
        marks["weights"] = time.monotonic() - T_START
        model_dir = cluster.write_model_dir(
            os.path.join(run_dir, "model"), config)
        front_thread.join()
        if "error" in front_box:
            raise front_box["error"]
        front = front_box["front"]
        marks["front"] = time.monotonic() - T_START
        worker = cluster.build_worker(cell, model_dir, front, params)
        del params
        engine = worker.runtimes[cell.config_name].engine
        # A single-device engine bans JAX's persistent cache for its
        # process; the configuration says whether its step programs may
        # come from the cache all the same (``cluster.enable_cache_again``
        # says when they may).
        from_cache = bool(cell.meta["step_programs_from_cache"])
        if from_cache:
            cluster.enable_cache_again()
        marks["engine"] = time.monotonic() - T_START
        shapes = traffic.warmup_shapes(cell.traffic, engine.ecfg.page_size)
        if args.rehearse_cpu:
            # A CPU compile proves nothing about the chip's: warm the
            # decode widths only and let the rest compile as it comes.
            shapes = {"prefill": [], "decode_widths":
                      shapes["decode_widths"][-1:]}
        elif not from_cache:
            log("the engine's ban on the persistent cache stands for this "
                "configuration (meta.json step_programs_from_cache): the "
                "warm-up compiles every step program, in this run and in "
                "every other")
        else:
            done = cluster.precompile_marker(cache_dir, config,
                                             cell.traffic, shapes)
            if os.path.exists(done):
                log("every step program of this cell is in the cache "
                    "already (marker " + os.path.basename(done) + ")")
            else:
                t = cluster.precompile(engine, shapes, threads=int(
                    cell.traffic["warmup"].get("compile_threads", 6)))
                with open(done, "w") as f:
                    json.dump(shapes, f)
                # The engine's own calls key the cache otherwise than an
                # ahead-of-time compile does: forget what this process
                # holds, so that the warm-up below goes by the call's own
                # path and leaves ITS entries too (a fast recompile now,
                # a plain load in every later run). Without this the
                # checkout's SECOND run pays.
                jax.clear_caches()
                log(f"compiled {len(shapes['prefill'])} prefill and "
                    f"{len(shapes['decode_widths'])} decode programs "
                    f"side by side in {t:.1f} s")
        marks["precompile"] = time.monotonic() - T_START
        with worker._engine_lock:
            engine.warmup(prefill_shapes=shapes["prefill"],
                          decode_widths=shapes["decode_widths"])
        marks["warmup"] = time.monotonic() - T_START
        worker.start()
        waddr = f"127.0.0.1:{worker.opts.port}"
        cluster.wait_registered(front, worker.name)
        marks["registered"] = time.monotonic() - T_START

        vocab = int(config["vocab_size"])
        schedule = traffic.build(cell.traffic, args.seed, args.seconds,
                                 vocab)
        schedule["sampling"] = cell.traffic.get("sampling") or {}
        setup_sched = dict(schedule, only="setup", requests=[])
        setup_sched["setup_requests"] = schedule["setup_requests"] + \
            traffic.warm_requests(cell.traffic, args.seed, vocab,
                                  len(schedule["docs"]))
        p, _, path = run_loadgen(procs, run_dir, "setup", setup_sched,
                                 front["http"], cell.config_name)
        setup_recs = finish_loadgen(p, path, 900)
        bad = [r for r in setup_recs if not r.get("ok")]
        if bad:
            raise RuntimeError(f"set-up request failed: {bad[0]}")
        setup_s = time.monotonic() - T_START
        marks["ready"] = setup_s
        log("set-up marks (s since start): " + ", ".join(
            f"{k} {v:.1f}" for k, v in marks.items()))

        # ---- the window ----------------------------------------------
        p, t0, path = run_loadgen(procs, run_dir, "load", schedule,
                                  front["http"], cell.config_name)
        open_t, close_t = t0 + schedule["open_t"], t0 + schedule["close_t"]
        sleep_until(open_t)
        c_open = cluster.scrape(waddr)
        steps: Dict[int, Dict[str, Any]] = {}
        traced = None
        if args.trace == 1:
            traced = traced_window(cell, worker, waddr, open_t, close_t,
                                   run_dir, steps)
        sleep_until(close_t)
        c_close = cluster.scrape(waddr)
        if args.trace:
            # --trace 2 pulls the step recorder here for the first time,
            # with the window closed: its ring holds the window's last
            # 512 steps (~10 s), over which kv_pages_peak_share reads.
            collect_steps(waddr, steps)
        records = finish_loadgen(p, path, schedule["end_t"]
                                 - schedule["close_t"] + 240)
        peak_bytes = run_peak_bytes = peak_in_use(dev)
        stretch_records: List[Dict] = []
        if args.trace == 2:
            traced, stretch_records = traced_stretch(
                cell, worker, waddr, procs, run_dir, front["http"],
                schedule, args.seed, vocab, steps)
            run_peak_bytes = peak_in_use(dev)

        # ---- stop the program and free its state, then check ---------
        worker.stop()
        name = worker.name
        from xllm_service_tpu.runtime import worker as W
        W._LOCAL_WORKERS.pop(name, None)
        worker.runtimes[cell.config_name].engine = None
        worker = engine = None
        procs.stop_all()
        gc.collect()
        log(f"program stopped; device bytes in use "
            f"{(dev.memory_stats() or {}).get('bytes_in_use', 'n/a')}")
    except BaseException:
        if worker is not None:
            try:
                worker.stop()
            except Exception:  # noqa: BLE001
                pass
        procs.stop_all()
        raise

    late = stats.lateness(records)
    log(f"generator lateness: median {late['median_ms']:.3f} ms, worst "
        f"{late['worst_ms']:.3f} ms over {len(records)} requests")
    by_id = {r["id"]: r for r in schedule["requests"]}
    for r in records:
        req = by_id.get(r["id"])
        r["n_prompt"] = len(traffic.prompt_of(schedule, req)) if req else 0
    in_window = [r for r in records if r.get("due") is not None
                 and open_t <= r["due"] < close_t]
    attempted = len(in_window)
    failed = sum(1 for r in in_window if not r.get("ok"))
    failed += sum(1 for r in records if r.get("due") is None
                  and not r.get("ok"))
    for r in records:
        if not r.get("ok"):
            log(f"request {r['id']} failed: {r.get('error')}")
            break
    e2e = stats.end_to_end(records, open_t, close_t)
    log(f"window: {attempted} requests due, {failed} failed, "
        f"{e2e['_n_ttft']} first tokens, {e2e['_n_gaps']} token gaps, "
        f"{e2e['_tokens']} tokens")

    ck = cell.traffic["check"]
    sample = check.pick_sample(records, open_t, close_t,
                               int(ck["served_tokens"]), args.seed)
    for s in sample:
        s["prompt"] = traffic.prompt_of(schedule, by_id[s["id"]])
    t_ck = time.monotonic()
    ref = spec.load_reference(cell)
    result = check.compare(ref, wts, config, args.seed, sample,
                           control=args.control or None)
    result["check_seconds"] = time.monotonic() - t_ck
    limit = args.limit if args.limit is not None else float(
        cell.meta["check"]["served_token_gap_limit"])
    q_limits = cell.meta["check"].get("served_token_gap_quantile_limits")
    correct, compared = check.verdict(result, limit, failed,
                                      int(ck["served_tokens"]), q_limits)
    if args.control and "control" in result:
        c = result["control"]
        log(f"CONTROL {c['precision']} first_choice_gap_max "
            f"{c['gap_max']:.6f} over {c['positions']} positions "
            f"({c['not_best']} not the reference's best); the program's "
            f"served_token_gap_max {result['gap_max']:.6f}")
        ctl_gaps = [g for r in result["per_request"]
                    for g in r["control_gaps"]]
        for q in sorted(q_limits or {}):
            name = check.quantile_name(q)
            log(f"CONTROL {c['precision']} "
                f"{name.replace('served_token', 'first_choice')} "
                f"{check.gap_quantile(ctl_gaps, float(q)):.6f}; the "
                f"program's {name} {compared[name]['value']:.6f}")
    with open(os.path.join(out_dir, "check.json"), "w") as f:
        json.dump({"seed": args.seed, "limit": limit, "correct": correct,
                   "failed_requests": [r for r in records
                                       if not r.get("ok")][:5],
                   **result}, f, indent=1)
    log(f"reference check took {result['check_seconds']:.1f} s")

    metrics: Dict[str, Dict[str, Any]] = {}
    if args.trace != 1:
        values = {**e2e, "setup_s": setup_s}
        for m in cell.end_to_end:
            if args.rehearse_cpu and m["name"] != "setup_s":
                continue
            v = values.get(m["name"])
            if v is None:
                log(f"metric {m['name']}: the window's sample does not "
                    f"support it")
                continue
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}
    if args.trace:
        # Under --trace 2 the window's own numbers (records, counters,
        # bounds, memory peak) stand beside the stretch's trace; the
        # stretch's records follow the window's because decode
        # attention's roofline share counts the tokens that arrived
        # inside the traced seconds (no reader takes a window number
        # from them: each filters on open_t..close_t).
        ctx = {"cell": cell, "config": config,
               "records": records + stretch_records,
               "schedule": schedule, "open_t": open_t, "close_t": close_t,
               "counters_open": c_open, "counters_close": c_close,
               "steps": [steps[k] for k in sorted(steps)],
               "memory_peak_bytes": peak_bytes, "trace": traced,
               "device_kind": dev.device_kind,
               "wall_minus_mono": time.time() - time.monotonic(),
               "rehearsal": args.rehearse_cpu, "root": ROOT}
        for m in cell.per_layer:
            info = spec.layer_metric_file(m["name"], ROOT)
            if args.rehearse_cpu and info["source"] != "program_counter":
                continue        # a CPU run reports counts, never a time
            value = spec.load_reader(info["reader"], ROOT).read(ctx, info)
            if value is not None:
                metrics[m["name"]] = {"value": float(value),
                                      "unit": m["unit"]}
    device = {"platform": dev.platform, "kind": dev.device_kind,
              "count": len(devices)}
    out: Dict[str, Any] = {"correct": bool(correct), "attempted": attempted,
                           "failed": failed, "metrics": metrics,
                           "device": device}
    if not args.rehearse_cpu:
        device["memory_peak_bytes"] = run_peak_bytes
        if traced is not None:
            device["busy_s"] = traced["busy"]["busy_s"]
            device["window_s"] = traced["busy"]["window_s"]
            out["breakdown"] = traced["breakdown"]
    elif args.trace != 1:
        out["rehearsal"] = {k: v for k, v in e2e.items() if v is not None}
    # Last in the line, where the driver's record of a run that is not
    # correct keeps it: each number compared, beside its limit.
    out["compared"] = compared
    print(json.dumps(out), flush=True)
    return 0


def peak_in_use(dev) -> int:
    return int((dev.memory_stats() or {}).get("peak_bytes_in_use", 0))


def collect_steps(waddr: str, steps: Dict[int, Dict[str, Any]]) -> None:
    from chipbench import cluster
    doc = json.loads(cluster.http_get(waddr, "/admin/steptrace?n=512"))
    for s in doc.get("steps", []):
        steps[int(s["seq"])] = s


def take_trace(cell, worker, run_dir: str, start: float, length: float
               ) -> Dict[str, Any]:
    """Hold the worker's device trace for ``length`` seconds from
    ``start`` (or from now, if that is later) and reduce it. The worker
    starts the profiler without the Python tracer and switches the
    program's ``xllm.*`` spans on, so the idle gaps are named by spans
    that cost nothing when off. The trace's files are deleted once they
    are read. A CPU trace has no device plane: it comes back with its
    events alone."""
    import shutil
    import jax
    from chipbench import spans, trace
    tdir = os.path.join(run_dir, "trace")
    shutil.rmtree(tdir, ignore_errors=True)
    sleep_until(start)
    worker.start_device_trace(tdir)
    w0 = time.time()
    until = time.monotonic() + length
    with jax.profiler.TraceAnnotation("chipbench.traced_window"):
        sleep_until(until)
    w1 = time.time()            # stopping itself takes seconds
    worker.stop_device_trace()
    events = trace.load_events(trace.find_xplane(tdir))
    shutil.rmtree(tdir, ignore_errors=True)
    out: Dict[str, Any] = {"wall0": w0, "wall1": w1, "events": events}
    counts = collections.Counter(
        e["name"] for e in spans.program_spans(events))
    log("program spans in the trace: " + (", ".join(
        f"{k} {v}" for k, v in sorted(counts.items())) or "none"))
    if jax.devices()[0].platform != "tpu":
        return out
    odir = os.path.join(ROOT, "chiprun_out", "chipbench")
    os.makedirs(odir, exist_ok=True)
    with open(os.path.join(odir, f"{cell.name}.trace_description.json"),
              "w") as f:
        json.dump(trace.describe(events), f, indent=1)
    out["busy"] = trace.busy(events)     # raises where no operation ran
    out["breakdown"] = {"device_ops": trace.top_ops(events),
                        "idle_gaps": spans.idle_by_span(events)}
    return out


def traced_window(cell, worker, waddr: str, open_t: float, close_t: float,
                  run_dir: str, steps: Dict[int, Dict[str, Any]]
                  ) -> Dict[str, Any]:
    """``--trace 1``: trace a few seconds of the steady window itself;
    poll the step recorder through the window (its ring holds ~10 s)."""
    tr = cell.traffic.get("trace") or {}
    start = open_t + float(tr.get("start_after_s", 3.0))
    length = min(float(tr.get("seconds", 3.0)),
                 max(0.5, close_t - start - 0.5))
    stop_poll = threading.Event()

    def poll():
        while not stop_poll.wait(4.0):
            try:
                collect_steps(waddr, steps)
            except Exception:  # noqa: BLE001 — next poll retries
                pass

    th = threading.Thread(target=poll, daemon=True)
    th.start()
    try:
        return take_trace(cell, worker, run_dir, start, length)
    finally:
        stop_poll.set()
        th.join()


def traced_stretch(cell, worker, waddr: str, procs, run_dir: str,
                   http_addr: str, schedule: Dict[str, Any], seed: int,
                   vocab: int, steps: Dict[int, Dict[str, Any]]):
    """``--trace 2``, after the measured window has closed and its
    numbers are taken: a second generator on the same mix brings the
    clients back to steady state (its own ramp), and a few seconds of it
    are traced. The documents are the window's, which are in the cache;
    the questions are drawn anew (seed + 1): a question the window has
    asked before would find its own tokens cached behind the document
    and leave a shorter window to prefill than any the mix warms up.
    Nothing polls the worker during the stretch; the step recorder is
    pulled once after the trace. Returns the trace and the stretch's
    records."""
    import shutil
    tr = cell.traffic.get("trace") or {}
    after = float(tr.get("start_after_s", 3.0))
    length = float(tr.get("seconds", 3.0))
    # The profiler's first start in a process is its dearest: pay for it
    # now, with nothing running, and throw that trace away.
    cold = os.path.join(run_dir, "trace_first_start")
    worker.start_device_trace(cold)
    worker.stop_device_trace()
    shutil.rmtree(cold, ignore_errors=True)
    stretch = traffic.build(cell.traffic, seed + 1, after + length + 1.0,
                            vocab)
    stretch["docs"] = schedule["docs"]
    stretch["sampling"] = schedule["sampling"]
    p, t0, path = run_loadgen(procs, run_dir, "stretch", stretch,
                              http_addr, cell.config_name)
    traced = take_trace(cell, worker, run_dir,
                        t0 + stretch["open_t"] + after, length)
    collect_steps(waddr, steps)
    records = finish_loadgen(p, path, stretch["end_t"] + 240)
    by_id = {r["id"]: r for r in stretch["requests"]}
    for r in records:
        req = by_id.get(r["id"])
        r["n_prompt"] = len(traffic.prompt_of(stretch, req)) if req else 0
    bad = [r for r in records if not r.get("ok")]
    log(f"traced stretch: {len(records)} requests, {len(bad)} failed"
        + (f" (first: {bad[0].get('error')})" if bad else ""))
    # What the traffic read WHILE the trace ran, on the generator's
    # clock: beside the window's own numbers, what tracing costs.
    off = time.time() - time.monotonic()
    on = stats.end_to_end(records, traced["wall0"] - off,
                          traced["wall1"] - off)
    ttft = [1000.0 * (r["frames"][0][0] - r["due"]) for r in records
            if r["ok"] and r["frames"] and traced["wall0"] - off
            <= r["due"] < traced["wall1"] - off]
    log(f"with the device trace running: {on['out_tok_s']:.1f} tokens/s "
        f"over {on['_tokens']} tokens; first token after "
        + (f"{statistics.median(ttft):.1f} ms (median of {len(ttft)})"
           if ttft else "no request due in it"))
    return traced, records


if __name__ == "__main__":
    raise SystemExit(main())
