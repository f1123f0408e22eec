"""Load generator + latency harness for the serving stack.

Fills the measurement gap the reference leaves open (it publishes no
benchmarks — BASELINE.md): ShareGPT-style mixed-length replay against any
OpenAI endpoint (this framework's service, a single worker, or anything
else speaking the API), with Poisson arrivals, SSE-timed TTFT/TPOT, and
SLA-tier attainment for the online/offline hybrid config (BASELINE.json
configs #2 and #4).

Usage:
  python -m benchmarks.loadgen --target 127.0.0.1:9888 --model tiny \
      --num-requests 64 --request-rate 8 --max-tokens 32

Prints one JSON summary: req/s, p50/p99 TTFT, p50/p99 TPOT, SLO
attainment vs --target-ttft/--target-tpot, and goodput-under-SLO
(completed req/s meeting BOTH targets). ``--closed-loop`` switches to
the concurrency-ramp harness (``run_closed_loop``): per-stage closed
loops with heavy-tailed prompt/output lengths whose last stage is the
burst, reporting burst-mode ``ttft_ms_p99``/``tpot_ms_p99_under_burst``.
"""

from __future__ import annotations

import argparse
import dataclasses
import json
import random
import threading
import time
from typing import Any, Dict, List, Optional, Sequence

from xllm_service_tpu.service.httpd import (
    http_json, http_stream_status, iter_sse_events)


@dataclasses.dataclass
class RequestResult:
    ok: bool = False
    ttft_ms: float = 0.0
    tpot_ms: float = 0.0
    total_ms: float = 0.0
    num_tokens: int = 0
    offline: bool = False
    error: str = ""
    # Shed by bounded admission (HTTP 429 + Retry-After): reported
    # separately from errors — the service refusing load under a cap is
    # policy, not failure.
    shed: bool = False
    # Start offset (s) from the harness epoch; lets --chaos split
    # results into pre/during/post stages after the fact.
    started_s: float = 0.0
    # Per-request SLO verdict, stamped by summarize_results: online,
    # completed, and met BOTH the TTFT and TPOT targets.
    slo_ok: bool = False
    # Multimodal request (--mm-ratio): encode_ms is the server-side
    # "encoded" span duration pulled from /admin/trace/<id> after the
    # stream finishes — the per-stage latency of the EPD encode plane,
    # 0.0 when the trace was unavailable.
    mm: bool = False
    encode_ms: float = 0.0
    # Service-added latency: request wall time minus the worker-span
    # received→finished interval (same-plane t_mono stamps from
    # /admin/trace/<id>) — what the service plane itself cost this
    # request, as opposed to time the worker spent generating. 0.0 when
    # the trace (or either worker stage) was unavailable.
    service_added_ms: float = 0.0


def _percentile(vals: List[float], p: float) -> float:
    if not vals:
        return 0.0
    s = sorted(vals)
    idx = min(int(round(p / 100.0 * (len(s) - 1))), len(s) - 1)
    return s[idx]


def sample_prompt_lens(n: int, seed: int = 0,
                       mean: int = 64, cap: int = 512) -> List[int]:
    """ShareGPT-like mixed lengths: log-normalish with a long tail."""
    rng = random.Random(seed)
    out = []
    for _ in range(n):
        ln = int(rng.lognormvariate(0, 0.6) * mean)
        out.append(max(4, min(ln, cap)))
    return out


def sample_gen_lens(n: int, seed: int = 0,
                    mean: int = 32, cap: int = 512) -> List[int]:
    """Heavy-tailed output lengths (heavier than the prompt mix: replies
    vary more than prompts in real traces)."""
    rng = random.Random(seed ^ 0x5EED)
    out = []
    for _ in range(n):
        ln = int(rng.lognormvariate(0, 0.9) * mean)
        out.append(max(2, min(ln, cap)))
    return out


def summarize_results(results: List[Optional[RequestResult]],
                      wall_s: float, *, target_ttft_ms: float,
                      target_tpot_ms: float,
                      num_requests: Optional[int] = None) -> dict:
    """One summary dict from a batch of per-request results — the single
    summarization path shared by open-loop ``run_load`` and the
    closed-loop ramp, so goodput and the percentile arithmetic cannot
    drift between harnesses.

    ``goodput_under_slo`` is completed req/s meeting BOTH the TTFT and
    TPOT targets (online tier only — offline is best-effort by design);
    a single-token reply has no TPOT and passes on TTFT alone."""
    done = [r for r in results if r is not None]
    ok = [r for r in done if r.ok]
    shed = [r for r in done if r.shed]
    online = [r for r in ok if not r.offline]
    ttfts = [r.ttft_ms for r in ok]
    tpots = [r.tpot_ms for r in ok if r.tpot_ms > 0]
    for r in done:
        r.slo_ok = (r.ok and not r.offline
                    and r.ttft_ms <= target_ttft_ms
                    and (r.tpot_ms == 0.0
                         or r.tpot_ms <= target_tpot_ms))
    good = sum(1 for r in done if r.slo_ok)
    mm_done = [r for r in ok if r.mm]
    enc = [r.encode_ms for r in mm_done if r.encode_ms > 0]
    extra = {}
    svc = [r.service_added_ms for r in ok if r.service_added_ms > 0]
    if svc:
        # Service-added latency (wall minus the worker received→finished
        # interval): attributes service-plane overhead per request, so a
        # bench can distinguish "the model got slower" from "the master
        # got slower" without a profiler attached.
        extra["service_added_ms"] = {
            "num": len(svc),
            "p50": round(_percentile(svc, 50), 2),
            "p99": round(_percentile(svc, 99), 2),
        }
    if mm_done:
        # Per-stage encode latency of the mixed tier (--mm-ratio): the
        # server-side "encoded" span, so it reflects the EPD stage the
        # scheduler priced, not client-visible TTFT.
        extra["mm"] = {
            "num_ok": len(mm_done),
            "encode_ms": {"p50": round(_percentile(enc, 50), 2),
                          "p99": round(_percentile(enc, 99), 2)},
        }
    return {
        **extra,
        "num_requests": (num_requests if num_requests is not None
                         else len(done)),
        "num_ok": len(ok),
        "num_shed": len(shed),
        "shed_rate": round(len(shed) / max(len(done), 1), 4),
        "num_errors": len(done) - len(ok) - len(shed),
        "wall_s": round(wall_s, 3),
        "req_per_s": round(len(ok) / wall_s, 3) if wall_s > 0 else 0.0,
        "tokens_per_s": round(sum(r.num_tokens for r in ok)
                              / wall_s, 2) if wall_s > 0 else 0.0,
        "goodput_under_slo": round(good / wall_s, 3) if wall_s > 0
        else 0.0,
        "ttft_ms": {"p50": round(_percentile(ttfts, 50), 2),
                    "p99": round(_percentile(ttfts, 99), 2)},
        "tpot_ms": {"p50": round(_percentile(tpots, 50), 2),
                    "p99": round(_percentile(tpots, 99), 2)},
        # SLA attainment of the ONLINE tier only (offline requests are
        # best-effort by design — reference target_ttft/target_tpot
        # flags).
        "online_slo": {
            "ttft": round(sum(1 for r in online
                              if r.ttft_ms <= target_ttft_ms)
                          / max(len(online), 1), 4),
            "tpot": round(sum(1 for r in online if r.tpot_ms > 0
                              and r.tpot_ms <= target_tpot_ms)
                          / max(sum(1 for r in online if r.tpot_ms > 0),
                                1), 4),
        },
    }


def load_sharegpt(path: str, num_requests: int, seed: int = 0,
                  max_output_cap: int = 512) -> List[tuple]:
    """Parse a ShareGPT-format dump (list of ``{"conversations":
    [{"from": "human"|"gpt", "value": ...}, ...]}``) into
    ``(prompt_text, output_len)`` replay pairs (BASELINE.md row 2).

    The first human→gpt exchange of each conversation becomes one request:
    the human turn is replayed verbatim as the prompt; the gpt reply's
    length (chars/4 ≈ tokens) sets that request's ``max_tokens``, so the
    replayed load reproduces the trace's real output-length mix."""
    with open(path, "r", encoding="utf-8") as f:
        data = json.load(f)
    pairs: List[tuple] = []
    for conv in data:
        msgs = conv.get("conversations") or conv.get("messages") or []
        for i in range(len(msgs) - 1):
            role = msgs[i].get("from") or msgs[i].get("role", "")
            nxt = msgs[i + 1].get("from") or msgs[i + 1].get("role", "")
            if role in ("human", "user") and nxt in ("gpt", "assistant"):
                prompt = (msgs[i].get("value")
                          or msgs[i].get("content") or "").strip()
                reply = (msgs[i + 1].get("value")
                         or msgs[i + 1].get("content") or "")
                if prompt and reply:
                    pairs.append((prompt,
                                  max(1, min(len(reply) // 4,
                                             max_output_cap))))
                break
    if not pairs:
        raise ValueError(f"no usable conversations in {path}")
    rng = random.Random(seed)
    rng.shuffle(pairs)
    while len(pairs) < num_requests:
        pairs.extend(pairs)
    return pairs[:num_requests]


def run_one(target: str, model: str, prompt_len: int, max_tokens: int,
            offline: bool, timeout: float,
            prompt_text: Optional[str] = None,
            mm_image: Optional[str] = None) -> RequestResult:
    res = RequestResult(offline=offline, mm=mm_image is not None)
    prompt = prompt_text if prompt_text is not None else \
        " ".join("tok" for _ in range(max(prompt_len // 4, 1)))
    if mm_image is not None:
        # Mixed-traffic tier (--mm-ratio): a chat completion carrying
        # one image, exercising the EPD encode plane end to end.
        path = "/v1/chat/completions"
        body = {
            "model": model, "messages": [{
                "role": "user",
                "content": [
                    {"type": "text", "text": prompt},
                    {"type": "image_url",
                     "image_url": {"url": mm_image}},
                ]}],
            "max_tokens": max_tokens, "temperature": 0.0,
            "ignore_eos": True, "stream": True, "offline": offline,
        }
    else:
        path = "/v1/completions"
        body = {
            "model": model, "prompt": prompt, "max_tokens": max_tokens,
            "temperature": 0.0, "ignore_eos": True, "stream": True,
            "offline": offline,
        }
    rid = ""
    t0 = time.monotonic()
    first = last = 0.0
    tokens = 0
    try:
        status, body_iter = http_stream_status(
            "POST", target, path, body, timeout=timeout)
        if status != 200:
            # Eager status lets shed (429 + Retry-After, bounded
            # admission) be counted apart from real failures.
            raw = b"".join(body_iter)
            res.shed = status == 429
            res.error = ("shed (429)" if res.shed else
                         f"HTTP {status}: "
                         f"{raw[:200].decode('utf-8', 'replace')}")
            return res
        for payload in iter_sse_events(body_iter):
            if payload == "[DONE]":
                break
            now = time.monotonic()
            obj = json.loads(payload)
            if obj.get("error"):
                res.error = str(obj["error"])
                return res
            if not rid:
                rid = str(obj.get("id", ""))
            if not obj.get("choices"):
                continue
            if first == 0.0:
                first = now
            last = now
            tokens += 1
    except Exception as e:  # noqa: BLE001
        res.error = str(e)
        return res
    if first == 0.0:
        res.error = "no tokens"
        return res
    res.ok = True
    res.ttft_ms = 1000.0 * (first - t0)
    res.total_ms = 1000.0 * (last - t0)
    res.num_tokens = tokens
    if tokens > 1:
        res.tpot_ms = 1000.0 * (last - first) / (tokens - 1)
    if rid:
        # One best-effort trace fetch serves two per-stage reports: the
        # mm tier's server-side "encoded" duration, and — for every
        # completed stream — the worker-plane received→finished
        # interval behind service_added_ms. Worker stages ride a
        # heartbeat, so give the fetch one short retry.
        for _ in range(2):
            try:
                status, span = http_json(
                    "GET", target, f"/admin/trace/{rid}", None,
                    timeout=10.0)
            except Exception:  # noqa: BLE001 — reports stay 0.0
                break
            if status == 200:
                events = span.get("events", [])
                if res.mm and not res.encode_ms:
                    enc = [e for e in events
                           if e.get("stage") == "encoded"]
                    if enc:
                        res.encode_ms = float(
                            enc[0].get("ms", 0.0) or 0.0)
                # Same-plane monotonic stamps: the worker's own clock
                # bounds its generation interval; wall minus that is
                # what the service plane added (relay, scheduling,
                # SSE assembly, queueing).
                w = {e.get("stage"): e.get("t_mono")
                     for e in events if e.get("plane") == "worker"
                     and isinstance(e.get("t_mono"), (int, float))}
                if "received" in w and "finished" in w \
                        and w["finished"] >= w["received"]:
                    worker_ms = 1000.0 * (w["finished"] - w["received"])
                    res.service_added_ms = max(
                        res.total_ms - worker_ms, 0.0)
                    if not res.mm or res.encode_ms:
                        break
            time.sleep(0.5)
    return res


def parse_chaos(spec: str) -> List[tuple]:
    """Parse a ``--chaos`` schedule:
    ``name[=mode[:arg[:value]]]@start+duration[,...]`` — e.g.
    ``store.partition@10+15`` arms the ``store.partition`` failpoint
    (mode ``always``) 10 s into the run and disarms it 15 s later;
    ``worker.fault_step=prob:0.2@5+10`` makes ~1 in 5 engine steps
    fault for 10 s, and ``worker.fault_step_req=always:POISON@5+10``
    faults every step whose batch holds a prompt containing "POISON"
    (the poison-pill drill — docs/ROBUSTNESS.md device-plane fault
    contract). ``worker.*`` names broadcast to every registered worker
    via the admin proxy's ``{"instance": "*"}``. Returns
    ``(name_or_spec, start_s, duration_s)`` tuples sorted by start."""
    stages: List[tuple] = []
    for part in spec.split(","):
        part = part.strip()
        if not part:
            continue
        name, _, when = part.partition("@")
        start_s, _, dur_s = when.partition("+")
        if not name or not start_s or not dur_s:
            raise ValueError(
                f"bad chaos stage {part!r}; want "
                f"name[=mode[:arg]]@start+duration")
        stages.append((name, float(start_s), float(dur_s)))
    return sorted(stages, key=lambda s: s[1])


def _arm_failpoint(target: str, spec: str) -> None:
    body: dict = {"spec": spec}
    if spec.startswith("worker."):
        # Worker-plane sites live behind the admin proxy; "*" asks the
        # service to arm every registered worker.
        body["instance"] = "*"
    status, resp = http_json("POST", target, "/admin/failpoint",
                             body, timeout=5.0)
    if status != 200:
        raise RuntimeError(f"failpoint {spec!r} -> {status}: {resp}")


def _fault_counters(target: str) -> dict:
    """Scrape the service /metrics for the device-plane fault ledger:
    contained engine faults (``xllm_events_total{type="engine_fault"}``
    — one per blame verdict struck at the fan-in) and poisoned
    requests (``xllm_requests_poisoned_total``). Best-effort: a target
    mid-blackout reports zeros."""
    import http.client
    host, _, port = target.partition(":")
    out = {"engine_fault_events": 0.0, "poisoned_requests": 0.0}
    try:
        conn = http.client.HTTPConnection(host, int(port or 80),
                                          timeout=5.0)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode("utf-8", "replace")
        conn.close()
    except Exception:  # noqa: BLE001 — scrape is advisory
        return out
    for line in text.splitlines():
        if line.startswith('xllm_events_total{type="engine_fault"}'):
            out["engine_fault_events"] = float(line.rsplit(" ", 1)[-1])
        elif line.startswith("xllm_requests_poisoned_total"):
            out["poisoned_requests"] = float(line.rsplit(" ", 1)[-1])
    return out


def _mixed_step_counters(target: str) -> dict:
    """Scrape the worker-plane mixed-step ledger: ragged one-dispatch
    mixed iterations (``xllm_worker_ragged_dispatches_total``,
    XLLM_RAGGED_ATTN) vs all mixed iterations
    (``xllm_worker_steps_total{phase="mixed"}``). Best-effort like the
    fault-ledger scrape: a target that exports no worker metrics
    reports zeros."""
    import http.client
    host, _, port = target.partition(":")
    out = {"ragged_dispatches": 0.0, "mixed_steps": 0.0}
    try:
        conn = http.client.HTTPConnection(host, int(port or 80),
                                          timeout=5.0)
        conn.request("GET", "/metrics")
        text = conn.getresponse().read().decode("utf-8", "replace")
        conn.close()
    except Exception:  # noqa: BLE001 — scrape is advisory
        return out
    for line in text.splitlines():
        if line.startswith("xllm_worker_ragged_dispatches_total"):
            out["ragged_dispatches"] += float(line.rsplit(" ", 1)[-1])
        elif line.startswith("xllm_worker_steps_total{") and \
                'phase="mixed"' in line:
            out["mixed_steps"] += float(line.rsplit(" ", 1)[-1])
    return out


def run_chaos_schedule(target: str, stages: List[tuple], t_start: float,
                       stop: threading.Event) -> None:
    """Arm each scheduled failpoint against the live service's admin
    plane at its start offset, disarm at start+duration. Disarms are
    best-effort even on abort so a cancelled run can't leave the
    service blacked out."""
    for name, start_s, dur_s in stages:
        if stop.wait(max(0.0, t_start + start_s - time.monotonic())):
            return
        base = name.split("=", 1)[0]
        try:
            _arm_failpoint(target,
                           name if "=" in name else f"{name}=always")
        except Exception as e:  # noqa: BLE001 — a dead target ends the
            print(f"chaos: arming {name} failed: {e}")  # schedule only
            continue
        try:
            stop.wait(max(0.0, t_start + start_s + dur_s
                          - time.monotonic()))
        finally:
            try:
                _arm_failpoint(target, f"{base}=off")
            except Exception as e:  # noqa: BLE001
                print(f"chaos: disarming {name} failed: {e}")
        if stop.is_set():
            return


def chaos_stage_summaries(results: List[Optional[RequestResult]],
                          chaos: List[tuple], wall_s: float, *,
                          target_ttft_ms: float,
                          target_tpot_ms: float) -> dict:
    """Split results into pre/during/post stages by each request's
    START offset against the chaos windows, and push every stage
    through the one shared ``summarize_results`` path so the blackout
    stage's goodput/shed numbers are computed exactly like the
    steady-state ones. ``recovery_s`` is the gap between the last
    window closing and the first post-stage request completing."""
    windows = [(s, s + d) for _, s, d in chaos]
    first_start = windows[0][0]
    last_end = max(e for _, e in windows)
    pre: List[RequestResult] = []
    during: List[RequestResult] = []
    post: List[RequestResult] = []
    for r in results:
        if r is None:
            continue
        if any(a <= r.started_s < b for a, b in windows):
            during.append(r)
        elif r.started_s < first_start:
            pre.append(r)
        else:
            post.append(r)

    def summ(rs: List[RequestResult], span_s: float) -> dict:
        return summarize_results(list(rs), max(span_s, 1e-9),
                                 target_ttft_ms=target_ttft_ms,
                                 target_tpot_ms=target_tpot_ms)

    recoveries = [r.started_s + r.total_ms / 1000.0 - last_end
                  for r in post if r.ok]
    return {
        "schedule": [{"name": n, "start_s": s, "duration_s": d}
                     for n, s, d in chaos],
        "pre": summ(pre, first_start),
        "during": summ(during, sum(d for _, _, d in chaos)),
        "post": summ(post, max(wall_s - last_end, 1e-9)),
        "recovery_s": (round(min(recoveries), 3) if recoveries
                       else None),
    }


def run_load(target: str, model: str, num_requests: int,
             request_rate: float, max_tokens: int,
             offline_fraction: float = 0.0, seed: int = 0,
             timeout: float = 600.0, mean_prompt_len: int = 64,
             target_ttft_ms: float = 1000.0,
             target_tpot_ms: float = 50.0,
             sharegpt_path: Optional[str] = None,
             chaos: Optional[List[tuple]] = None,
             mm_ratio: float = 0.0) -> dict:
    if sharegpt_path:
        # Trace replay: real prompts + real per-request output lengths.
        plan = [(None, text, out_len) for text, out_len in
                load_sharegpt(sharegpt_path, num_requests, seed)]
    else:
        plan = [(plen, None, max_tokens) for plen in
                sample_prompt_lens(num_requests, seed,
                                   mean=mean_prompt_len)]
    rng = random.Random(seed + 1)
    results: List[Optional[RequestResult]] = [None] * num_requests
    threads: List[threading.Thread] = []
    t_start = time.monotonic()
    chaos_stop = threading.Event()
    chaos_th: Optional[threading.Thread] = None
    faults_before: Optional[dict] = None
    mixed_before = _mixed_step_counters(target)
    if chaos:
        faults_before = _fault_counters(target)
        chaos_th = threading.Thread(
            target=run_chaos_schedule,
            args=(target, chaos, t_start, chaos_stop), daemon=True)
        chaos_th.start()

    def fire(i: int, plen, text, mt: int, off: bool,
             image: Optional[str]) -> None:
        started = time.monotonic() - t_start
        r = run_one(target, model, plen or 0, mt, off, timeout,
                    prompt_text=text, mm_image=image)
        r.started_s = started
        results[i] = r

    for i, (plen, text, mt) in enumerate(plan):
        off = rng.random() < offline_fraction
        # Mixed text/image traffic: a small seed pool so repeat images
        # exercise the encode plane's embedding cache, not only misses.
        image = (f"random:{rng.randrange(8)}"
                 if rng.random() < mm_ratio else None)
        th = threading.Thread(target=fire,
                              args=(i, plen, text, mt, off, image),
                              daemon=True)
        threads.append(th)
        th.start()
        if request_rate > 0:
            # Poisson arrivals at the requested rate.
            time.sleep(rng.expovariate(request_rate))
    for th in threads:
        th.join(timeout=timeout)
    wall = time.monotonic() - t_start
    if chaos_th is not None:
        chaos_stop.set()
        chaos_th.join(timeout=10.0)

    summary = summarize_results(results, wall,
                                target_ttft_ms=target_ttft_ms,
                                target_tpot_ms=target_tpot_ms,
                                num_requests=num_requests)
    # Mixed-step ledger across the run (delta of the worker counters):
    # how many interleaved iterations ran, and how many of those went
    # through the single ragged dispatch (XLLM_RAGGED_ATTN).
    mixed_after = _mixed_step_counters(target)
    ms = mixed_after["mixed_steps"] - mixed_before["mixed_steps"]
    rd = mixed_after["ragged_dispatches"] - \
        mixed_before["ragged_dispatches"]
    summary["mixed_step"] = {
        "mixed_steps": int(ms), "ragged_dispatches": int(rd),
        "ragged_share": round(rd / ms, 4) if ms > 0 else None}
    if chaos:
        summary["chaos"] = chaos_stage_summaries(
            results, chaos, wall, target_ttft_ms=target_ttft_ms,
            target_tpot_ms=target_tpot_ms)
        # Device-plane fault ledger across the run (delta of the
        # service counters — docs/ROBUSTNESS.md): blame verdicts
        # struck and requests failed as poison pills.
        after = _fault_counters(target)
        summary["chaos"]["contained_faults"] = int(
            after["engine_fault_events"]
            - (faults_before or {}).get("engine_fault_events", 0.0))
        summary["chaos"]["poisoned_requests"] = int(
            after["poisoned_requests"]
            - (faults_before or {}).get("poisoned_requests", 0.0))
    return summary


def run_closed_loop(target: str, model: str, *,
                    stages: Sequence[int] = (1, 2, 4),
                    requests_per_stage: int = 8,
                    mean_prompt_len: int = 64,
                    mean_output_len: int = 32, seed: int = 0,
                    target_ttft_ms: float = 1000.0,
                    target_tpot_ms: float = 50.0,
                    timeout: float = 600.0) -> dict:
    """Closed-loop goodput-under-SLO harness.

    Each stage holds ``concurrency`` requests in flight — a worker fires
    its next request the moment the previous one completes — and the
    stage list ramps concurrency, so offered load tracks what the stack
    actually absorbs instead of an open-loop arrival rate it may never
    keep up with. Prompt AND output lengths are heavy-tailed. The last
    (highest-concurrency) stage is the burst: its percentiles become
    the summary's ``ttft_ms_p99`` / ``tpot_ms_p99_under_burst``, the
    numbers a TPOT-bounding interleaver is supposed to hold down while
    the burst's prompts prefill."""
    stage_summaries: List[dict] = []
    all_results: List[RequestResult] = []
    t0 = time.monotonic()
    for si, conc in enumerate(stages):
        plan = list(zip(
            sample_prompt_lens(requests_per_stage, seed + si,
                               mean=mean_prompt_len),
            sample_gen_lens(requests_per_stage, seed + si,
                            mean=mean_output_len)))
        results: List[RequestResult] = []
        lock = threading.Lock()

        def worker() -> None:
            while True:
                with lock:
                    if not plan:
                        return
                    plen, glen = plan.pop()
                r = run_one(target, model, plen, glen, False, timeout)
                with lock:
                    results.append(r)

        threads = [threading.Thread(target=worker, daemon=True)
                   for _ in range(conc)]
        st0 = time.monotonic()
        for th in threads:
            th.start()
        for th in threads:
            th.join(timeout=timeout)
        s = summarize_results(results, time.monotonic() - st0,
                              target_ttft_ms=target_ttft_ms,
                              target_tpot_ms=target_tpot_ms)
        s["concurrency"] = conc
        stage_summaries.append(s)
        all_results.extend(results)
    overall = summarize_results(all_results, time.monotonic() - t0,
                                target_ttft_ms=target_ttft_ms,
                                target_tpot_ms=target_tpot_ms)
    burst = stage_summaries[-1]
    overall.update(
        mode="closed_loop",
        stages=stage_summaries,
        ttft_ms_p99=burst["ttft_ms"]["p99"],
        tpot_ms_p99_under_burst=burst["tpot_ms"]["p99"],
    )
    return overall


def fetch_timeline(target: str, path: str,
                   seconds: float) -> Dict[str, Any]:
    """Pull the master's cluster-merged chrome-trace document and write
    it as a run artifact: the per-request flow chains and per-step
    engine slices behind this run's latency percentiles. Returns the
    summary subdict ({"path", "events", "instances"}, or an "error"
    entry — a missing timeline must not fail the load run)."""
    try:
        status, trace = http_json(
            "GET", target, f"/admin/timeline?seconds={seconds:g}",
            timeout=30.0)
    except Exception as e:  # noqa: BLE001 — artifact is best-effort
        return {"path": path, "error": str(e)}
    if status != 200 or not isinstance(trace, dict):
        return {"path": path, "error": f"status {status}"}
    with open(path, "w", encoding="utf-8") as f:
        json.dump(trace, f, sort_keys=True, separators=(",", ":"))
    meta = trace.get("metadata") or {}
    return {"path": path,
            "events": len(trace.get("traceEvents", [])),
            "instances": list(meta.get("instances", []))}


def main(argv: Optional[List[str]] = None) -> int:
    ap = argparse.ArgumentParser(description="xllm-service-tpu loadgen")
    ap.add_argument("--target", required=True, help="host:port of service")
    ap.add_argument("--model", default="tiny")
    ap.add_argument("--num-requests", type=int, default=32)
    ap.add_argument("--request-rate", type=float, default=4.0,
                    help="Poisson arrival rate (req/s); 0 = all at once")
    ap.add_argument("--max-tokens", type=int, default=32)
    ap.add_argument("--mean-prompt-len", type=int, default=64)
    ap.add_argument("--offline-fraction", type=float, default=0.0)
    ap.add_argument("--mm-ratio", type=float, default=0.0,
                    help="fraction of requests carrying an image "
                         "(chat-completion tier through the EPD encode "
                         "plane); summary gains mm.encode_ms "
                         "percentiles from the server-side encoded "
                         "span (open-loop only)")
    ap.add_argument("--target-ttft-ms", type=float, default=1000.0)
    ap.add_argument("--target-tpot-ms", type=float, default=50.0)
    ap.add_argument("--sharegpt", default="",
                    help="path to a ShareGPT-format JSON dump to replay "
                         "(real prompts + output-length mix)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--closed-loop", action="store_true",
                    help="concurrency-ramp closed loop (goodput-under-"
                         "SLO harness) instead of open-loop arrivals")
    ap.add_argument("--stages", default="1,2,4",
                    help="closed-loop concurrency ramp; the last stage "
                         "is the burst")
    ap.add_argument("--requests-per-stage", type=int, default=8)
    ap.add_argument("--mean-output-len", type=int, default=32)
    ap.add_argument("--chaos", default="",
                    help="failpoint schedule armed mid-run against the "
                         "target's admin plane: 'name@start+duration"
                         "[,...]', e.g. 'store.partition@10+15' "
                         "(open-loop only); summary gains per-stage "
                         "pre/during/post goodput + shed + recovery_s")
    ap.add_argument("--timeline", default="",
                    help="after the run, fetch the master's cluster-"
                         "merged GET /admin/timeline and write the "
                         "chrome://tracing-loadable JSON here "
                         "(validate/summarize with tools/trace_view.py)"
                         "; summary gains a timeline subdict")
    ap.add_argument("--timeline-seconds", type=float, default=120.0,
                    help="merge window for the --timeline fetch")
    args = ap.parse_args(argv)

    if args.chaos and args.closed_loop:
        ap.error("--chaos requires the open-loop harness")
    if args.mm_ratio and args.closed_loop:
        ap.error("--mm-ratio requires the open-loop harness")

    if args.closed_loop:
        summary = run_closed_loop(
            args.target, args.model,
            stages=tuple(int(x) for x in args.stages.split(",") if x),
            requests_per_stage=args.requests_per_stage,
            mean_prompt_len=args.mean_prompt_len,
            mean_output_len=args.mean_output_len, seed=args.seed,
            target_ttft_ms=args.target_ttft_ms,
            target_tpot_ms=args.target_tpot_ms)
    else:
        summary = run_load(
            args.target, args.model, args.num_requests,
            args.request_rate, args.max_tokens, args.offline_fraction,
            args.seed, mean_prompt_len=args.mean_prompt_len,
            target_ttft_ms=args.target_ttft_ms,
            target_tpot_ms=args.target_tpot_ms,
            sharegpt_path=args.sharegpt or None,
            chaos=parse_chaos(args.chaos) if args.chaos else None,
            mm_ratio=args.mm_ratio)
    if args.timeline:
        summary["timeline"] = fetch_timeline(
            args.target, args.timeline, args.timeline_seconds)
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
