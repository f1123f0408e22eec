#!/usr/bin/env python3
"""The quickest proof that the serving path starts and answers on the chip.

    python chip_smoke.py               # one TPU chip: preflight, parity, serve
    python chip_smoke.py --four-chip   # four chips: replicas, pd, tp — only
    python chip_smoke.py --rehearse [--four-chip]   # CPU, tiny, never "ok"

Default run, one chip, llama3-1b at full width and depth, seeded random
weights:

- *preflight* (no JAX): versions from package metadata, where the compile
  cache lives, native artefacts built from ``csrc/``.
- *parity* (own process): one prefill and several decode steps through
  ``models/transformer.py``, once on the path the chip serves by default
  (Pallas decode attention and the in-place KV writers, not interpreted)
  and once on the XLA reference path of ``ops/attention.py``; logits are
  compared under ``PARITY_TOL``. The same process reads
  ``memory_analysis()`` of the engine's decode and prefill step programs
  at the serve configuration.
- *serve*: coordination store + ``service.master`` + ``runtime.worker``
  started as README "Run it" starts them, five request checks over
  ``/v1/chat/completions``, then the worker's own report of its compiles,
  its device and its memory.

The last line of standard output is one JSON object,
``{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}``,
with the device as the WORKER reported the devices its engine lives on.
Any failed check raises and the exit code is non-zero; on a device that
is not a TPU the script fails and never prints ``"ok": true``.

One process may hold a chip. This parent never imports JAX; every phase
that needs the chip is a child process that has exited before the next
one starts, and every process started here is stopped before the script
ends. Logs of the children land under ``chiprun_out/chip_smoke/``.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import http.client
import json
import math
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
from typing import Any, Dict, List, Optional, Sequence, Tuple

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT = os.path.join(ROOT, "chiprun_out", "chip_smoke")

# The repo's own helpers (none of them imports JAX). In a directory that
# holds this script and nothing else of the repo, this is where it fails.
sys.path.insert(0, ROOT)
from xllm_service_tpu.obs.expfmt import parse_exposition  # noqa: E402
from xllm_service_tpu.utils import pick_free_port  # noqa: E402

# The serve configuration: the worker CLI's own defaults (page 128, len
# 2048, batch 8) and the largest pool that the offline compile for the
# described v5e showed to fit beside the weights (tests/test_chip_compile.py
# and CHANGES.md PR 22 carry the arithmetic): 1024 pages = 131072 tokens
# of KV, 8.59 GB pinned (the row-major pool pads head_dim 64 to the
# 128-lane tile, twice its nominal 4.29 GB), 2.47 GB of weights, 0.43 GB
# of temporaries in the largest prefill program.
MODEL = "llama3-1b"
PAGE_SIZE, NUM_PAGES, MAX_LEN, BATCH = 128, 1024, 2048, 8

# Parity tolerance, on logits (float32, standard deviation 1.00 with the
# seeded weights): max |served - reference| <= PARITY_TOL * std(ref).
# Both paths compute in bfloat16 with float32 accumulation but in a
# different order (online softmax over page blocks against one softmax
# over the gathered context, attention output rounded to bfloat16 in
# each of 16 layers). On a v5e at llama3-1b widths the worst of 2 x
# 128256 logits moved by 0.069 of the spread at every decode step, and
# the prefill (same XLA attention on both paths, only the writer
# differs) by 0.000 (chip run, PR 22); the bound is about twice that. A
# page read from the wrong offset, a KV row written to the wrong slot or
# a kernel that is only right under the interpreter replaces whole
# attention inputs: the run repeats one step with a deliberately wrong
# page table, prints that ratio beside the real one (5.1 in the same
# run, 34 times the bound) and requires it to fail the bound fourfold.
PARITY_TOL = 0.15

# Tokens of the prefix-cache repeat must be the same; their logprobs may
# differ by the arithmetic of a shorter prefill window over cached pages.
REPEAT_LOGPROB_TOL = 0.05


class SmokeFailure(Exception):
    pass


def say(msg: str) -> None:
    """To standard output and, whole, to <OUT>/smoke.log: the chip tool
    shows only the end of a long output."""
    print(msg, flush=True)
    os.makedirs(OUT, exist_ok=True)
    with open(os.path.join(OUT, "smoke.log"), "a") as f:
        f.write(msg + "\n")


def check(cond: bool, what: str, quiet: bool = False) -> None:
    if not cond:
        raise SmokeFailure(what)
    if not quiet:
        say(f"  ok: {what}")


# ---------------------------------------------------------------------------
# Small HTTP and process helpers. Requests go through the standard
# library's client, not the package's: what the smoke checks is what a
# user's client would see.
# ---------------------------------------------------------------------------

def http_get(addr: str, path: str, timeout: float = 30.0) -> Tuple[int, bytes]:
    host, port = addr.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    try:
        conn.request("GET", path)
        resp = conn.getresponse()
        return resp.status, resp.read()
    finally:
        conn.close()


def get_json(addr: str, path: str, timeout: float = 30.0) -> Any:
    status, body = http_get(addr, path, timeout)
    if status != 200:
        raise SmokeFailure(f"GET {addr}{path} -> {status}: {body[:200]!r}")
    return json.loads(body)


def scrape(addr: str) -> List[Tuple[str, Dict[str, str], float]]:
    """``/metrics`` as (family, labels, value) samples, read with the
    repo's own exposition parser; a malformed exposition fails."""
    status, body = http_get(addr, "/metrics")
    if status != 200:
        raise SmokeFailure(f"GET {addr}/metrics -> {status}")
    samples, _types, errors = parse_exposition(body.decode())
    if errors:
        raise SmokeFailure(f"{addr}/metrics does not parse: {errors[:3]}")
    return samples


def metric_sum(samples, family: str, **labels: str) -> float:
    return sum(v for name, lab, v in samples if name == family
               and all(lab.get(k) == want for k, want in labels.items()))


class Answer:
    """One chat completion as the client saw it."""

    def __init__(self) -> None:
        self.status = 0
        self.text = ""
        self.tokens: List[str] = []
        self.logprobs: List[float] = []
        self.completion_tokens = -1
        self.prompt_tokens = -1
        self.seconds = 0.0
        self.error = ""

    def same_tokens(self, other: "Answer") -> bool:
        return self.text == other.text and self.tokens == other.tokens \
            and len(self.logprobs) == len(other.logprobs)

    def max_logprob_gap(self, other: "Answer") -> float:
        return max((abs(a - b) for a, b in
                    zip(self.logprobs, other.logprobs)), default=0.0)


def chat(addr: str, prompt: str, max_tokens: int, stream: bool = False,
         rid: str = "", model: str = "", timeout: float = 900.0) -> Answer:
    """POST /v1/chat/completions at temperature 0 and read the whole
    answer. ``logprobs`` is on so that two answers can be compared token
    by token: with seeded random weights most sampled ids decode to no
    text at all, and the chosen token's logprob is what tells them apart."""
    body = {"model": model or SERVED["model"],
            "messages": [{"role": "user", "content": prompt}],
            "max_tokens": max_tokens, "temperature": 0, "ignore_eos": True,
            "logprobs": True, "stream": stream}
    if stream:
        body["stream_options"] = {"include_usage": True}
    headers = {"Content-Type": "application/json"}
    if rid:
        headers["x-request-id"] = rid
    ans = Answer()
    host, port = addr.rsplit(":", 1)
    conn = http.client.HTTPConnection(host, int(port), timeout=timeout)
    t0 = time.monotonic()
    try:
        conn.request("POST", "/v1/chat/completions",
                     json.dumps(body).encode(), headers)
        resp = conn.getresponse()
        ans.status = resp.status
        if resp.status != 200:
            ans.error = resp.read()[:300].decode(errors="replace")
            return ans
        if not stream:
            doc = json.loads(resp.read())
            _take_choice(ans, doc["choices"][0], "message")
            _take_usage(ans, doc.get("usage"))
        else:
            for raw in resp:
                line = raw.decode().strip()
                if not line.startswith("data:"):
                    continue
                data = line[5:].strip()
                if data == "[DONE]":
                    break
                doc = json.loads(data)
                if doc.get("error"):
                    ans.error = json.dumps(doc["error"])[:300]
                    break
                for choice in doc.get("choices") or []:
                    _take_choice(ans, choice, "delta")
                _take_usage(ans, doc.get("usage"))
    finally:
        conn.close()
        ans.seconds = time.monotonic() - t0
    return ans


def _take_choice(ans: Answer, choice: Dict[str, Any], key: str) -> None:
    ans.text += (choice.get(key) or {}).get("content") or ""
    for entry in (choice.get("logprobs") or {}).get("content") or []:
        ans.tokens.append(entry.get("token", ""))
        ans.logprobs.append(float(entry["logprob"]))


def _take_usage(ans: Answer, usage: Optional[Dict[str, Any]]) -> None:
    if usage:
        ans.completion_tokens = int(usage.get("completion_tokens", -1))
        ans.prompt_tokens = int(usage.get("prompt_tokens", -1))


def check_answer(ans: Answer, max_tokens: int, what: str,
                 n_logprobs: Optional[int] = None,
                 quiet: bool = False) -> None:
    """200, completion_tokens == max_tokens, one finite logprob a token."""
    check(ans.status == 200 and not ans.error
          and ans.completion_tokens == max_tokens
          and len(ans.logprobs) == (max_tokens if n_logprobs is None
                                    else n_logprobs)
          and all(math.isfinite(x) and x <= 0.0 for x in ans.logprobs),
          f"{what}: HTTP {ans.status} {ans.error}, completion_tokens "
          f"{ans.completion_tokens} of max_tokens {max_tokens}, "
          f"{len(ans.logprobs)} finite logprobs, prompt_tokens "
          f"{ans.prompt_tokens}, {ans.seconds:.2f} s", quiet)


SERVED = {"model": MODEL}     # "tiny" under --rehearse


class Procs:
    """Every process this script starts; all are stopped on the way out,
    whatever happened."""

    def __init__(self) -> None:
        self._procs: List[Tuple[str, subprocess.Popen, Any]] = []
        os.makedirs(OUT, exist_ok=True)

    def start(self, name: str, argv: Sequence[str],
              env: Optional[Dict[str, str]] = None,
              capture: bool = False) -> subprocess.Popen:
        """Start ``argv``; its output goes to ``<OUT>/<name>.log`` (or,
        with ``capture``, standard output comes back on a pipe)."""
        log = open(os.path.join(OUT, f"{name}.log"), "w")
        proc = subprocess.Popen(
            list(argv), cwd=ROOT, env=env or dict(os.environ),
            stdout=subprocess.PIPE if capture else log,
            stderr=log, text=True, start_new_session=True)
        self._procs.append((name, proc, log))
        return proc

    def stop(self, proc: subprocess.Popen, grace_s: float = 20.0) -> None:
        if proc.poll() is None:
            proc.send_signal(signal.SIGTERM)
            try:
                proc.wait(grace_s)
            except subprocess.TimeoutExpired:
                os.killpg(proc.pid, signal.SIGKILL)
                proc.wait(10)

    def stop_all(self) -> None:
        for _name, proc, _log in reversed(self._procs):
            with contextlib.suppress(ProcessLookupError):
                self.stop(proc)
        for _name, _proc, log in self._procs:
            log.close()
        self._procs.clear()

    def log_tail(self, name: str, n: int = 25) -> str:
        try:
            with open(os.path.join(OUT, f"{name}.log")) as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def __enter__(self) -> "Procs":
        return self

    def __exit__(self, *exc) -> None:
        self.stop_all()


def py(*args: str) -> List[str]:
    return [sys.executable, *args]


def run_child(procs: Procs, phase: str, rehearse: bool,
              env: Optional[Dict[str, str]] = None,
              extra: Sequence[str] = ()) -> List[str]:
    """Run one JAX phase of this script as a child, relay what it prints,
    and return its lines. A child that fails fails the smoke."""
    argv = py(os.path.abspath(__file__), "--child", phase, *extra)
    if rehearse:
        argv.append("--rehearse")
    proc = procs.start(f"child-{phase}", argv, env=env, capture=True)
    lines = []
    assert proc.stdout is not None
    for line in proc.stdout:
        lines.append(line.rstrip("\n"))
        say(f"  [{phase}] {lines[-1]}")
    rc = proc.wait()
    if rc != 0:
        raise SmokeFailure(
            f"{phase} child exited {rc}:\n"
            + procs.log_tail(f"child-{phase}"))
    return lines


# ---------------------------------------------------------------------------
# The cluster, started as README "Run it" starts it.
# ---------------------------------------------------------------------------

class Cluster:
    """One coordination store and one master on loopback, and the workers
    registered with them."""

    def __init__(self, procs: Procs, tag: str, rehearse: bool,
                 policy: str = "CAR") -> None:
        self.procs, self.tag, self.rehearse = procs, tag, rehearse
        self.workers: Dict[str, Dict[str, Any]] = {}
        store_port = pick_free_port()
        self.store_addr = f"127.0.0.1:{store_port}"
        procs.start(f"{tag}-store", py(
            "-m", "xllm_service_tpu.service.coordination_net",
            "--port", str(store_port)))
        _wait_port(store_port, 30, f"{tag}-store")
        master = procs.start(f"{tag}-master", py(
            "-m", "xllm_service_tpu.service.master", "--host", "127.0.0.1",
            "--http-port", "0", "--rpc-port", "0",
            "--etcd-addr", self.store_addr,
            "--load-balance-policy", policy), capture=True)
        assert master.stdout is not None
        line = ""
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            line = master.stdout.readline()
            if not line or line.startswith("XLLM_SERVICE_UP"):
                break
        m = re.search(r"http=(\S+) rpc=(\S+)", line or "")
        if not m:
            raise SmokeFailure(f"{tag}-master did not come up: "
                               + procs.log_tail(f"{tag}-master"))
        self.http, self.rpc = m.group(1), m.group(2)
        # Keep draining the pipe so the master can never block on it.
        threading.Thread(target=lambda: [None for _ in master.stdout],
                         daemon=True).start()

    def start_worker(self, name: str, extra: Sequence[str] = (),
                     env: Optional[Dict[str, str]] = None,
                     model: Optional[str] = None) -> Dict[str, Any]:
        port = pick_free_port()
        w = {"name": name, "addr": f"127.0.0.1:{port}",
             "t_spawn": time.monotonic(),
             "model": model or SERVED["model"]}
        w["proc"] = self.procs.start(f"{self.tag}-{name}", py(
            "-m", "xllm_service_tpu.runtime.worker",
            "--port", str(port), "--store-addr", self.store_addr,
            "--service-addr", self.rpc, "--model", w["model"],
            "--num-pages", str(NUM_PAGES), *extra),
            env=worker_env(self.rehearse, env))
        self.workers[name] = w
        return w

    def wait_registered(self, names: Sequence[str],
                        timeout_s: float) -> None:
        """Until the master has confirmed each named worker. A worker
        process that dies first fails the smoke with its log."""
        deadline = time.monotonic() + timeout_s
        pending = set(names)
        while pending:
            events = get_json(self.http, "/admin/events?since=0&limit=4096")
            confirmed = {e["attrs"].get("instance")
                         for e in events["events"]
                         if e["type"] == "instance_confirm"}
            for n in sorted(pending):
                w = self.workers[n]
                if w["addr"] in confirmed:
                    w["registered_s"] = time.monotonic() - w["t_spawn"]
                    pending.discard(n)
                elif w["proc"].poll() is not None:
                    raise SmokeFailure(
                        f"worker {n} exited {w['proc'].returncode} before "
                        f"registering:\n"
                        + self.procs.log_tail(f"{self.tag}-{n}", 40))
            if pending and time.monotonic() > deadline:
                raise SmokeFailure(
                    f"workers {sorted(pending)} not registered after "
                    f"{timeout_s:.0f} s:\n" + "".join(
                        self.procs.log_tail(f"{self.tag}-{n}", 15)
                        for n in sorted(pending)))
            if pending:
                time.sleep(0.25)

    def served_by(self, rid: str) -> str:
        """The instance(s) the master scheduled request ``rid`` onto,
        read back from its span: "prefill>decode" addresses."""
        doc = get_json(self.http, f"/admin/trace/{rid}")
        dec = _find_key(doc, "schedule_decision") or {}
        pre = (dec.get("prefill") or {}).get("winner") or ""
        de = (dec.get("decode") or {}).get("winner") or ""
        return f"{pre}>{de}"


def _find_key(doc: Any, key: str) -> Any:
    if isinstance(doc, dict):
        if key in doc:
            return doc[key]
        doc = list(doc.values())
    if isinstance(doc, list):
        for item in doc:
            found = _find_key(item, key)
            if found is not None:
                return found
    return None


def _wait_port(port: int, timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        with socket.socket() as s:
            s.settimeout(0.5)
            if s.connect_ex(("127.0.0.1", port)) == 0:
                return
        time.sleep(0.1)
    raise SmokeFailure(f"{what} never listened on port {port}")


def worker_env(rehearse: bool,
               extra: Optional[Dict[str, str]] = None) -> Dict[str, str]:
    env = dict(os.environ)
    # The default warm-up compiles 43 programs at about 25 s each before
    # the first request, which does not fit a cold chip call; the
    # existing switch keeps the boot warm-up to its first three and the
    # smoke REPORTS the programs compiled lazily after registration
    # instead of claiming zero.
    env["XLLM_WARMUP_EXTENDED"] = "0"
    if rehearse:
        env["JAX_PLATFORMS"] = "cpu"
    env.update(extra or {})
    return env


def one_chip_env(chip: int) -> Dict[str, str]:
    """Confine a process to chip ``chip`` of the host before JAX starts:
    libtpu reads these at load. Both spellings of the visibility list are
    set (TPU_VISIBLE_CHIPS is the current one, TPU_VISIBLE_DEVICES the
    older; libtpu 0.0.34 on the v5e 2x2 host honoured either one alone,
    four processes at once, each seeing one device with id 0). The
    process bounds describe a one-chip topology of its own, as JAX's own
    multi-process launcher sets them."""
    return {"TPU_VISIBLE_CHIPS": str(chip), "TPU_VISIBLE_DEVICES": str(chip),
            "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
            "TPU_PROCESS_BOUNDS": "1,1,1",
            "ALLOW_MULTIPLE_LIBTPU_LOAD": "1"}


def worker_report(w: Dict[str, Any]) -> Dict[str, Any]:
    """What a worker says about itself: GET /admin/steptrace (device,
    pin, memory) and /metrics (compile census, liveness)."""
    st = get_json(w["addr"], "/admin/steptrace?n=1")
    m = scrape(w["addr"])
    compiles = {lab["program"]: int(v) for name, lab, v in m
                if name == "xllm_worker_jit_compiles_total" and v}
    return {
        "platform": st["platform"], "kind": st["device_kind"],
        "count": st["device_count"], "devices": st["devices"],
        "kv_pinned": st["kv_pinned"],
        "compiles": compiles, "compiles_total": sum(compiles.values()),
        "recompiles": int(metric_sum(m, "xllm_worker_recompiles_total")),
        "engine_alive": metric_sum(m, "xllm_worker_engine_alive"),
        "lookups": int(metric_sum(
            m, "xllm_worker_prefix_cache_lookups_total")),
        "hit_tokens": int(metric_sum(
            m, "xllm_worker_prefix_cache_hit_tokens_total")),
        "metrics": m,
    }


def device_line(rep: Dict[str, Any]) -> str:
    def gb(v: Optional[int]) -> str:
        return "n/a" if v is None else f"{v / 1e9:.2f} GB"
    parts = []
    for d in rep["devices"]:
        parts.append(f"id {d['id']} coords {d['coords']} in use "
                     f"{gb(d['bytes_in_use'])} peak "
                     f"{gb(d['peak_bytes_in_use'])} of "
                     f"{gb(d['bytes_limit'])}")
    return (f"{rep['platform']} / {rep['kind']} x{rep['count']}: "
            + "; ".join(parts))


# ---------------------------------------------------------------------------
# Prompts. The byte tokenizer makes one token of each byte, so lengths
# in tokens are lengths in characters plus the chat template's few.
# ---------------------------------------------------------------------------

def text_of(n_chars: int, salt: str) -> str:
    """Deterministic filler of exactly ``n_chars`` characters that
    differs from its first character on for different ``salt``."""
    words = ("page", "router", "prefill", "decode", "cache", "token",
             "stream", "replica", "window", "batch", "kernel", "mesh")
    out = [salt]
    i = sum(map(ord, salt))
    while sum(map(len, out)) + len(out) < n_chars + 16:
        out.append(words[i % len(words)])
        i = i * 7 + 3
    return " ".join(out)[:n_chars]


# ---------------------------------------------------------------------------
# Phase: preflight (no JAX)
# ---------------------------------------------------------------------------

def phase_preflight() -> None:
    say("== preflight")
    from importlib import metadata
    vers = {}
    for pkg in ("jax", "jaxlib", "libtpu", "numpy"):
        try:
            vers[pkg] = metadata.version(pkg)
        except metadata.PackageNotFoundError:
            vers[pkg] = "absent"
    say("  versions: " + " ".join(f"{k}={v}" for k, v in vers.items())
        + f" python={sys.version.split()[0]}")
    from xllm_service_tpu.service import etcd_native, native_httpd
    from xllm_service_tpu.utils import hashing, jaxcache
    say(f"  compile cache: {jaxcache.cache_dir()} "
        f"({jaxcache.ENV_VAR} "
        f"{'set' if os.environ.get(jaxcache.ENV_VAR) else 'unset'}; "
        f"{_dir_entries(jaxcache.cache_dir())} entries at start)")
    t0 = time.monotonic()
    front = "native" if native_httpd.native_httpd_available() else \
        "python fallback"
    hashp = "native" if hashing.native_available() else "python fallback"
    etcd = etcd_native.build_binary()
    built = sorted(os.listdir(os.path.join(ROOT, "build", "native"))) \
        if os.path.isdir(os.path.join(ROOT, "build", "native")) else []
    say(f"  front door: {front}; hash path: {hashp}; native store: "
        f"{'built' if etcd else 'not built'} "
        f"({time.monotonic() - t0:.1f} s; build/native: {built})")
    check("jax" not in sys.modules, "the parent has not imported JAX")


def _dir_entries(path: str) -> int:
    try:
        return len(os.listdir(path))
    except OSError:
        return 0


# ---------------------------------------------------------------------------
# Phase: serve (one chip)
# ---------------------------------------------------------------------------

def phase_serve(procs: Procs, rehearse: bool) -> Dict[str, Any]:
    say("== serve")
    cl = Cluster(procs, "serve", rehearse)
    w = cl.start_worker("worker")
    cl.wait_registered(["worker"], 900)
    at_reg = worker_report(w)
    say(f"  registered after {w['registered_s']:.1f} s; compiled at "
        f"registration: {at_reg['compiles_total']} {at_reg['compiles']}")
    say(f"  device at registration: {device_line(at_reg)}")

    short = text_of(200, "smoke-a")
    long_ = text_of(1500 - 40, "smoke-long")

    say("  -- 1. short prompt, non-streamed")
    a1 = chat(cl.http, short, 16)
    check_answer(a1, 16, "short")
    say("  -- 2. streamed")
    a2 = chat(cl.http, text_of(180, "smoke-b"), 24, stream=True)
    check_answer(a2, 24, "streamed")
    say("  -- 3. long prompt (a large prefill bucket)")
    a3 = chat(cl.http, long_, 16)
    check_answer(a3, 16, "long")
    check(1400 <= a3.prompt_tokens <= 1600,
          f"long: about 1,500 prompt tokens ({a3.prompt_tokens})")
    say("  -- 4. the short prompt again (prefix-cache hit)")
    hits_before = worker_report(w)["hit_tokens"]
    a4 = chat(cl.http, short, 16)
    check_answer(a4, 16, "repeat")
    hits = worker_report(w)["hit_tokens"] - hits_before
    check(hits >= PAGE_SIZE, f"repeat: {hits} prompt tokens served from "
          f"the prefix cache (>= one {PAGE_SIZE}-token page)")
    check(a4.same_tokens(a1), "repeat: identical text and tokens at "
          "temperature 0")
    gap = a4.max_logprob_gap(a1)
    check(gap <= REPEAT_LOGPROB_TOL, f"repeat: max |logprob gap| {gap:.2e}"
          f" <= {REPEAT_LOGPROB_TOL}")
    say("  -- 5. eight concurrent streams (decode batch above 1)")
    answers: List[Optional[Answer]] = [None] * 8

    def one(i: int) -> None:
        answers[i] = chat(cl.http, text_of(150 + 9 * i, f"smoke-c{i}"),
                          32, stream=True)
    threads = [threading.Thread(target=one, args=(i,)) for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(900)
    for i, a in enumerate(answers):
        check(a is not None, f"stream {i} returned", quiet=True)
        check_answer(a, 32, f"stream {i}")
    m = scrape(w["addr"])
    decode_tok = metric_sum(m, "xllm_worker_step_tokens_total",
                            phase="decode")
    decode_steps = sum(metric_sum(m, "xllm_worker_steps_total", phase=p)
                       for p in ("decode", "mixed"))
    say(f"  decode tokens {decode_tok:.0f} over {decode_steps:.0f} decode/"
        f"mixed steps since boot")
    check(decode_tok > decode_steps > 0,
          "some decode step carried more than one sequence")

    end = worker_report(w)
    say(f"  compile census: {end['compiles_total']} programs "
        f"{end['compiles']}; at registration {at_reg['compiles_total']}; "
        f"compiled lazily after registration "
        f"{end['compiles_total'] - at_reg['compiles_total']} "
        f"(xllm_worker_recompiles_total {end['recompiles']}; warm-up was "
        f"XLLM_WARMUP_EXTENDED=0, see worker_env)")
    say(f"  seconds to registration: {w['registered_s']:.1f}")
    check(end["engine_alive"] == 1.0, "xllm_worker_engine_alive 1")
    check(all(end["kv_pinned"].values()) and end["kv_pinned"],
          f"KV pool layout pin on ({end['kv_pinned']})")
    say(f"  device at end: {device_line(end)}")
    return end


# ---------------------------------------------------------------------------
# Phases: four chips
# ---------------------------------------------------------------------------

def reference_answers(cl: Cluster, prompts: List[Tuple[str, str, int]]
                      ) -> Dict[str, Answer]:
    """Each (key, prompt, max_tokens) asked of ``cl`` one at a time."""
    out = {}
    for key, prompt, n in prompts:
        out[key] = chat(cl.http, prompt, n, stream=True, rid=f"ref-{key}")
        check_answer(out[key], n, f"control {key}", quiet=True)
    say(f"  ok: control answered {len(out)} prompts one at a time in "
        f"{sum(a.seconds for a in out.values()):.0f} s")
    return out


def replica_prompts() -> Tuple[List[Tuple[str, str, int]],
                               List[Tuple[str, str, int]],
                               List[Tuple[str, str, int]]]:
    """Sixteen requests: four sessions that share a long prefix within
    the session (an opening turn and a follow-up each: eight requests),
    and eight unrelated prompts. Every prompt is 350 to 500 tokens long,
    so that a worker compiles one prefill program for all of them (and
    one more for the short window a prefix hit leaves): on four chips a
    lazily compiled program costs four chips' time."""
    openers, followups, singles = [], [], []
    for s in range(4):
        prefix = text_of(3 * PAGE_SIZE - 30, f"session-{s}")
        openers.append((f"s{s}t1", prefix, 192))
        followups.append((f"s{s}t2", prefix + text_of(90, f" turn2-{s}"),
                          16))
    for i in range(8):
        singles.append((f"x{i}", text_of(300 + 10 * i, f"single-{i}"), 16))
    return openers, followups, singles


def phase_four_chip(procs: Procs, rehearse: bool) -> Dict[str, Any]:
    chip_env = (lambda i: {}) if rehearse else one_chip_env
    openers, followups, singles = replica_prompts()
    pd_prompts = [(f"pd{i}", text_of(310 + 40 * i, f"pd-{i}"), 24)
                  for i in range(3)]

    # -- the comparison: one MIX worker on a chip of its own, behind its
    # own master, asked every prompt of both phases one at a time.
    say("== control: one MIX worker (chip 3)")
    control = Cluster(procs, "control", rehearse)
    cw = control.start_worker("mix", env=chip_env(3))
    # -- pd: started beside it, on chips 0 and 1.
    pd = Cluster(procs, "pd", rehearse)
    pw = pd.start_worker("prefill", ["--instance-type", "PREFILL"],
                         env=chip_env(0))
    control.wait_registered(["mix"], 900)
    dw = pd.start_worker("decode", ["--instance-type", "DECODE"],
                         env=chip_env(1))
    pd.wait_registered(["prefill", "decode"], 900)
    say(f"  control registered after {cw['registered_s']:.1f} s, prefill "
        f"{pw['registered_s']:.1f} s, decode {dw['registered_s']:.1f} s")
    ref = reference_answers(control, openers + followups + singles
                            + pd_prompts)

    say("== pd: PREFILL (chip 0) + DECODE (chip 1) against the MIX control")
    # FINDING (PR 22): the first token is sampled by the prefill worker
    # and re-emitted by the decode worker from the migrated token list,
    # which does not carry its logprob (worker._adopt_migrated_inner), so
    # a PD stream has one logprob entry fewer than the MIX stream. Text
    # and every later token's logprob must be identical; each depends on
    # the first token, which is thereby checked too.
    for key, prompt, n in pd_prompts:
        a = chat(pd.http, prompt, n, stream=True, rid=f"pd-{key}")
        check_answer(a, n, f"pd {key}", n_logprobs=n - 1)
        check(a.text == ref[key].text
              and a.tokens == ref[key].tokens[1:]
              and a.logprobs == ref[key].logprobs[1:],
              f"pd {key}: text and the {n - 1} logprobs after the first "
              f"token identical to the MIX control's (served by "
              f"{pd.served_by(f'pd-{key}')})")
    pre, dec = worker_report(pw), worker_report(dw)
    pm = pre["metrics"]
    moved = {k: int(metric_sum(pm, f"xllm_worker_kv_migration_{k}_total"))
             for k in ("device_wire", "direct", "chunked")}
    nbytes = metric_sum(pm, "xllm_worker_kv_migration_bytes_total")
    secs = metric_sum(pm, "xllm_worker_kv_migration_seconds_total")
    transport = ("device wire" if moved["device_wire"] else
                 "direct (in-process)" if moved["direct"] else
                 "host shuttle (chunked)" if moved["chunked"] else
                 "host shuttle (monolithic)" if nbytes else "none")
    say(f"  KV transport: {transport} {moved}; {nbytes / 1e6:.2f} MB in "
        f"{secs:.3f} s over {len(pd_prompts)} migrations")
    check(nbytes > 0, "KV blocks moved from the prefill to the decode "
          "worker")
    check(metric_sum(dec["metrics"], "xllm_worker_step_tokens_total",
                     phase="decode") > 0
          and metric_sum(pre["metrics"], "xllm_worker_step_tokens_total",
                         phase="decode") == 0,
          "decode tokens came from the DECODE worker only")
    say(f"  prefill device: {device_line(pre)}")
    say(f"  decode  device: {device_line(dec)}")
    devices = {"prefill": pre, "decode": dec, "control": worker_report(cw)}
    for cluster in (pd, control):
        for w in cluster.workers.values():
            procs.stop(w["proc"])

    say("== replicas: four one-chip workers behind one master (CAR)")
    rp = Cluster(procs, "replicas", rehearse)
    # DEFAULT, not the CLI's MIX: several MIX workers behind one master
    # are seated as one decode and three prefill instances (a PD
    # topology, instance_mgr._reseat_mix); a DEFAULT worker serves both
    # phases of whatever is routed to it. One-second heartbeats: load is
    # what the policy spreads by, and it reads the last heartbeat.
    ws = [rp.start_worker(
        f"r{i}", ["--instance-type", "DEFAULT",
                  "--heartbeat-interval-s", "1.0"], env=chip_env(i))
        for i in range(4)]
    rp.wait_registered([w["name"] for w in ws], 900)
    say("  registered after "
        + ", ".join(f"{w['name']} {w['registered_s']:.1f} s" for w in ws))
    got: Dict[str, Answer] = {}
    where: Dict[str, str] = {}
    # Openers: one long-running stream after another. CAR sends a prompt
    # with no cached prefix anywhere to the least loaded worker, and load
    # is what the last heartbeat said, so each opener is sent once the
    # master has seen the one before it running.
    threads = []
    for key, prompt, n in openers:
        def run(key=key, prompt=prompt, n=n) -> None:
            got[key] = chat(rp.http, prompt, n, stream=True,
                            rid=f"rep-{key}")
        t = threading.Thread(target=run)
        t.start()
        threads.append(t)
        _wait_load_seen(rp, len(threads))
    for t in threads:
        t.join(900)
    time.sleep(2.5)      # two heartbeats: the cache events reach the master
    # The follow-ups together (each belongs on another replica, so each
    # replica still serves one request at a time, as the control did),
    # the unrelated prompts one after the other.
    threads = []
    for key, prompt, n in followups:
        def run(key=key, prompt=prompt, n=n) -> None:
            got[key] = chat(rp.http, prompt, n, stream=True,
                            rid=f"rep-{key}")
        threads.append(threading.Thread(target=run))
        threads[-1].start()
    for t in threads:
        t.join(900)
    for key, prompt, n in singles:
        got[key] = chat(rp.http, prompt, n, stream=True, rid=f"rep-{key}")
    for key, _prompt, n in openers + followups + singles:
        check_answer(got[key], n, f"replicas {key}", quiet=True)
        where[key] = rp.served_by(f"rep-{key}").rstrip(">")
        check(got[key].same_tokens(ref[key])
              and got[key].logprobs == ref[key].logprobs,
              f"replicas {key}: 200, {n} tokens in "
              f"{got[key].seconds:.2f} s, text and logprobs equal the "
              f"one-worker answer (served by {where[key]})")
    for s in range(4):
        check(where[f"s{s}t2"] == where[f"s{s}t1"],
              f"session {s}: the follow-up went to the replica that holds "
              f"its prefix ({where[f's{s}t2']})")
    reports = [worker_report(w) for w in ws]
    for w, rep in zip(ws, reports):
        say(f"  {w['name']} ({w['addr']}): admitted {rep['lookups']}, "
            f"prefix-hit tokens {rep['hit_tokens']}, {device_line(rep)}")
        check(rep["lookups"] >= 1, f"{w['name']} served at least one")
        check(rep["count"] == 1, f"{w['name']} sees one device")
        if not rehearse:
            check(rep["platform"] == "tpu", f"{w['name']} is on a TPU")
            check(all((d["bytes_in_use"] or 0) > 0
                      for d in rep["devices"]),
                  f"{w['name']}: memory in use on its chip")
    if not rehearse:
        # Four pools and four copies of the weights cannot share one
        # 16 GB chip: what each worker holds, summed, is the proof that
        # the four processes are on four chips.
        total = sum(d["bytes_in_use"] for rep in reports
                    for d in rep["devices"])
        limit = max(d["bytes_limit"] for rep in reports
                    for d in rep["devices"])
        check(total > limit, f"in use across the four workers "
              f"{total / 1e9:.1f} GB exceeds one chip's "
              f"{limit / 1e9:.1f} GB: four distinct chips")
    devices["replicas"] = reports[0]
    for w in ws:
        procs.stop(w["proc"])

    say("== tp: one worker --tp 4")
    tp = Cluster(procs, "tp", rehearse)
    tp_env = {"XLA_FLAGS": "--xla_force_host_platform_device_count=4"} \
        if rehearse else {}
    tw = tp.start_worker("tp4", ["--tp", "4"], env=tp_env)
    tp.wait_registered(["tp4"], 900)
    for key, prompt, n in pd_prompts[:2]:
        a = chat(tp.http, prompt, n, stream=True)
        check_answer(a, n, f"tp4 {key}")
        near = sum(abs(x - y) <= REPEAT_LOGPROB_TOL
                   for x, y in zip(a.logprobs, ref[key].logprobs))
        say(f"  tp4 {key}: {near} of {n} logprobs within "
            f"{REPEAT_LOGPROB_TOL} of the one-chip control's, max gap "
            f"{a.max_logprob_gap(ref[key]):.2e} (another attention path "
            f"and another summation order: reported, not required)")
    rep = worker_report(tw)
    say(f"  tp4 registered after {tw['registered_s']:.1f} s; "
        f"{device_line(rep)}")
    check(rep["count"] == 4, "tp4: the worker's mesh has four devices")
    if not rehearse:
        check_split(rep, MODEL, "tp4")
    devices["tp"] = rep
    procs.stop(tw["proc"])

    big = "tiny" if rehearse else "llama3-8b"
    say(f"== tp: {big} --tp 4 (the case that needs four chips)")
    bw = tp.start_worker("tp4-big", ["--tp", "4"], env=tp_env, model=big)
    tp.wait_registered(["tp4-big"], 1500)
    a = chat(tp.http, pd_prompts[0][1], 16, stream=True, model=big)
    check_answer(a, 16, f"{big} tp4")
    rep8 = worker_report(bw)
    say(f"  {big} registered after {bw['registered_s']:.1f} s; "
        f"{device_line(rep8)}")
    check(rep8["count"] == 4, f"{big}: four devices")
    if not rehearse:
        check_split(rep8, big, f"{big} tp4")
    return rep


def nominal_bytes(model: str) -> Tuple[int, int]:
    """(weights, KV pool) bytes of ``model`` in bfloat16 at the smoke's
    pool, from the configuration's shapes."""
    from xllm_service_tpu.config import ModelConfig
    cfg = {"llama3-1b": ModelConfig.llama3_1b,
           "llama3-8b": ModelConfig.llama3_8b}[model]()
    d, f, dh = cfg.hidden_size, cfg.intermediate_size, cfg.head_dim
    layer = (d * cfg.num_heads * dh * 2 + 2 * d * cfg.num_kv_heads * dh
             + 3 * d * f + 2 * d)
    params = cfg.vocab_size * d * (1 if cfg.tie_word_embeddings else 2) \
        + cfg.num_layers * layer + d
    pool = 2 * cfg.num_layers * NUM_PAGES * PAGE_SIZE \
        * cfg.num_kv_heads * dh
    return 2 * params, 2 * pool


def check_split(rep: Dict[str, Any], model: str, what: str) -> None:
    """Weights and pool are really split: each chip holds about a
    quarter of their nominal bytes (a sharded engine's pool is not
    pinned, so it is not padded), not a copy of them."""
    weights, pool = nominal_bytes(model)
    quarter = (weights + pool) / 4
    used = [d["bytes_in_use"] for d in rep["devices"]]
    check(all(0.8 * quarter < u < 1.5 * quarter for u in used),
          f"{what}: per-chip bytes in use "
          f"{[round(u / 1e9, 2) for u in used]} GB are about a quarter "
          f"({quarter / 1e9:.2f} GB) of weights {weights / 1e9:.2f} GB + "
          f"pool {pool / 1e9:.2f} GB")


def _wait_load_seen(cl: Cluster, n_loaded: int,
                    timeout_s: float = 180.0) -> None:
    """Until the master's view (heartbeat load, as the policy reads it)
    shows ``n_loaded`` instances busy."""
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        bundle = get_json(cl.http, "/admin/debug_bundle")
        loads = _instance_loads(bundle)
        if sum(1 for v in loads.values() if v > 0) >= n_loaded:
            return
        time.sleep(0.2)
    raise SmokeFailure(f"the master never saw {n_loaded} loaded instances")


def _instance_loads(bundle: Dict[str, Any]) -> Dict[str, float]:
    out = {}
    for inst in _find_key(bundle, "instances") or []:
        load = inst.get("load") or {}
        out[inst.get("name", "?")] = (
            float(load.get("running_requests", 0))
            + float(load.get("waiting_requests", 0))
            + float(load.get("kv_cache_usage", 0.0)))
    return out


# ---------------------------------------------------------------------------
# Child phase: parity (this is the only code here that imports JAX)
# ---------------------------------------------------------------------------

def child_parity(rehearse: bool) -> None:
    import jax
    import numpy as np

    from xllm_service_tpu.config import EngineConfig, ModelConfig
    from xllm_service_tpu.models import transformer
    from xllm_service_tpu.ops.plan import KernelPlan
    from xllm_service_tpu.utils.jaxcache import enable_compile_cache

    enable_compile_cache()
    dev = jax.devices()[0]
    print(f"device: {dev.platform} / {dev.device_kind} x"
          f"{len(jax.devices())}", flush=True)
    if dev.platform != "tpu" and not rehearse:
        raise SystemExit(f"parity needs a TPU, JAX gave {dev.platform!r}")

    cfg = ModelConfig.tiny() if rehearse else ModelConfig.llama3_1b()
    ps, n_pages, steps = (16, 32, 5) if rehearse else (PAGE_SIZE, 16, 6)
    params = transformer.init_params(cfg, jax.random.PRNGKey(22))

    # Two prompts of different length in one window of T tokens (T is a
    # multiple of the page: the in-place prefill writer's condition). The
    # longer fills its last page exactly, so its first decode step opens
    # a new page. Pages are handed out in a shuffled order, so an offset
    # computed wrongly lands in another sequence's page.
    T = 2 * ps
    # The two paths, as plans: what an engine of this geometry resolves
    # on this device (the served path; the rehearsal states the chip's
    # plan, interpreted), and the XLA reference.
    on_chip = KernelPlan(decode_attn=True, kv_writers=True,
                         write_then_attend=True)
    served_plan = dataclasses.replace(on_chip, interpret=True) \
        if rehearse else KernelPlan.from_env(cfg, EngineConfig(
            page_size=ps, num_pages=n_pages, max_model_len=4 * ps,
            prefill_buckets=(T,)))
    print(f"served plan: {served_plan}", flush=True)
    if dataclasses.replace(served_plan, interpret=False) != on_chip:
        raise SystemExit("not the path the chip serves by default")
    lens = np.array([T - ps // 2 - 3, T], np.int32)
    rng = np.random.default_rng(22)
    tokens = rng.integers(3, cfg.vocab_size, (2, T)).astype(np.int32)
    order = rng.permutation(np.arange(1, n_pages))[:8].astype(np.int32)
    table = np.stack([order[:4], order[4:]])
    start, active = np.zeros(2, np.int32), np.ones(2, bool)

    # One jitted forward each: the plan is a static, so part of the
    # cache key, and each path is its own program.
    prefill = jax.jit(transformer.forward_prefill,
                      static_argnames=("cfg", "plan"))
    decode = jax.jit(transformer.forward_decode,
                     static_argnames=("cfg", "plan"))

    def run(served: bool, feed, table_after_prefill=None):
        """Logits of the prefill and of each decode step. ``feed`` None:
        continue greedily and return the tokens fed, too."""
        plan = served_plan if served else KernelPlan()
        kv = transformer.init_kv_cache(cfg, n_pages, ps)
        calls = prefill.lower(
            params, cfg, tokens, start, lens, kv, table,
            plan=plan).as_text().count("tpu_custom_call")
        last, _, kv = prefill(params, cfg, tokens, start, lens, kv, table,
                              plan=plan)
        out, fed, pos = [np.asarray(last, np.float32)], [], lens.copy()
        pt = table if table_after_prefill is None else table_after_prefill
        for i in range(steps if feed is None else len(feed)):
            tok = out[-1].argmax(-1).astype(np.int32) if feed is None \
                else feed[i]
            if i == 0:
                calls += decode.lower(
                    params, cfg, tok, pos, active, kv, pt,
                    plan=plan).as_text().count("tpu_custom_call")
            logits, kv = decode(params, cfg, tok, pos, active, kv, pt,
                                plan=plan)
            out.append(np.asarray(logits, np.float32))
            fed.append(tok)
            pos = pos + 1
        return out, fed, calls

    # The greedy continuation of the REFERENCE feeds both paths: same
    # inputs at every step, so a difference is the path's and not an
    # earlier token's.
    ref, feed, ref_calls = run(False, None)
    got, _, calls = run(True, feed)
    # The control: the served path with the first page of the longer
    # sequence read from the other sequence's first page after the
    # prefill (swapping two of its own pages would change nothing: keys
    # carry their rotary position, and attention does not care in which
    # order it meets them).
    wrong = table.copy()
    wrong[1, 0] = table[0, 0]
    bad, _, _ = run(True, feed[:1], table_after_prefill=wrong)

    print(f"custom calls in the lowered programs: served path {calls}, "
          f"reference path {ref_calls}", flush=True)
    if not rehearse and (calls == 0 or ref_calls != 0):
        raise SystemExit("the served path must hold Pallas calls and the "
                         "reference path none")
    worst = 0.0
    for i, (a, b) in enumerate(zip(got, ref)):
        if not np.isfinite(a).all() or a.shape != (2, cfg.vocab_size):
            raise SystemExit(f"step {i}: bad logits {a.shape}")
        ratio = float(np.abs(a - b).max() / b.std())
        worst = max(worst, ratio)
        print(f"step {i} ({'prefill' if i == 0 else 'decode'}): "
              f"max|diff|/std {ratio:.4f}  argmax equal "
              f"{bool((a.argmax(-1) == b.argmax(-1)).all())}  std "
              f"{b.std():.3f}", flush=True)
    bad_ratio = float(np.abs(bad[1] - ref[1]).max() / ref[1].std())
    print(f"parity: worst max|diff|/std {worst:.4f} against tolerance "
          f"{PARITY_TOL}; wrong-page control {bad_ratio:.4f}", flush=True)
    if not worst <= PARITY_TOL:
        raise SystemExit("parity FAILED")
    if not bad_ratio > 4 * PARITY_TOL:
        raise SystemExit("the tolerance does not tell a wrong page from a "
                         "right one")
    if rehearse:
        return

    # The engine's own step programs at the serve configuration, pinned:
    # what the compiler says they need on this device.
    from xllm_service_tpu.runtime.engine import Engine
    _step_memory(Engine(cfg, EngineConfig(
        page_size=PAGE_SIZE, num_pages=NUM_PAGES, max_model_len=MAX_LEN,
        max_batch_size=BATCH), params=params))


def _step_memory(eng) -> None:
    """Print memory_analysis() of the engine's decode and largest
    prefill step programs at its own configuration."""
    import jax
    import jax.numpy as jnp

    from xllm_service_tpu.runtime import engine as E
    B, V = eng.ecfg.max_batch_size, eng.cfg.vocab_size
    mp = eng.ecfg.max_pages_per_seq
    key = jax.random.PRNGKey(0)

    def small(b):
        return (*eng._sampling_tensors([], b), *eng._batch_bias([], b, V))
    st_f32, st_i32, b_ids, b_vals = small(B)
    dec = eng._jit_decode.lower(
        eng.params, jnp.zeros((B, E._PACK_COLS + mp), jnp.int32), eng.kv,
        st_f32, st_i32, key, None, b_ids, b_vals).compile()
    st_f32, st_i32, b_ids, b_vals = small(1)
    T = eng.ecfg.prefill_buckets[-1]
    pre = eng._jit_prefill.lower(
        eng.params, jnp.zeros((1, E._PREFILL_HDR + T + mp), jnp.int32),
        eng.kv, st_f32, st_i32, key, None, None, None, b_ids, b_vals,
        None, T).compile()
    for name, c in ((f"decode B{B} mp{mp}", dec),
                    (f"prefill B1 T{T} mp{mp}", pre)):
        m = c.memory_analysis()
        print(f"step memory [{name}] pinned={eng.kv_pinned}: arguments "
              f"{m.argument_size_in_bytes / 1e9:.3f} GB, temporaries "
              f"{m.temp_size_in_bytes / 1e9:.3f} GB, aliased "
              f"{m.alias_size_in_bytes / 1e9:.3f} GB, output "
              f"{m.output_size_in_bytes / 1e9:.3f} GB, code "
              f"{m.generated_code_size_in_bytes / 1e6:.1f} MB; "
              f"tpu_custom_call x{c.as_text().count('tpu_custom_call')}",
              flush=True)
    pool = sum(x.nbytes for x in eng.kv)
    ms = jax.devices()[0].memory_stats() or {}
    print(f"pool nominal {pool / 1e9:.3f} GB for {eng.ecfg.num_pages} "
          f"pages of {eng.ecfg.page_size}; device in use "
          f"{ms.get('bytes_in_use', 0) / 1e9:.3f} GB, peak "
          f"{ms.get('peak_bytes_in_use', 0) / 1e9:.3f} GB of "
          f"{ms.get('bytes_limit', 0) / 1e9:.3f} GB", flush=True)


# ---------------------------------------------------------------------------

def main(argv: Optional[Sequence[str]] = None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run the four-chip path (replicas, pd, tp) and "
                         "what it is compared with, and no other phase")
    ap.add_argument("--rehearse", action="store_true",
                    help="tiny model on the CPU, kernels interpreted: "
                         "walks every step of the script; never ok")
    ap.add_argument("--child", default="", help=argparse.SUPPRESS)
    args = ap.parse_args(argv)
    if args.child == "parity":
        child_parity(args.rehearse)
        return 0
    if args.child:
        raise SystemExit(f"unknown child phase {args.child!r}")

    if args.rehearse:
        SERVED["model"] = "tiny"
    t0 = time.monotonic()
    with Procs() as procs:
        phase_preflight()
        if args.four_chip:
            rep = phase_four_chip(procs, args.rehearse)
        else:
            say("== parity")
            run_child(procs, "parity", args.rehearse)
            rep = phase_serve(procs, args.rehearse)
    say(f"all phases passed in {time.monotonic() - t0:.0f} s")
    if args.rehearse or rep["platform"] != "tpu":
        say(json.dumps({"ok": False, "rehearsal": args.rehearse,
                        "device": {"platform": rep["platform"],
                                   "kind": rep["kind"],
                                   "count": rep["count"]}}))
        return 3
    say(json.dumps({"ok": True, "device": {
        "platform": rep["platform"], "kind": rep["kind"],
        "count": rep["count"]}}))
    return 0


if __name__ == "__main__":
    try:
        raise SystemExit(main())
    except SmokeFailure as exc:
        print(f"FAILED: {exc}", flush=True)
        raise SystemExit(1)
