"""The system under test, brought up the way it is served: coordination
store and master as child processes (they never touch JAX), the worker
inside the benchmark's own process, because only the chip's holder can
trace the chip. Requests enter at the master's OpenAI HTTP endpoint.
"""

from __future__ import annotations

import concurrent.futures
import json
import os
import re
import signal
import socket
import subprocess
import sys
import threading
import time
import urllib.request
from typing import Any, Dict, List, Optional, Sequence, Tuple


def log(msg: str) -> None:
    print(f"[chipbench {time.strftime('%H:%M:%S')}] {msg}", flush=True)


def free_port() -> int:
    with socket.socket() as s:
        s.bind(("127.0.0.1", 0))
        return s.getsockname()[1]


class Procs:
    """Child processes, each with its log; every one is stopped and
    waited for at the end, whatever happened."""

    def __init__(self, log_dir: str) -> None:
        self.log_dir = log_dir
        os.makedirs(log_dir, exist_ok=True)
        self.procs: List[Tuple[str, subprocess.Popen]] = []

    def start(self, name: str, argv: Sequence[str], capture: bool = False,
              env: Optional[Dict[str, str]] = None) -> subprocess.Popen:
        child_env = dict(os.environ)
        # Children never hold the chip: the master, the store and the
        # load generator run without an accelerator.
        child_env["JAX_PLATFORMS"] = "cpu"
        child_env.update(env or {})
        errlog = open(os.path.join(self.log_dir, name + ".log"), "wb")
        p = subprocess.Popen(
            [sys.executable, *argv], env=child_env, stderr=errlog,
            stdout=subprocess.PIPE if capture else errlog,
            text=True if capture else None, start_new_session=True)
        self.procs.append((name, p))
        return p

    def log_tail(self, name: str, n: int = 20) -> str:
        try:
            with open(os.path.join(self.log_dir, name + ".log"), "r",
                      errors="replace") as f:
                return "".join(f.readlines()[-n:])
        except OSError:
            return ""

    def stop_all(self) -> None:
        for _, p in self.procs:
            if p.poll() is None:
                p.terminate()
        deadline = time.monotonic() + 10
        for _, p in self.procs:
            try:
                p.wait(max(0.1, deadline - time.monotonic()))
            except subprocess.TimeoutExpired:
                try:
                    os.killpg(p.pid, signal.SIGKILL)
                except OSError:
                    p.kill()
                p.wait()
        self.procs = []


def wait_port(port: int, timeout_s: float, what: str) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        try:
            socket.create_connection(("127.0.0.1", port), 0.5).close()
            return
        except OSError:
            time.sleep(0.05)
    raise RuntimeError(f"{what} did not open port {port}")


def start_front(procs: Procs) -> Dict[str, str]:
    """Store and master on loopback. Returns their addresses."""
    port = free_port()
    store = f"127.0.0.1:{port}"
    procs.start("store", ["-m", "xllm_service_tpu.service.coordination_net",
                          "--port", str(port)])
    wait_port(port, 30, "store")
    master = procs.start("master", [
        "-m", "xllm_service_tpu.service.master", "--host", "127.0.0.1",
        "--http-port", "0", "--rpc-port", "0", "--etcd-addr", store],
        capture=True)
    line, deadline = "", time.monotonic() + 60
    while time.monotonic() < deadline:
        line = master.stdout.readline()
        if not line or line.startswith("XLLM_SERVICE_UP"):
            break
    m = re.search(r"http=(\S+) rpc=(\S+)", line or "")
    if not m:
        raise RuntimeError("master did not come up:\n"
                           + procs.log_tail("master"))
    threading.Thread(target=lambda: [None for _ in master.stdout],
                     daemon=True).start()
    return {"store": store, "http": m.group(1), "rpc": m.group(2)}


def write_model_dir(path: str, config: Dict[str, Any]) -> str:
    """A ``--model-dir`` of the benchmark's own: the configuration's
    ``config.json`` as it is run, and a word-level tokenizer that spells
    token i as ``t<i>``, so that the streamed text gives the served token
    ids back (the relay strips the worker's own id extension)."""
    os.makedirs(path, exist_ok=True)
    with open(os.path.join(path, "config.json"), "w") as f:
        json.dump(config, f, indent=1)
    tok_path = os.path.join(path, "tokenizer.json")
    vocab = int(config["vocab_size"])
    from tokenizers import Tokenizer
    from tokenizers.models import WordLevel
    from tokenizers.pre_tokenizers import WhitespaceSplit
    tok = Tokenizer(WordLevel({f"t{i}": i for i in range(vocab)},
                              unk_token="t0"))
    tok.pre_tokenizer = WhitespaceSplit()
    tok.save(tok_path)
    return path


def http_get(addr: str, path: str, timeout: float = 30.0) -> bytes:
    with urllib.request.urlopen(f"http://{addr}{path}",
                                timeout=timeout) as r:
        return r.read()


_SAMPLE = re.compile(r"^([a-zA-Z_:][\w:]*)(\{[^}]*\})?\s+(\S+)")


def scrape(addr: str) -> Dict[str, float]:
    """``/metrics`` as {family: sum over its label sets} plus
    {family{labels}: value} for the labelled series."""
    out: Dict[str, float] = {}
    for ln in http_get(addr, "/metrics").decode().splitlines():
        if ln.startswith("#"):
            continue
        m = _SAMPLE.match(ln)
        if not m:
            continue
        try:
            v = float(m.group(3))
        except ValueError:
            continue
        out[m.group(1)] = out.get(m.group(1), 0.0) + v
        if m.group(2):
            out[m.group(1) + m.group(2)] = v
    return out


def labelled(counters: Dict[str, float], family: str, **labels: str
             ) -> float:
    """Sum of the series of ``family`` whose labels include ``labels``."""
    tot = 0.0
    for k, v in counters.items():
        if not k.startswith(family + "{"):
            continue
        if all(f'{a}="{b}"' in k for a, b in labels.items()):
            tot += v
    return tot


def build_worker(cell, model_dir: str, front: Dict[str, str], params):
    """The worker, in this process, with the ``EngineConfig`` that
    ``worker.main`` builds from its options (page size, pool, length,
    batch) and no other knob, and with the benchmark's weights."""
    from xllm_service_tpu.config import EngineConfig
    from xllm_service_tpu.runtime import worker as W
    from xllm_service_tpu.service.coordination_net import connect_store
    eng = cell.traffic["engine"]
    engine_cfg = EngineConfig(
        page_size=int(eng["page_size"]), num_pages=int(eng["num_pages"]),
        max_model_len=int(eng["max_model_len"]),
        max_batch_size=int(eng["max_batch_size"]))
    # The one seam the program offers for weights: ModelRuntime loads a
    # checkpoint from the model directory or lets the engine draw its
    # own. The benchmark's weights go in where the checkpoint would.
    real = W.ModelRuntime._load_params
    W.ModelRuntime._load_params = lambda self: params
    try:
        opts = W.WorkerOptions(
            host="127.0.0.1", port=free_port(), service_addr=front["rpc"],
            model=cell.config_name, model_dir=model_dir, warmup=False)
        return W.Worker(opts, connect_store(front["store"]),
                        engine_cfg=engine_cfg, mesh=None)
    finally:
        W.ModelRuntime._load_params = real    # and the weights' last
        # reference outside the engine goes with this frame


def enable_cache_again() -> None:
    """A single-device engine switches JAX's persistent cache off for its
    process (a cached executable drops non-default entry layouts, PR 22).
    Where the row-major layout the engine pins IS the chip's default one
    for the pool's shape, a cached program takes the pools as they are,
    and the ban can be lifted: so for pools of 8 heads of 128 (the
    Mistral cells; PR 26). It is NOT so for a latent pool, one "head" of
    576: there the chip's default puts the page's 128 slots minor-most,
    a program that came through the cache hands the pool back in that
    order, and the next call fails loudly ("Layout passed to jit does
    not match"; my chip run, PR 29); it cannot serve wrong answers.
    Whether a configuration's step programs may come from the cache is
    therefore its own statement (``meta.json``
    ``step_programs_from_cache``), and ``run.py`` calls this only where
    it says so."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    if not jax.config.jax_enable_compilation_cache:
        jax.config.update("jax_enable_compilation_cache", True)
        compilation_cache.reset_cache()


def precompile(engine, shapes: Dict[str, Any], threads: int) -> float:
    """Compile the cell's step programs side by side into the persistent
    cache; the engine's own warm-up then finds each one there. A
    checkout's first run has 1200 s: chat's 64 programs take ~22 s each
    one after another (23 min) and 427 s in 8 threads (my chip runs,
    PR 26). The price is that the arguments are built here as
    ``Engine.warmup`` builds them, from the engine's private helpers: if
    those move, this raises and the cell's first run fails loudly; every
    later run never comes here (``precompile_marker``)."""
    import jax
    import jax.numpy as jnp
    from xllm_service_tpu.runtime import engine as E
    t0 = time.monotonic()
    key = jax.random.PRNGKey(0)
    lowered = []
    for B, T, mp in shapes["prefill"]:
        st_f32, st_i32 = engine._sampling_tensors([], B)
        b_ids, b_vals = engine._batch_bias([], B, engine.cfg.vocab_size)
        lowered.append(engine._jit_prefill.lower(
            engine.params,
            jnp.zeros((B, E._PREFILL_HDR + T + mp), jnp.int32),
            engine.kv, st_f32, st_i32, key, None, None, None,
            b_ids, b_vals, None, T))
    Bmax = engine.ecfg.max_batch_size
    st_f32, st_i32 = engine._sampling_tensors([], Bmax)
    b_ids, b_vals = engine._batch_bias([], Bmax, engine.cfg.vocab_size)
    for mp in shapes["decode_widths"]:
        lowered.append(engine._jit_decode.lower(
            engine.params, jnp.zeros((Bmax, E._PACK_COLS + mp), jnp.int32),
            engine.kv, st_f32, st_i32, key, None, b_ids, b_vals))
    with concurrent.futures.ThreadPoolExecutor(threads) as pool:
        list(pool.map(lambda lo: lo.compile(), lowered))
    return time.monotonic() - t0


def precompile_marker(cache_dir: str, config: Dict[str, Any],
                      mix: Dict[str, Any], shapes: Dict[str, Any]) -> str:
    """Where a checkout notes that a cell's programs are all in its
    cache: a file beside them, named from everything they depend on."""
    import hashlib
    import jax
    key = json.dumps([config, mix["engine"], shapes, jax.__version__],
                     sort_keys=True)
    os.makedirs(cache_dir, exist_ok=True)
    return os.path.join(cache_dir, "chipbench-precompiled-"
                        + hashlib.sha256(key.encode()).hexdigest()[:16])


def wait_registered(front: Dict[str, str], worker_addr: str,
                    timeout_s: float = 60.0) -> None:
    deadline = time.monotonic() + timeout_s
    while time.monotonic() < deadline:
        ev = json.loads(http_get(
            front["http"], "/admin/events?since=0&limit=4096"))
        if any(e["type"] == "instance_confirm"
               and e["attrs"].get("instance") == worker_addr
               for e in ev["events"]):
            return
        time.sleep(0.1)
    raise RuntimeError(f"worker {worker_addr} was not confirmed by the "
                       f"master within {timeout_s:.0f} s")
