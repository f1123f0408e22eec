"""Worker: one TPU engine host process — the "xLLM engine instance" the
reference assumes but does not contain (SURVEY.md §2 intro, §7.1).

A worker owns one or more ``ModelRuntime``s (model → engine) on one device
mesh, drives a continuous-batching loop thread, and speaks the cluster
contract:

- registers by writing ``XLLM:<TYPE>:<name>`` to the coordination store
  under a TTL lease (liveness = lease, instance_mgr.cpp:584-604);
- heartbeats the service every ``heartbeat_interval_s`` with load/latency
  metrics + prefix-cache deltas (rpc_service/client.cpp:55-77);
- serves the forwarded OpenAI request body (``token_ids`` already attached
  by the service, http_service/service.cpp:457-463) with SSE streaming
  back through the relay — or pushes tokens straight to the service's
  ``/rpc/generations`` fan-in when decode-response-to-service mode is on
  (the reference's two response topologies, rpc_service/service.h:67-79);
- implements the serverless control surface ``/fork_master``, ``/sleep``,
  ``/wakeup`` (instance_mgr.cpp:229-285): on TPU, sleep = donate weights
  to host RAM + drop KV pool; wakeup = re-shard weights back to HBM with
  compiled executables still cached (SURVEY.md §7.1);
- ``/flip_role`` switches PREFILL↔DECODE priority (both program sets stay
  AOT-compiled, so a flip is bookkeeping).
"""

from __future__ import annotations

import dataclasses
import json
import logging
import math
import os
import queue
import threading

import time
import weakref
from collections import deque
from typing import Any, Dict, Iterator, List, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from xllm_service_tpu.config import (
    EngineConfig, InstanceType, ModelConfig)
from xllm_service_tpu.nlp.tokenizer import (
    IncrementalDecoder, Tokenizer, TokenizerFactory)
from xllm_service_tpu.obs import (
    FRONT_MS_HEADER, SCHEDULE_MS_HEADER, Failpoints, REQUEST_ID_HEADER,
    Registry, SpanStore, first_token_stages)
from xllm_service_tpu.obs import steptrace
from xllm_service_tpu.obs.events import EventLog
from xllm_service_tpu.obs.expfmt import quantile_from_buckets
from xllm_service_tpu.runtime.engine import Engine, EngineRequest, StepOutput
from xllm_service_tpu.service.coordination import (
    KEY_MASTER_ADDR, CoordinationStore, instance_prefix)
from xllm_service_tpu.service.store_guard import (
    StoreGuard, StoreOutageError)
from xllm_service_tpu.service.httpd import (
    HttpServer, Request, Response, Router, http_json)
from xllm_service_tpu.service.instance_types import (
    Heartbeat, InstanceMetaInfo, LatencyMetrics, LoadMetrics)
from xllm_service_tpu.service.response_handler import (
    ChatStreamAssembler, CompletionStreamAssembler, ResponseCollector,
    sse_frame, SSE_DONE)
from xllm_service_tpu.utils.misc import short_uuid
from xllm_service_tpu.utils.retry import RetryPolicy
from xllm_service_tpu.utils import threads
from xllm_service_tpu.utils.threads import spawn
from xllm_service_tpu.utils.wire import check_version, stamp
from xllm_service_tpu.utils.types import (
    FinishReason, LogProb, RequestOutput, SamplingParams, SequenceOutput,
    Status, StatusCode, Usage, parse_openai_sampling, validate_sampling)
from xllm_service_tpu.utils.locks import make_lock

logger = logging.getLogger(__name__)

MODEL_AWAKE = "awake"
MODEL_ASLEEP = "asleep"
MODEL_DRAINING = "draining"

# Queue sentinel for a SIMULATED worker death (the die_after_n_tokens
# failpoint): unlike the graceful None sentinel — which closes a stream
# with a tidy [DONE] — _ABORT makes the consumer RAISE so the client
# socket breaks mid-stream, exactly like a SIGKILL'd process.
_ABORT = object()


class DeviceTraceError(RuntimeError):
    """start_device_trace / stop_device_trace asked for in the wrong
    state: a trace is already running, or none is."""


class StepFaultInjected(Exception):
    """Raised by the worker.fault_step* failpoints inside the engine's
    step fault boundary — a deterministic device-plane fault for chaos
    tests (docs/ROBUSTNESS.md, device-plane fault contract)."""


class _EngineFault:
    """Queue sentinel for a request blamed by the step fault boundary:
    the consumer emits the typed ``engine_fault`` error (500 / error
    frame carrying the blame verdict) instead of a generic broken
    stream, so the service can count a poison strike."""

    __slots__ = ("verdict",)

    def __init__(self, verdict: str) -> None:
        self.verdict = verdict


# Posted to the stream writer in an output's place, by a stream's own
# handler: the stream has begun and its sink is attached; no engine
# output came for ``request_timeout_s``.
_ATTACH = object()
_TIMEOUT = object()


def _timeout_frame(limit_s: float) -> bytes:
    """The engine stopped producing (hang, wedged step): a TYPED frame,
    never a silent stall."""
    return sse_frame({"error": {
        "message": f"no engine output within {limit_s:g}s",
        "type": "timeout", "code": 504}})


def _engine_fault_frame(verdict: str) -> bytes:
    """Blamed by the step fault boundary: a TYPED error frame (not a
    broken socket), so that the relay can strike the poison ledger and
    reroute or fail clean (docs/ROBUSTNESS.md)."""
    return sse_frame({"error": {
        "message": f"engine_fault: {verdict}",
        "type": "engine_fault", "code": 500}})


class _Stream:
    """One streamed response's way out, whoever writes it: the frames'
    assembler, and ``done`` once nothing more is to be written
    (``Worker._stream_output`` is what both paths run an output
    through; ``last_t`` is its last clock read). ``path`` says who
    writes: ``"handler"``, the connection's own thread pulling from
    ``live.q`` (``Worker._stream_sse``), or ``"writer"``, the worker's
    ONE stream writer (``Worker._stream_writer_loop``), which then owns
    the stream as ``live.push`` and keeps the rest here: the sink its
    handler attached (``write``), what the engine emitted before that
    (``held``), the frames that lead (``initial``), and ``over``, the
    event the handler's thread parks on until the writer ends the
    stream, ``clean`` or with the socket broken. But for ``write`` and
    ``initial``, set before the handler posts ``_ATTACH``, only the
    writer's thread writes these."""

    __slots__ = ("worker", "live", "asm", "path", "done", "last_t",
                 "initial", "write", "held", "attached", "clean", "over")

    def __init__(self, worker: "Worker", live: "_LiveRequest",
                 path: str) -> None:
        self.worker = worker
        self.live = live
        self.asm = (ChatStreamAssembler if live.is_chat
                    else CompletionStreamAssembler)(
            live.service_request_id, live.model, live.include_usage,
            emit_token_ids=live.emit_token_ids)
        self.path = live.out_path = path
        self.done = False
        self.last_t = 0.0
        self.initial: Any = ()
        self.write: Any = None
        self.held: List[Any] = []
        self.attached = False
        self.clean = True
        self.over = threading.Event()

    def serve(self, write) -> bool:
        """``Response.push``: on the handler's thread, once the headers
        are queued."""
        return self.worker._serve_pushed(self, write)


def _classify_step_fault(exc: BaseException) -> str:
    """Transient device faults (a flaky transport, a device timeout)
    are retried in place with no one blamed; anything else is treated
    as deterministic and attributed by bisection. Matched by type NAME
    for the XLA runtime error so the classification needs no jaxlib
    import at module scope."""
    if isinstance(exc, (TimeoutError, ConnectionError)):
        return "transient"
    if type(exc).__name__ == "XlaRuntimeError" and any(
            tag in str(exc) for tag in
            ("UNAVAILABLE", "DEADLINE_EXCEEDED", "ABORTED", "CANCELLED")):
        return "transient"
    return "deterministic"

# Token-count buckets for the prefill-quantum histogram (pow2 — window
# sizes are bucketed prompt chunks, not latencies, so the default ms
# buckets would be meaningless here).
_PREFILL_QUANTUM_BUCKETS = (
    4.0, 8.0, 16.0, 32.0, 64.0, 128.0, 256.0, 512.0, 1024.0, 2048.0,
    4096.0, 8192.0)


@dataclasses.dataclass
class WorkerOptions:
    host: str = "127.0.0.1"
    port: int = 0
    instance_type: InstanceType = InstanceType.MIX
    service_addr: str = ""              # service RPC address for heartbeats
    model: str = "tiny"
    model_dir: str = ""                 # HF dir (tokenizer + config.json)
    heartbeat_interval_s: float = 3.0
    lease_ttl_s: float = 9.0
    # End-to-end bound on one generation (PD relay reads, import waits).
    request_timeout_s: float = 600.0
    # Concurrent-request admission cap on this worker's HTTP server
    # (reference engine-side brpc max_concurrency; 0 = unlimited). A 503
    # past the cap is the refusal class the service re-dispatches.
    max_concurrency: int = 128
    enable_profiling: bool = False
    memory_budget_gb: float = 60.0
    # PD migration to a decode worker in this process skips the HTTP
    # shuttle and moves KV device-to-device (off to force the wire path,
    # e.g. for testing it).
    pd_direct_kv: bool = True
    # Cross-process device-to-device KV migration over the PJRT transfer
    # server (runtime/kv_wire.py). Auto-degrades to the host shuttle on
    # backends that can't serve transfers; off pins the host shuttle.
    pd_device_wire: bool = True
    # Pre-compile every steady-state engine program (and, for multimodal
    # models, the vision tower) BEFORE self-registration, so no routed
    # request ever pays a compile: one step program compiles in tens of
    # seconds, so a first-request compile blows the TTFT SLO by an order
    # of magnitude. None = auto (on for TPU backends, off on CPU
    # where tests boot dozens of workers and compiles are cheap anyway).
    warmup: Optional[bool] = None
    seed: int = 0
    murmur_seed: int = 0
    # EPD dedicated encode mode (``--role encode``, docs/EPD.md): the
    # vision tower is this worker's ONLY compiled graph — the LM
    # runtime starts asleep (no Engine, no params, no KV pool), the
    # worker registers as ENCODE advertising encode capability + image
    # grid, and generate traffic can never route here.
    encode_only: bool = False


def _decode_kv_blob(meta: Dict[str, Any], blob: bytes):
    """Decode one KV wire body (monolithic /kv/import, one /kv/chunk,
    or a /kv/blocks response): ``blob`` is k-bytes then v-bytes at
    ``meta``'s shape/dtype. The ONE codec lives in runtime/kv_cache.py
    (the disk spill tier shares it). Raises ValueError on a size
    mismatch (the HTTP 400 text)."""
    from xllm_service_tpu.runtime.kv_cache import decode_kv_blob
    return decode_kv_blob(meta, blob)


def _mm_meta(req) -> Optional[Dict[str, Any]]:
    """Multimodal state for a migration meta line (None for text): the
    vision embeddings, splice positions, and mrope prompt streams the
    decode side needs to re-prefill after preemption and to keep the
    sequence out of the content-addressed prefix cache."""
    if req.mm_embeds is None:
        return None
    from xllm_service_tpu.runtime.multimodal import embeds_to_wire
    return {
        "embeds": embeds_to_wire(req.mm_embeds),
        "positions": list(req.mm_positions or []),
        "rope_pos": (req.mm_rope_pos.tolist()
                     if req.mm_rope_pos is not None else None),
    }


_MODEL_REGISTRY = {
    # vocab 512 ≥ ByteTokenizer's id range (256 bytes + specials).
    "tiny": lambda: ModelConfig.tiny(vocab_size=512),
    "llama3-1b": ModelConfig.llama3_1b,
    "llama3-8b": ModelConfig.llama3_8b,
    "qwen2-7b": ModelConfig.qwen2_7b,
    "qwen2.5-7b": ModelConfig.qwen25_7b,
    "qwen3-8b": ModelConfig.qwen3_8b,
    "qwen3-30b-a3b": ModelConfig.qwen3_30b_a3b,
    "phi3-mini": ModelConfig.phi3_mini,
    "mistral-7b": ModelConfig.mistral_7b,
    "mistral-7b-v01": ModelConfig.mistral_7b_v01,
    "gemma2-9b": ModelConfig.gemma2_9b,
    "gemma3-12b": ModelConfig.gemma3_12b,
    "deepseek-v2-lite": ModelConfig.deepseek_v2_lite,
    "deepseek-v3": ModelConfig.deepseek_v3,
    "gpt-oss-20b": ModelConfig.gpt_oss_20b,
    "mixtral-8x7b": ModelConfig.mixtral_8x7b,
    "tiny-moe": lambda: ModelConfig.tiny(num_experts=4),
}


# Workers in this process, by address. PD migration consults it to keep a
# co-hosted transfer device-to-device (export_held(device=True) → direct
# adopt) instead of round-tripping KV bytes through the HTTP shuttle —
# the data plane the reference drives over NCCL stays on-device here.
_LOCAL_WORKERS: "weakref.WeakValueDictionary" = weakref.WeakValueDictionary()


def resolve_model_config(name: str, model_dir: str = "") -> ModelConfig:
    if model_dir:
        import os
        cfg_path = os.path.join(model_dir, "config.json")
        if os.path.exists(cfg_path):
            with open(cfg_path, "r", encoding="utf-8") as f:
                return ModelConfig.from_hf_config(json.load(f), name=name)
    factory = _MODEL_REGISTRY.get(name)
    if factory is None:
        raise ValueError(f"unknown model {name!r}; known: "
                         f"{sorted(_MODEL_REGISTRY)}")
    return factory()


class ModelRuntime:
    """One model's engine + sleep/wakeup state on this worker."""

    def __init__(self, model: str, model_cfg: ModelConfig,
                 engine_cfg: EngineConfig, tokenizer: Tokenizer,
                 mesh=None, seed: int = 0, murmur_seed: int = 0,
                 start_asleep: bool = False, model_dir: str = "") -> None:
        self.model = model
        self.model_cfg = model_cfg
        self.engine_cfg = engine_cfg
        self.tokenizer = tokenizer
        self.mesh = mesh
        self.seed = seed
        self.murmur_seed = murmur_seed
        self.model_dir = model_dir
        self.state = MODEL_ASLEEP if start_asleep else MODEL_AWAKE
        self._host_params: Optional[Any] = None
        self.engine: Optional[Engine] = None
        if not start_asleep:
            self.engine = Engine(model_cfg, engine_cfg,
                                 params=self._load_params(), mesh=mesh,
                                 seed=seed, murmur_seed=murmur_seed)

    def _load_params(self) -> Optional[Any]:
        """Real weights from the HF model dir when present (sharded
        device_put); None → Engine random-inits (tests / shape-only runs)."""
        import glob
        if self.model_dir and glob.glob(
                os.path.join(self.model_dir, "*.safetensors")):
            from xllm_service_tpu.runtime.checkpoint import load_checkpoint
            logger.info("loading %s weights from %s", self.model,
                        self.model_dir)
            return load_checkpoint(self.model_dir, self.model_cfg,
                                   mesh=self.mesh)
        return None

    def sleep(self) -> None:
        """Donate weights to host RAM, drop the KV pool (TPU sleep —
        SURVEY.md §7.1 sleep/wakeup row)."""
        if self.state == MODEL_ASLEEP:
            return
        if self.engine is not None:
            # Settle the decode pipeline first: a step on the device
            # ahead must not be left referencing a pool we are dropping.
            self.engine.drain_pipeline()
            self._host_params = jax.tree_util.tree_map(
                np.asarray, jax.device_get(self.engine.params))
            self.engine = None      # KV pool + device params released
        self.state = MODEL_ASLEEP

    def wakeup(self) -> None:
        """Weights back to HBM (resharded); XLA executable cache makes
        recompilation a no-op."""
        if self.state == MODEL_AWAKE:
            return
        params = None
        if self._host_params is not None:
            import jax.numpy as jnp
            params = jax.tree_util.tree_map(jnp.asarray, self._host_params)
            self._host_params = None
        else:
            params = self._load_params()    # cold wake: real weights
        self.engine = Engine(self.model_cfg, self.engine_cfg,
                             params=params, mesh=self.mesh, seed=self.seed,
                             murmur_seed=self.murmur_seed)
        self.state = MODEL_AWAKE

    @property
    def memory_gb(self) -> float:
        """Rough HBM footprint for the serverless allocator."""
        cfg = self.model_cfg
        if cfg.is_moe:
            # Every expert's weights are resident (Mixtral: E dense-width
            # MLPs; Qwen3-MoE: E narrow moe_intermediate_size MLPs) —
            # counting one dense MLP under-places a 30B MoE by ~8x.
            f = cfg.moe_intermediate_size or cfg.intermediate_size
            mlp = cfg.num_experts * 3 * cfg.hidden_size * f \
                + cfg.hidden_size * cfg.num_experts      # router
        else:
            mlp = 3 * cfg.hidden_size * cfg.intermediate_size
        n_params = (cfg.vocab_size * cfg.hidden_size * 2
                    + cfg.num_layers * (
                        4 * cfg.hidden_size * cfg.num_heads * cfg.head_dim
                        + mlp))
        # A state by slot is the cache of a model that keeps one (all of
        # it where no layer attends): 1 + 3 x max_batch_size slots
        # (Engine.__init__), float32.
        slots = 1 + 3 * self.engine_cfg.max_batch_size
        state = 4 * cfg.num_state_layers * slots * math.prod(
            cfg.state_shape) if cfg.num_state_layers else 0
        return (2.0 * n_params + state) / 1e9


class _StopWatcher:
    """Detokenizer-level OpenAI ``stop`` string matching with holdback.

    Streams may not emit text that could be the prefix of a stop string;
    ``feed`` returns only safe-to-emit text and flags ``stopped`` when a
    stop sequence appears (the stop text itself is never emitted)."""

    __slots__ = ("stops", "pending", "stopped")

    def __init__(self, stops: Optional[List[str]]) -> None:
        self.stops = [s for s in (stops or []) if s]
        self.pending = ""
        self.stopped = False

    def feed(self, text: str) -> str:
        if not self.stops or self.stopped:
            return text
        self.pending += text
        idx = -1
        for s in self.stops:
            i = self.pending.find(s)
            if i >= 0 and (idx < 0 or i < idx):
                idx = i
        if idx >= 0:
            self.stopped = True
            out, self.pending = self.pending[:idx], ""
            return out
        hold = 0
        for s in self.stops:
            m = min(len(s) - 1, len(self.pending))
            for h in range(m, 0, -1):
                if s.startswith(self.pending[len(self.pending) - h:]):
                    hold = max(hold, h)
                    break
        if hold:
            out = self.pending[:-hold]
            self.pending = self.pending[-hold:]
        else:
            out, self.pending = self.pending, ""
        return out

    def flush(self) -> str:
        out, self.pending = self.pending, ""
        return out


def _moe_record(st: Dict[str, int]) -> Optional[Dict[str, Any]]:
    """The step record's ``moe``: what the step's sparse layers counted
    (``Engine.last_step_moe``), and ``load_max_over_mean``: rows of a
    layer's busiest expert (mean over the step's sparse layers) over the
    mean rows of the experts touched. None where the step routed
    nothing, or the family's layer does not count."""
    if not (st["assignments"] and st["layers"] and st["experts_touched"]):
        return None
    return {"assignments": st["assignments"],
            "experts_touched": st["experts_touched"],
            "dropped": st["dropped"],
            "elsewhere": st["elsewhere"],
            "load_max_over_mean": round(
                (st["load_max"] / st["layers"])
                / (st["assignments"] / st["experts_touched"]), 3)}


def _loop_record(st: Dict[str, Any]):
    """The step record's ``passes`` and ``exit_cdf`` of a looped model's
    step (``Engine.last_step_loop``): the layer passes its programs ran,
    and over its decode rows the mean cumulative exit probability after
    each pass but the last (None where it decoded no row)."""
    rows = st["rows"]
    return (sum(st["passes"].values()),
            [round(x / rows, 6) for x in st["cdf_sum"]] if rows else None)


def _header_ms(headers: Dict[str, str], name: str) -> Optional[float]:
    """A duration header's milliseconds; None where it is absent or not
    a number (a direct caller, an older master)."""
    try:
        return float(headers[name])
    except (KeyError, ValueError):
        return None


def _merge_step_outputs(outs: List[StepOutput]) -> StepOutput:
    """Concatenate held-back deltas of one choice (in arrival order) into
    a single StepOutput; the final element supplies finish state."""
    last = outs[-1]
    merged = StepOutput(
        request_id=last.request_id,
        new_token_ids=[t for o in outs for t in o.new_token_ids],
        logprobs=[l for o in outs for l in o.logprobs],
        finish_reason=last.finish_reason,
        num_prompt_tokens=last.num_prompt_tokens,
        num_generated=last.num_generated)
    if any(o.top_logprobs for o in outs):
        merged.top_logprobs = [row for o in outs
                               for row in (o.top_logprobs or [])]
    return merged


class _Choice:
    """Per-choice (OpenAI ``n`` / ``best_of`` candidate) streaming state."""

    __slots__ = ("decoder", "stopper", "completion_tokens", "finished",
                 "cum_logprob", "echo_done", "pending")

    def __init__(self, decoder: IncrementalDecoder,
                 stops: Optional[List[str]]) -> None:
        self.decoder = decoder
        self.stopper = _StopWatcher(stops)
        self.completion_tokens = 0
        self.finished = False
        self.cum_logprob = 0.0
        self.echo_done = False
        # echo+logprobs, multi-candidate: deltas held back until the
        # (single, shared) prompt scoring arrives from candidate 0.
        self.pending: List[StepOutput] = []


class _LiveRequest:
    """Host-side streaming state of one in-flight request (all ``n``
    choices; engine request ids are ``<srid>`` for n=1, ``<srid>#k``
    otherwise)."""

    __slots__ = ("req", "q", "tokenizer", "choices", "engine_rids",
                 "stream_to_service", "service_request_id", "model",
                 "is_chat", "stream", "include_usage", "first_out_time",
                 "sampling", "prompt_tokens", "target_n", "prompt_lps",
                 "_echo_cache", "emit_token_ids", "stamps", "front_ms",
                 "tok_wake_s", "tok_write_s", "tok_n", "out_n", "out_path",
                 "push")

    def __init__(self, req: EngineRequest, tokenizer: Tokenizer,
                 service_request_id: str, model: str, is_chat: bool,
                 stream: bool, include_usage: bool,
                 stream_to_service: bool, n: int = 1,
                 stops: Optional[List[str]] = None) -> None:
        self.req = req
        self.q: "queue.Queue[Optional[StepOutput]]" = queue.Queue()
        self.tokenizer = tokenizer
        self.service_request_id = service_request_id
        self.model = model
        self.is_chat = is_chat
        self.stream = stream
        self.include_usage = include_usage
        self.stream_to_service = stream_to_service
        self.first_out_time = 0.0
        # The request's first-token chain (obs/spans.py
        # FIRST_TOKEN_STAMPS): stamp -> this process's monotonic clock,
        # written where each stage ends; None once the handler's thread
        # has folded it (``Worker._fold_first_token``), so a request past
        # its first token carries nothing. ``front_ms``: the master's
        # share, as the forward's header gave it.
        self.stamps: Optional[Dict[str, float]] = {}
        self.front_ms: Optional[float] = None
        # Every token from ``emit`` to the wire, summed by the thread
        # that writes it (``token_out``) until ``Worker._fold_token_out``
        # moves them into the counters: seconds from the emit until that
        # thread has the output in hand, seconds from there to the frame
        # written, tokens; and the outputs ``Worker._stream_output`` ran,
        # under the path that ran them.
        self.tok_wake_s = 0.0
        self.tok_write_s = 0.0
        self.tok_n = 0
        self.out_n = 0
        self.out_path = ""
        # The stream's state where the worker's writer owns it
        # (``Worker._writer_adopt``); None: outputs go to ``q``.
        self.push: Optional[_Stream] = None
        n = max(1, n)
        self.engine_rids = ([service_request_id] if n == 1 else
                            [f"{service_request_id}#{k}" for k in range(n)])
        self.choices = [_Choice(IncrementalDecoder(tokenizer), stops)
                        for _ in range(n)]
        self.prompt_tokens = 0
        # best_of: ``n`` above is the CANDIDATE count; target_n is how
        # many survive server-side selection (set by _parse_generate).
        self.target_n = n
        # Recovery ledger extension (service-set "ledger_tokens" on the
        # forwarded body): stream assemblers include per-frame token
        # ids under a top-level "xllm" key the service strips.
        self.emit_token_ids = False
        # echo+logprobs: prompt-token scores, computed ONCE (candidate 0)
        # and shared by every choice's echo emission.
        self.prompt_lps: Optional[List[Optional[float]]] = None
        # (decoded prompt text, prompt LogProb entries) — identical for
        # every choice; built once on first echo emission.
        self._echo_cache: Optional[tuple] = None

    def echo_prefix(self) -> tuple:
        """(prompt_text, prompt LogProbs) for echo — cached: a best_of
        pool must not re-decode the whole prompt per choice."""
        if self._echo_cache is None:
            text = self.tokenizer.decode(list(self.req.token_ids))
            lps = []
            if self.sampling.logprobs and self.prompt_lps:
                for tid, plp in zip(self.req.token_ids, self.prompt_lps):
                    lps.append(LogProb(
                        token=self.tokenizer.decode([tid]), token_id=tid,
                        logprob=plp, top_logprobs=[]))
            self._echo_cache = (text, lps)
        return self._echo_cache

    def stamp(self, name: str, t: Optional[float] = None) -> None:
        """Take one stamp of the first-token chain (now, or ``t``)."""
        stamps = self.stamps
        if stamps is not None:
            stamps[name] = time.monotonic() if t is None else t

    def token_out(self, out: StepOutput, wake: float,
                  written: float) -> bool:
        """Book one output's tokens (on the thread that writes them):
        each waited ``wake - out.emit_t`` for this thread to turn to it
        and ``written - wake`` for its frame. True when a fold is due."""
        n = len(out.new_token_ids)
        if n and out.emit_t:
            self.tok_wake_s += n * (wake - out.emit_t)
            self.tok_write_s += n * (written - wake)
            self.tok_n += n
        return self.tok_n >= 64

    def choice_index(self, engine_rid: str) -> int:
        if len(self.choices) == 1:
            return 0
        try:
            return int(engine_rid.rsplit("#", 1)[1])
        except (IndexError, ValueError):
            return 0

    @property
    def decoder(self) -> IncrementalDecoder:
        # Single-choice shorthand used by the PD migration paths.
        return self.choices[0].decoder

    @property
    def all_finished(self) -> bool:
        return all(c.finished for c in self.choices)


class Worker:
    # Per-input token cap for /v1/embeddings (pow2-bucketed compile
    # shape); over-limit inputs get a 400 naming this limit — never a
    # silent truncation (tests/test_e2e.py pins the semantics).
    EMBED_MAX_TOKENS = 256

    def __init__(self, opts: WorkerOptions, store: CoordinationStore,
                 engine_cfg: Optional[EngineConfig] = None,
                 mesh=None) -> None:
        self.opts = opts
        self.store = store
        self.mesh = mesh
        self.instance_type = opts.instance_type
        self.engine_cfg = engine_cfg or EngineConfig()
        self.tokenizer = TokenizerFactory.create_tokenizer(opts.model_dir)

        if opts.encode_only:
            self.instance_type = InstanceType.ENCODE
            self.opts.instance_type = InstanceType.ENCODE
        self.runtimes: Dict[str, ModelRuntime] = {}
        primary_cfg = resolve_model_config(opts.model, opts.model_dir)
        if (primary_cfg.num_conv_layers or primary_cfg.num_state_layers
                or primary_cfg.num_swa_layers) \
                and self.instance_type in (InstanceType.PREFILL,
                                           InstanceType.DECODE):
            # Its pages do not move between workers (Engine.pages_only):
            # a disaggregated role could only ever fall back.
            raise ValueError(
                f"{opts.model} keeps more than (k, v) pages (convolution "
                f"tails that ride the page table, a state by slot, window "
                f"layers' keys and values in a second pool): "
                f"instance type {self.instance_type.value} (PD migration) "
                f"is refused; serve it as DEFAULT or MIX")
        # Encode-only mode: the LM runtime starts asleep — engine=None,
        # no params, no KV pool. Every heartbeat/metrics/registration
        # path already handles an asleep runtime; the vision tower
        # below is this worker's only XLA program.
        self.runtimes[opts.model] = ModelRuntime(
            opts.model, primary_cfg, self.engine_cfg, self.tokenizer,
            mesh=mesh, seed=opts.seed, murmur_seed=opts.murmur_seed,
            model_dir=opts.model_dir, start_asleep=opts.encode_only)

        self._live: Dict[str, _LiveRequest] = {}        # engine rid → live
        self._live_srid: Dict[str, _LiveRequest] = {}   # srid → live
        self._live_lock = make_lock("worker.live", 10)
        # Outputs queued for the service fan-in ahead of the next engine
        # dispatch (ordering: appended under the engine lock, drained by
        # the engine-loop thread before it pushes step outputs — no network
        # calls ever happen inside the engine lock).
        self._service_push_buffer: List[RequestOutput] = []
        # Engines are single-threaded; HTTP threads and the loop thread
        # serialize on this (submission is cheap, steps hold it for one
        # iteration).
        self._engine_lock = make_lock("worker.engine", 20)
        self._work_event = threading.Event()
        self._stop = threading.Event()
        self._latency = LatencyMetrics()
        # Per-worker observability: metrics registry + span ring. Per
        # WORKER, not process-global — the test harness co-locates
        # several workers serving the same model name in one process,
        # and model-labeled series must not collide across them
        # (obs/metrics.py module docstring). The engine loop flushes
        # step-level stats here each iteration; /metrics renders it.
        self.obs = Registry()
        self.spans = SpanStore(capacity=int(os.environ.get(
            "XLLM_SPAN_RING", "2048")))
        # Worker-plane event ring: thread crashes (and any future
        # worker-local lifecycle events) land here so a supervised
        # restart is an EVENT, not just a log line. Small — the service
        # plane's ring is the cluster's memory; this one is the
        # worker's own black box.
        self.events = EventLog(capacity=256)
        # Device-plane step flight recorder (obs/steptrace.py): one
        # fixed-schema record per engine iteration into a bounded ring,
        # served on GET /admin/steptrace and shipped as a heartbeat
        # tail. XLLM_STEPTRACE=0 collapses the whole recording path to
        # the single `if enabled:` branch in _flush_engine_obs.
        self.steptrace = steptrace.StepTrace()
        # Per-model cumulative-ledger snapshots backing the per-STEP
        # deltas in the records (phase ms, speculation outcomes, prefix
        # hit tokens, free pages). Engine-loop thread only.
        self._st_phase_snap: Dict[str, Dict[str, float]] = {}
        self._st_spec_snap: Dict[str, Dict[str, int]] = {}
        self._st_prefix_snap: Dict[str, int] = {}
        self._st_state_snap: Dict[str, Dict[str, int]] = {}
        self._st_free_pages: Dict[str, int] = {}
        # The directory of the device trace that is running
        # (start_device_trace), None when none is. jax's profiler allows
        # one session a process and refuses a second itself.
        self._devtrace_dir: Optional[str] = None
        # Highest step seq already DELIVERED on a heartbeat; committed
        # only on an acked beat (same discipline as _hb_step_cum).
        self._hb_steps_seq = 0                  # guarded-by: worker.hb
        # The devices this worker's engines live on (the mesh's, or the
        # process's first device for a meshless engine), resolved once
        # here — device enumeration is not hot-path safe — and reported
        # on GET /admin/steptrace. A query that fails raises: a worker
        # that cannot name its device must not come up calling it "cpu".
        self._devices = (list(mesh.devices.flat) if mesh is not None
                         else jax.devices()[:1])
        self._device_kind = self._devices[0].device_kind
        # Deterministic fault injection (obs/failpoints.py): per-worker
        # so the co-located test harness can kill ONE of two in-process
        # workers; armed via XLLM_FAILPOINTS and POST /admin/failpoint.
        # Trips surface as xllm_failpoints_tripped_total{name}.
        self.failpoints = Failpoints(obs=self.obs)
        # Device-plane fault containment (docs/ROBUSTNESS.md): the
        # engine loop's step dispatch runs inside a fault boundary that
        # evicts the blamed request set (attributed by bisection under
        # _fault_bisect_budget extra probe steps) and resumes, instead
        # of dying. The crash-loop breaker falls back to today's
        # visible engine death once _fault_times exceeds the limit
        # inside the window — containment can never loop forever on
        # corrupt state.
        self._fault_bisect_budget = int(os.environ.get(
            "XLLM_FAULT_BISECT_BUDGET", "4") or 4)
        self._fault_limit = int(os.environ.get(
            "XLLM_ENGINE_FAULT_LIMIT", "5") or 5)
        self._fault_window_s = float(os.environ.get(
            "XLLM_ENGINE_FAULT_WINDOW_S", "60") or 60)
        # Flag discipline (xlint flag-registry): serving-path knobs are
        # read ONCE here at config time, never per-request — a per-call
        # environ read makes the effective config mutable mid-flight
        # and re-parses strings on the hot path. Tests monkeypatch the
        # env and THEN construct the Worker, so __init__ is the
        # latest-safe read point.
        self._vision_image_size = int(os.environ.get(
            "XLLM_VISION_IMAGE_SIZE", "224") or 224)
        try:
            self._encode_timeout_s = float(os.environ.get(
                "XLLM_ENCODE_TIMEOUT_S", "120") or 120)
        except ValueError:
            self._encode_timeout_s = 120.0
        try:
            self._kv_shuttle_chunk_mb = float(os.environ.get(
                "XLLM_KV_SHUTTLE_CHUNK_MB", "32"))
        except ValueError:
            self._kv_shuttle_chunk_mb = 32.0
        try:
            self._kv_fetch_timeout_s = float(os.environ.get(
                "XLLM_KV_FETCH_TIMEOUT_S", "15") or 15)
        except ValueError:
            self._kv_fetch_timeout_s = 15.0
        # Contained-fault timestamps inside the breaker window; engine-
        # loop thread only.
        self._fault_times: "deque[float]" = deque()
        # Engine request ids marked as poison pills by the
        # worker.fault_step_req failpoint. guarded-by: worker.engine
        self._fault_marked: set = set()
        # Liveness flag behind xllm_worker_engine_alive and the
        # heartbeat's LoadMetrics.engine_alive: True while the engine
        # loop serves, False once the breaker let it die. Plain bool —
        # written by the engine-loop thread, read by heartbeat/scrape
        # (benign race).
        self._engine_loop_alive = True
        # Store guard (service/store_guard.py): this worker's own view
        # of coordination-store health, wired to ITS failpoints so the
        # co-located harness blacks out one plane without touching its
        # twin. On heal the worker idempotently re-establishes lease +
        # registration instead of self-fencing over a store-only outage.
        if not isinstance(self.store, StoreGuard):
            self.store = StoreGuard(self.store,
                                    failpoints=self.failpoints,
                                    events=self.events)
        self.store.on_heal(self._on_store_heal)
        # Simulated death (worker.die_after_n_tokens): refuses work,
        # drops liveness, breaks streams — but the process survives.
        self._dead = False
        # Heartbeat backoff against a down master: the loop keeps
        # ticking (store keepalive must continue — master-down is not
        # worker-dead) but beat SENDS back off exponentially with full
        # jitter so a restarting master isn't thundering-herded by the
        # fleet. Resets on the first acked beat.
        self._hb_backoff = RetryPolicy(
            max_attempts=1,     # unused: the loop is unbounded
            base_delay_s=opts.heartbeat_interval_s,
            max_delay_s=float(os.environ.get(
                "XLLM_HB_BACKOFF_CAP_S", "30") or 30),
            multiplier=2.0, jitter=0.5)
        # Registration (store write) retry at boot — same policy shape.
        self._reg_retry = RetryPolicy(max_attempts=5, base_delay_s=0.2,
                                      max_delay_s=5.0)
        # Serializes heartbeat BUILD+SEND: without it a pre-drain
        # heartbeat still in flight can land after the drain heartbeat
        # and re-mark the models awake at the router.
        self._hb_lock = make_lock("worker.hb", 5)
        # Undelivered heartbeat cache delta (KvCacheEvent), retried on
        # the next beat. Touched only under _hb_lock.
        self._hb_cache_pending = None           # guarded-by: worker.hb
        # Highest master epoch this worker has acked (fenced elections,
        # docs/ROBUSTNESS.md): a beat-ack carrying a LOWER epoch comes
        # from a deposed master and is rejected like a failed beat, so
        # the backoff + advertised-address re-read retarget us to the
        # real master. Touched only under _hb_lock.
        self._master_epoch = 0                  # guarded-by: worker.hb
        # Last-shipped cumulative step_ms bucket counts per
        # (model, phase): the heartbeat diffs against these so
        # LatencyMetrics.step_ms_p99 is the p99 of the steps since the
        # PREVIOUS beat (a recent signal the service watchdog can
        # baseline), not a boot-cumulative average that dampens
        # regressions. Touched only under _hb_lock.
        self._hb_step_cum: Dict[Any, List[Any]] = {}  # guarded-by: worker.hb
        self._decode_to_service = False
        # Heartbeat / generation-push target. Starts at the configured
        # address and FOLLOWS the store's master advertisement
        # (KEY_MASTER_ADDR): after a service-replica takeover the worker
        # retargets instead of orphaning on the dead master's address.
        # The (addr, stale) PAIR is written from two threads — the
        # store's watch dispatcher (_on_master_addr) and the heartbeat
        # loop (_adopt_advertised_addr / _refresh_service_config) — so
        # it gets its own innermost mutex: without it the hb loop's
        # "stale = not fetched" could clobber a concurrent retarget's
        # stale=True and never re-fetch the new master's config (xlint
        # thread-root-race finding XLINT13-001).
        self._addr_mu = make_lock("worker.addr", 89)
        self._service_addr = opts.service_addr  # guarded-by: worker.addr
        self._addr_watch: Optional[int] = None
        # Set on retarget; the heartbeat loop re-fetches /rpc/config so
        # the decode-response topology follows the new master's mode.
        self._service_config_stale = False      # guarded-by: worker.addr
        # Graceful shutdown: while draining, heartbeats advertise every
        # model as "draining" (the router neither routes to nor wakes
        # those), new generate calls get 503, and stop() waits for
        # in-flight work. _inflight_parse (under _live_lock) counts
        # requests accepted but not yet registered in _live_srid — the
        # drain loop must not declare idle inside that window.
        self._draining = False
        # Refusal starts only after the drain state is acknowledged (or
        # its push retries are exhausted): a 503 issued while the router
        # still considers us healthy would surface to end clients.
        self._refuse_new = False
        self._inflight_parse = 0
        # PD relay/migrate streams proxied by THIS worker after its own
        # live entry is finalized — drain must wait for them too.
        self._relay_streams = 0

        router = Router()
        router.route("GET", "/hello", lambda r: Response.json({"ok": True}))
        router.route("POST", "/v1/chat/completions",
                     lambda r: self._serve_generate(r, is_chat=True))
        router.route("POST", "/v1/completions",
                     lambda r: self._serve_generate(r, is_chat=False))
        router.route("GET", "/v1/models", self._serve_models)
        router.route("GET", "/metrics", self._serve_metrics)
        router.route("POST", "/sleep", self._serve_sleep)
        router.route("POST", "/wakeup", self._serve_wakeup)
        router.route("POST", "/fork_master", self._serve_fork_master)
        router.route("POST", "/flip_role", self._serve_flip_role)
        router.route("POST", "/cancel", self._serve_cancel)
        router.route("POST", "/kv/import", self._serve_kv_import)
        router.route("POST", "/kv/chunk", self._serve_kv_chunk)
        router.route("POST", "/kv/blocks", self._serve_kv_blocks)
        router.route("POST", "/kv/blocks_done",
                     self._serve_kv_blocks_done)
        router.route("POST", "/encode", self._serve_encode)
        router.route("POST", "/encode_done", self._serve_encode_done)
        router.route("POST", "/v1/embeddings", self._serve_embeddings)
        router.route("POST", "/admin/failpoint", self._serve_failpoint)
        router.route("GET", "/admin/failpoints",
                     self._serve_failpoints)
        router.route("GET", "/admin/steptrace", self._serve_steptrace)
        router.route("POST", "/admin/devtrace", self._serve_devtrace)
        self._router = router
        # Jitted embedding fns keyed by model name — a multi-model worker
        # must never run model B's params through model A's closed-over
        # ModelConfig (rope theta / eps / head counts differ).
        self._embed_fns: Dict[str, Any] = {}
        # EPD vision encoder (lazy; eager for dedicated ENCODE workers).
        self._vision = None
        self._vision_lock = make_lock("worker.vision", 90)
        if opts.instance_type == InstanceType.ENCODE:
            self._get_vision()
        # EPD encode-stage timing book (BASELINE.md row 5).
        self.encode_seconds = 0.0
        self.encode_calls = 0
        self.encode_images_total = 0
        # --- EPD encode plane (docs/EPD.md) ---------------------------
        # Batched encode queue: every tower invocation on this worker —
        # remote /encode calls AND the local-fallback path — goes
        # through one queue drained by the supervised encode loop, so
        # concurrent requests batch into one tower step and the queue
        # depth in heartbeats is an honest pressure signal.
        self._encode_q: "queue.Queue" = queue.Queue()
        # Content-addressed embedding cache keyed by image digest
        # (multimodal.image_digest — same spirit as the PR-7 prefix
        # index): repeated images skip the tower. LRU, bounded by
        # XLLM_EMBED_CACHE_CAP entries (literal env read for the
        # flag-registry xlint rule).
        import collections as _collections
        self._embed_cache: "_collections.OrderedDict[str, np.ndarray]" \
            = _collections.OrderedDict()
        self._embed_cache_cap = int(os.environ.get(
            "XLLM_EMBED_CACHE_CAP", "256") or 256)
        self._embed_mu = make_lock("worker.embedcache", 87)
        # Heartbeat delta of cache digests (stored/evicted since the
        # last delivered beat) + recent per-step tower durations (ms)
        # for the service-side encode SLO. All guarded-by:
        # worker.embedcache; the heartbeat drains them under worker.hb
        # → worker.embedcache (ranks 5 → 87, increasing).
        self._embed_stored_pending: List[str] = []
        self._embed_removed_pending: List[str] = []
        self._encode_recent_ms: List[float] = []
        # Encode step ledger (mirrors the engine's step books): steps
        # run, images per step, cache outcomes.
        self.encode_steps = 0
        self.encode_cache_hits = 0
        self.encode_cache_misses = 0
        # Device-wire embedding handoff, holder side (mirrors
        # _kv_fetch_staged): tickets staged for a requester's pull,
        # uuid → (staged_at, wire). Released by /encode_done or the
        # heartbeat loop's TTL sweep.
        self._encode_staged: Dict[int, Tuple[float, Any]] = {}
        self._encode_staged_mu = make_lock("worker.encstage", 26)
        # KV-migration throughput book (BASELINE.md north-star metric).
        self.kv_migration_bytes = 0
        self.kv_migration_seconds = 0.0
        self.kv_migration_direct = 0    # device-to-device (no host copy)
        self.kv_migration_device_wire = 0  # cross-process PJRT transfer
        self.kv_migration_chunked = 0   # pipelined host-shuttle sends
        # Decode-side staging for the chunked shuttle: srid → parts.
        # TTL-evicted (a prefill that died mid-send must not pin device
        # buffers forever).
        self._kv_chunk_staging: Dict[str, Dict[str, Any]] = {}
        self._kv_chunk_mu = threading.Lock()
        # Decode peers that proved unable to pull the device wire (424):
        # stop offering and take the host shuttle straight away.
        self._wire_refused: set = set()
        # Cross-worker cached-block fetch (docs/KV_CACHE.md), holder
        # side: wire tickets staged for a requester's pull, uuid →
        # (staged_at, wire). Released by /kv/blocks_done or the
        # heartbeat loop's TTL sweep (a requester that died mid-pull
        # must not pin device blocks forever).
        self._kv_fetch_staged: Dict[int, Tuple[float, Any]] = {}
        self._kv_fetch_mu = make_lock("worker.kvfetch", 25)
        # Requester-side fetch book (xllm_worker_kv_fetch_* on
        # /metrics): outcomes + transferred bytes.
        self.kv_fetch_attempts = 0
        self.kv_fetch_failures = 0
        self.kv_fetch_bytes = 0
        # Measured prefill throughput for the heartbeat's cost-model
        # signal: cumulative prompt tokens / wall seconds over prefill
        # steps (engine-loop thread writes, heartbeat reads — benign).
        self._prefill_tok_cum = 0
        self._prefill_s_cum = 0.0
        # Admission guards the ENTRY endpoints (/v1/* generate /
        # embeddings — the ones the service re-dispatches on 503).
        # Control verbs and mid-request continuation traffic are exempt:
        # shedding /sleep desyncs the router's model-state map, and
        # shedding /kv/import or /encode breaks an already-admitted
        # request's PD/EPD pipeline instead of reducing load.
        from xllm_service_tpu.service.httpd import _ADMISSION_EXEMPT
        self._srv = HttpServer(
            opts.host, opts.port, router,
            max_concurrency=lambda: self.opts.max_concurrency,
            admission_exempt=_ADMISSION_EXEMPT + (
                "/sleep", "/wakeup", "/cancel", "/flip_role",
                "/fork_master", "/kv/import", "/kv/chunk", "/kv/blocks",
                "/kv/blocks_done", "/encode", "/encode_done"))
        self.name = self._srv.address
        # The worker's ONE stream writer (``_stream_writer_loop``): an
        # iteration's outputs for the streams it owns reach it as one
        # item of this queue (a sequence of ``(live, output)`` pairs, in
        # emit order), a stream's ends and its handler's posts as items
        # of one pair, ``None`` at stop. It owns a streamed response
        # served by a server whose chunk call cannot block
        # (``_writer_adopt``); ``_writer_owned`` holds the streams it
        # has seen and not yet ended, ``_writer_batch`` the item in its
        # hands (both its thread's alone). ``_writer_closed``: it takes
        # no more streams (set at stop, once).
        self._writer_q: "queue.SimpleQueue[Any]" = queue.SimpleQueue()
        self._writer_owned: set = set()
        self._writer_batch: Any = ()
        self._writer_closed = False     # guarded-by: worker.live
        # Iterations handed to the writer and the outputs in them (the
        # engine loop's thread counts; a scrape mirrors them).
        self._writer_batches = 0
        self._writer_batch_outs = 0

        # Supervised roots (utils/threads.py): an uncaught exception
        # logs + counts (xllm_thread_crashes_total) + emits
        # thread_crashed instead of killing the thread silently. The
        # heartbeat loop RESTARTS with jittered backoff — a dead beat
        # loop is indistinguishable from a dead worker to the master
        # (lease expiry) — while the engine loop stays DELIBERATELY
        # non-restarting: step faults are already contained INSIDE the
        # loop by the fault boundary (_contain_engine_fault — classify,
        # bisect blame, fault_reset, resume; docs/ROBUSTNESS.md
        # device-plane fault contract), so an exception that still
        # escapes means containment itself failed (crash-loop breaker
        # or boundary bug) and device state is unknown — a supervised
        # visible death (engine_alive gauge 0 → engine_dead anomaly →
        # lease-expiry recovery) is correct where a blind restart could
        # silently serve from a broken pool.
        self._loop_thread = spawn(
            "worker.engine_loop", self._engine_loop,
            thread_name=f"worker-loop-{self.name}",
            events=self.events, stop=self._stop)
        self._hb_thread = spawn(
            "worker.hb_loop", self._heartbeat_loop,
            thread_name=f"worker-hb-{self.name}",
            restart=threads.RESTART_POLICY,
            events=self.events, stop=self._stop)
        # EPD encode loop (docs/EPD.md): drains the batched encode
        # queue, one tower step per drain. RESTARTS on a crash — a
        # silently dead encode loop would hang every queued /encode
        # call until its deadline instead of failing visibly (per-job
        # errors are caught inside the step; a restart only fires on a
        # bug escaping the step harness).
        self._encode_thread = spawn(
            "worker.encode_loop", self._encode_loop,
            thread_name=f"worker-encode-{self.name}",
            restart=threads.RESTART_POLICY,
            events=self.events, stop=self._stop)
        # The stream writer RESTARTS: a crash breaks the streams it
        # owned (none is left hanging) and the next ones need a writer.
        self._writer_thread = spawn(
            "worker.stream_writer", self._stream_writer_loop,
            thread_name=f"worker-writer-{self.name}",
            restart=threads.RESTART_POLICY,
            events=self.events, stop=self._stop)
        # Registration plane: one lock serializes every revoke→grant→put
        # re-registration (boot retry, hb-loop lease re-establishment,
        # role flip) so racing registrars can't interleave lease grants
        # and leak one.
        self._reg_mu = make_lock("worker.reg", 8)
        self._lease_id: Optional[int] = None  # guarded-by: worker.reg
        # Set by the store-guard heal callback; the hb loop performs
        # the actual re-registration. A heal callback must never call
        # _register itself: its own lease_revoke/lease_grant may be the
        # very call that healed the guard, and re-entering _register
        # under worker.reg would deadlock.
        self._heal_pending = threading.Event()

    # ------------------------------------------------------------------
    # Lifecycle
    # ------------------------------------------------------------------
    @staticmethod
    def _warmup_extended() -> bool:
        return os.environ.get("XLLM_WARMUP_EXTENDED", "1") != "0"

    def _should_warmup(self) -> bool:
        if self.opts.warmup is not None:
            return self.opts.warmup
        return self._devices[0].platform == "tpu"

    def _warmup_all(self) -> None:
        """Registered = ready: compile every steady-state program before
        the instance becomes routable (the reference's engine arrives
        warmed; here the engine is in-repo so the worker owns it)."""
        for name, rt in self.runtimes.items():
            if rt.engine is None:
                continue
            # Engines are single-threaded and warmup drives DONATED-KV
            # jitted steps: the HTTP server is already up (start() binds
            # it first), so a concurrent /sleep or KV export racing an
            # in-flight warmup step would use-after-donate the pool —
            # hold the same lock every other engine toucher holds.
            with self._engine_lock:
                t = rt.engine.warmup(extended=self._warmup_extended())
            logger.info("engine warmup for %s: %.1fs", name, t)
        # Vision tower (fixed serve-time grid = exactly one program):
        # without this the FIRST image request pays the tower compile.
        if any(rt.model_cfg.is_mrope for rt in self.runtimes.values()):
            t0 = time.monotonic()
            try:
                self.encode_images(["random:0"])
                logger.info("vision warmup: %.1fs",
                            time.monotonic() - t0)
            except Exception as e:  # noqa: BLE001 — a missing tower dir
                # must not block a text-only deployment of a VLM config
                logger.warning("vision warmup skipped: %s", e)

    def start(self) -> "Worker":
        self._srv.start()
        _LOCAL_WORKERS[self.name] = self
        if self._should_warmup():
            self._warmup_all()
        # Registration writes through the coordination store — retry a
        # boot-time store hiccup with capped, jittered backoff instead
        # of crashing the (already warmed) worker on one bad RPC. A
        # store OUTAGE (guard-classed) is not a hiccup: the registration
        # queues until the store heals (docs/ROBUSTNESS.md outage
        # contract) — outage waits don't burn the finite retry budget.
        attempt = 0
        outage_waits = 0
        while not self._stop.is_set():
            try:
                self._register()
                break
            except StoreOutageError as e:
                outage_waits += 1
                if outage_waits == 1 or outage_waits % 10 == 0:
                    logger.warning("store outage at boot (%s); "
                                   "registration queued until heal", e)
                self._reg_retry.sleep(min(outage_waits - 1, 4),
                                      stop_event=self._stop)
            except Exception as e:  # noqa: BLE001 — transient store error
                attempt += 1
                if attempt >= self._reg_retry.max_attempts \
                        or self._stop.is_set():
                    raise
                logger.warning("registration attempt %d failed (%s); "
                               "backing off", attempt, e)
                self._reg_retry.sleep(attempt - 1, stop_event=self._stop)
        # A heal that fired during the boot retry loop is satisfied by
        # the successful registration above.
        self._heal_pending.clear()
        # Failover-follow is only for workers CONFIGURED with a service in
        # front: a deliberately standalone worker sharing the store must
        # not silently adopt the advertised master and start taking
        # routed traffic.
        if self.opts.service_addr:
            # Adopt the advertised master address (may differ from the
            # configured one after a takeover that happened before we
            # booted), then follow future changes.
            self._adopt_advertised_addr()
            self._addr_watch = self.store.add_watch(
                KEY_MASTER_ADDR, self._on_master_addr)
        self._loop_thread.start()
        self._writer_thread.start()
        self._hb_thread.start()
        self._encode_thread.start()
        return self

    @property
    def service_addr(self) -> str:
        """Current service RPC target (configured, then store-advertised)."""
        return self._service_addr

    def _retarget(self, info) -> bool:
        """Adopt an advertised master address if it differs from the
        current target. Marks the service config stale — the heartbeat
        loop re-fetches /rpc/config (never HTTP from the watch thread,
        it must stay responsive to further events). Compare-and-swap
        under worker.addr: this runs on BOTH the watch thread and the
        hb thread (XLINT13-001)."""
        rpc = (info or {}).get("rpc")
        if not rpc:
            return False
        with self._addr_mu:
            if rpc == self._service_addr:
                return False
            old = self._service_addr
            self._service_addr = rpc
            self._service_config_stale = True
        logger.info("service master moved %s -> %s (takeover by %s)",
                    old, rpc, (info or {}).get("service_id"))
        return True

    def _refresh_service_config(self) -> None:
        """Fetch /rpc/config for the CURRENT target and update the
        stale flag atomically with respect to retargets: the flag is
        cleared only if no retarget landed while the fetch (network
        I/O, outside the lock) was in flight — otherwise the
        retarget's stale=True must survive so the NEW master's config
        is fetched next tick (XLINT13-001 regression shape)."""
        addr = self.service_addr
        ok = self._fetch_service_config()
        with self._addr_mu:
            if self._service_addr == addr:
                self._service_config_stale = bool(addr) and not ok

    def _adopt_advertised_addr(self) -> bool:
        """Re-read ``KEY_MASTER_ADDR`` and retarget if it moved. The
        heartbeat loop calls this after consecutive failures too, closing
        the get-then-watch race (a PUT landing before the watch is live)
        and the watch-compaction gap."""
        try:
            info = self.store.get_json(KEY_MASTER_ADDR)
        except Exception:  # noqa: BLE001 — store hiccup; retried next beat
            return False
        return self._retarget(info)

    def _on_master_addr(self, event) -> None:
        ev_type, _key, value = event
        if ev_type != "PUT" or not value:
            return   # DELETE = master lease expired; keep last known
        try:
            info = json.loads(value)
        except ValueError:
            return
        self._retarget(info)

    def drain_and_stop(self, timeout_s: float = 30.0) -> bool:
        """Graceful shutdown: advertise draining (router stops sending
        work), refuse new requests, let in-flight requests finish, then
        stop. Returns True if everything drained inside ``timeout_s``
        (the reference has no graceful path at all — its handler is
        effectively abort, master.cpp:144-148 / SURVEY.md §7.4)."""
        self._draining = True
        # Push the draining state until the router acknowledges (any
        # successful heartbeat) BEFORE refusing work: 503s issued while
        # the router still routes here would surface to end clients.
        # A standalone worker (no service in front) has no router to
        # convince — skip straight to refusing.
        if self.service_addr:
            for _ in range(3):
                try:
                    if self._send_heartbeat():   # ack == HTTP 200, not
                        break                    # "the POST didn't raise"
                except Exception:  # noqa: BLE001 — push retried above;
                    pass            # the drain proceeds either way
                time.sleep(0.2)
            else:
                # Could not tell the router; give its next poll a beat.
                time.sleep(min(1.0, self.opts.heartbeat_interval_s))
        self._refuse_new = True
        deadline = time.monotonic() + timeout_s
        drained = False
        try:
            while time.monotonic() < deadline:
                # list(): /fork_master can mutate runtimes mid-iteration.
                busy = any(rt.engine is not None and rt.engine.has_work()
                           for rt in list(self.runtimes.values()))
                with self._live_lock:
                    busy = busy or bool(self._live_srid) \
                        or self._inflight_parse > 0 \
                        or self._relay_streams > 0
                if not busy:
                    drained = True
                    break
                time.sleep(0.05)
        finally:
            self.stop()
        return drained

    def stop(self) -> None:
        self._stop.set()
        self._work_event.set()
        _LOCAL_WORKERS.pop(self.name, None)
        if self._devtrace_dir is not None:
            self.stop_device_trace()    # the session is this worker's
        if self._addr_watch is not None:
            try:
                self.store.cancel_watch(self._addr_watch)
            except Exception:  # noqa: BLE001 — shutdown cleanup is
                pass            # best-effort; the store may be gone
            self._addr_watch = None
        # Release consumer threads blocked on live.q.get(), and the
        # handlers parked on a stream the writer owns: the engine
        # loop is about to exit, so no further outputs (or cancel
        # effects) will ever arrive — without the sentinel a client of
        # an abandoned request hangs until process exit instead of
        # getting a terminated stream. A handler already past the
        # refusal check may register AFTER a single snapshot, so refuse
        # first and re-sentinel until the in-parse window empties
        # (bounded; extra sentinels to finished lives are inert).
        self._refuse_new = True
        release_deadline = time.monotonic() + 1.0
        while True:
            with self._live_lock:
                lives = list(self._live_srid.values())
                inflight = self._inflight_parse
            for live in lives:
                self._post_end(live, None)
            if inflight == 0 or time.monotonic() > release_deadline:
                break
            time.sleep(0.02)
        # The writer ends what it still owns and exits; a handler that
        # attaches after this ends its stream itself (_serve_pushed).
        # Joined BEFORE the server goes down, so that the sentinels'
        # frames are with the server's event loop by then.
        self._writer_q.put(None)
        if self._writer_thread.ident is not None:
            self._writer_thread.join(timeout=5)
        self._srv.stop()
        if self._lease_id is not None:
            try:
                self.store.lease_revoke(self._lease_id)
            except Exception:  # noqa: BLE001 — best-effort: the lease
                pass            # TTL expires it anyway
        self._loop_thread.join(timeout=5)
        self._hb_thread.join(timeout=5)
        if self._encode_thread.ident is not None:
            self._encode_thread.join(timeout=5)

    def _register(self) -> None:
        """Write the registration key under a TTL lease
        (engine-side contract, rpc_service/client.cpp:55-77)."""
        ttft_prof: List = []
        tpot_prof: List = []
        if self.opts.enable_profiling:
            from xllm_service_tpu.service.time_predictor import \
                profile_engine
            rt = self.primary_runtime()
            if rt.engine is not None:
                ttft_prof, tpot_prof = profile_engine(rt.engine)
        eng = self.primary_runtime().engine
        meta = InstanceMetaInfo(
            name=self.name,
            rpc_address=self.name,
            instance_type=self.instance_type,
            models=[m for m, rt in self.runtimes.items()
                    if rt.state == MODEL_AWAKE],
            dp_size=self.engine_cfg.dp,
            ttft_profiling_data=ttft_prof,
            tpot_profiling_data=tpot_prof,
            memory_budget_gb=self.opts.memory_budget_gb,
            # one id a slot of the pools' leading axis: a layer that
            # keeps keys and values, a pass (ModelConfig.kv_cache_layers)
            k_cache_ids=list(range(
                self.primary_runtime().model_cfg.kv_cache_layers)),
            v_cache_ids=list(range(
                self.primary_runtime().model_cfg.kv_cache_layers)),
            addrs=[self.name],
            # Block-hash contract + block weight (docs/KV_CACHE.md):
            # the service fails loud when page_size/seed diverge from
            # its (block_size, murmur seed), and prices cross-worker
            # fetches with kv_block_bytes.
            page_size=self.engine_cfg.page_size,
            hash_seed=self.opts.murmur_seed,
            kv_block_bytes=eng.kv_block_bytes() if eng is not None
            else 0,
            # EPD encode-plane advertisement (docs/EPD.md): ENCODE
            # workers (and encode-only mode) serve the vision tower as
            # a first-class stage; the grid is the compiled serve-time
            # image side.
            encode_capable=(self.instance_type == InstanceType.ENCODE
                            or self.opts.encode_only),
            encode_image_size=self._encode_image_size(),
        )
        with self._reg_mu:
            if self._lease_id is not None:
                # Re-registration (role flip): the old lease must die with
                # the old key or every flip leaks a live lease in the store.
                try:
                    self.store.lease_revoke(self._lease_id)
                except Exception:  # noqa: BLE001 — best-effort: the old
                    pass            # lease's TTL expires it anyway
                self._lease_id = None
            self._lease_id = self.store.lease_grant(self.opts.lease_ttl_s)
            self.store.put_json(
                instance_prefix(self.instance_type.value) + self.name,
                stamp(meta.to_json()), self._lease_id)

    def _on_store_heal(self) -> None:
        """Store-guard heal callback: the blackout ended — flag the hb
        loop to re-establish lease + registration idempotently (the
        lease almost certainly expired while the store was unreachable)
        and re-read the master advertisement we may have missed. The
        callback itself only sets the flag: it runs on whichever
        thread's store call healed the guard — possibly inside
        ``_register`` itself — so calling ``_register`` here would
        re-enter worker.reg and deadlock."""
        if self._stop.is_set() or self._dead:
            return
        self._heal_pending.set()

    def primary_runtime(self) -> ModelRuntime:
        return self.runtimes[self.opts.model]

    # ------------------------------------------------------------------
    # Engine loop
    # ------------------------------------------------------------------
    def _engine_loop(self) -> None:
        while not self._stop.is_set():
            busy = False
            for rt in list(self.runtimes.values()):
                eng = rt.engine
                if eng is None:
                    continue
                if eng.fault_hook is None:
                    # (Re)installed lazily: wakeup builds a fresh Engine.
                    eng.fault_hook = self._step_fault_hook
                if not eng.has_work():
                    continue
                busy = True
                t0 = time.monotonic()
                # Entered here and left once the lock is held: what the
                # loop waits behind an admission, apart from the step.
                lock_wait = steptrace.span("xllm.loop.lock_wait")
                lock_wait.__enter__()
                try:
                    with self._engine_lock:
                        lock_wait.__exit__(None, None, None)
                        with steptrace.span(
                                "xllm.loop.step",
                                seq=self.steptrace.next_seq):
                            outs = eng.step()
                except Exception as exc:  # noqa: BLE001 — the step
                    # fault boundary (docs/ROBUSTNESS.md): contain,
                    # attribute, resume — or re-raise through the
                    # breaker into today's visible engine death.
                    step_ms = 1000.0 * (time.monotonic() - t0)
                    if not self._contain_engine_fault(rt, exc, step_ms):
                        self._engine_loop_alive = False
                        self._engine_alive_gauge().set(0, model=rt.model)
                        raise
                    continue
                step_ms = 1000.0 * (time.monotonic() - t0)
                with steptrace.span("xllm.loop.emit", tokens=sum(
                        len(o.new_token_ids) for o in outs)):
                    self._dispatch_outputs(rt, outs, step_ms)
                with steptrace.span("xllm.loop.obs_flush"):
                    self._flush_engine_obs(rt, step_ms)
                    self._engine_alive_gauge().set(1, model=rt.model)
            if not busy:
                with steptrace.span("xllm.loop.idle_wait"):
                    self._work_event.wait(timeout=0.05)
                self._work_event.clear()

    def _engine_alive_gauge(self):
        return self.obs.gauge(
            "xllm_worker_engine_alive",
            "1 while the engine loop serves this model; 0 once the "
            "fault breaker let it die (docs/ROBUSTNESS.md) — the "
            "anomaly watchdog opens engine_dead on the heartbeat copy",
            labelnames=("model",))

    def _step_fault_hook(self, member_rids: Tuple[str, ...]) -> None:
        """Installed as Engine.fault_hook — called (under the engine
        lock) with each step section's batch membership. The injection
        point for the two chaos failpoints."""
        if self.failpoints.fire("worker.fault_step") is not None:
            raise StepFaultInjected("worker.fault_step")
        if self._fault_marked \
                and self._fault_marked.intersection(member_rids) \
                and self.failpoints.fire(
                    "worker.fault_step_req") is not None:
            raise StepFaultInjected("worker.fault_step_req")

    def _contain_engine_fault(self, rt: ModelRuntime,
                              exc: BaseException,
                              step_ms: float) -> bool:
        """The step fault boundary's recovery path. Returns True when
        the fault was contained (loop resumes), False when the
        crash-loop breaker is open (caller re-raises into the
        supervised death path — lease-expiry recovery, as before this
        boundary existed)."""
        eng = rt.engine
        # Satellite fix: the faulted iteration's obs flush used to be
        # lost entirely (the exception skipped _flush_engine_obs) —
        # flush it with its own phase label before anything else.
        self._flush_engine_obs(rt, step_ms, phase="fault")
        faults = self.obs.counter(
            "xllm_engine_faults_total",
            "engine step faults seen by the fault boundary, by "
            "containment outcome (docs/ROBUSTNESS.md)",
            labelnames=("model", "outcome"))
        now = time.monotonic()
        self._fault_times.append(now)
        while self._fault_times and \
                now - self._fault_times[0] > self._fault_window_s:
            self._fault_times.popleft()
        if len(self._fault_times) > self._fault_limit:
            faults.inc(model=rt.model, outcome="uncontained")
            logger.error(
                "engine fault breaker open (%d faults in %.0fs window) "
                "— falling back to engine death: %s",
                len(self._fault_times), self._fault_window_s, exc)
            return False
        kind = _classify_step_fault(exc)
        probe_outs: List[Tuple[List[Any], float]] = []
        with self._engine_lock:
            live_ids = set(eng.live_request_ids())
            suspects = [r for r in eng.step_members if r in live_ids] \
                or sorted(live_ids)
            # Committed outputs of the iteration's COMPLETED sections
            # (e.g. the decode that ran before a faulting prefill):
            # their tokens are already on the sequences, so dropping
            # the StepOutputs would silently lose stream tokens.
            salvaged = list(eng.last_step_partial_outs)
            if kind == "transient":
                blamed: List[str] = []
                eng.fault_reset(())
            else:
                blamed, probe_outs = self._bisect_step_fault(
                    eng, suspects)
                eng.fault_reset(blamed)
            self._fault_marked.difference_update(blamed)
        outcome = ("transient_retry" if kind == "transient" else
                   "culprit" if len(blamed) == 1 else
                   "whole_batch" if blamed else
                   # Deterministic fault that no probe could reproduce:
                   # nobody blamed, retry in place like a transient.
                   "transient_retry")
        verdict = (f"{outcome} [{type(exc).__name__}: {exc}] "
                   f"on {self.name}")
        logger.warning("engine step fault contained (%s): blamed %s",
                       outcome, blamed or "nobody")
        blamed_set = set(blamed)
        salvaged = [o for o in salvaged
                    if o.request_id not in blamed_set]
        if salvaged:
            self._dispatch_outputs(rt, salvaged, step_ms)
        for outs, ms in probe_outs:
            kept = [o for o in outs if o.request_id not in blamed_set]
            if kept:
                self._dispatch_outputs(rt, kept, ms)
        faults.inc(model=rt.model, outcome=outcome)
        if blamed:
            self._fail_lives_engine_fault(blamed, verdict)
        self._work_event.set()
        return True

    def _bisect_step_fault(self, eng, suspects: List[str]
                           ) -> Tuple[List[str],
                                      List[Tuple[List[Any], float]]]:
        """Blame attribution: retry halves of the faulting batch in
        isolation under the XLLM_FAULT_BISECT_BUDGET probe-step budget.
        A faulting half narrows the suspect set; a clean half is
        exonerated (its probe outputs are returned for dispatch — the
        probe made real progress). On budget exhaustion the whole
        remaining suspect set is blamed. Runs under the engine lock."""
        probe_outs: List[Tuple[List[Any], float]] = []
        budget = self._fault_bisect_budget
        if len(suspects) <= 1 or budget <= 0:
            return list(suspects), probe_outs
        eng.fault_reset(())      # known-good point before probing
        while len(suspects) > 1 and budget > 0:
            half = suspects[:max(1, len(suspects) // 2)]
            budget -= 1
            t0 = time.monotonic()
            outs: List[Any] = []
            faulted = False
            try:
                eng.isolate(half)
                outs = eng.step()
            except Exception:  # noqa: BLE001 — the probe reproduced
                faulted = True  # the fault: suspects narrow to this half
            finally:
                eng.release_isolation()
            if faulted:
                eng.fault_reset(())
                suspects = list(half)
            else:
                probe_outs.append(
                    (outs, 1000.0 * (time.monotonic() - t0)))
                suspects = [r for r in suspects if r not in half]
        return list(suspects), probe_outs

    def _fail_lives_engine_fault(self, rids: List[str],
                                 verdict: str) -> None:
        """Surface blamed-and-evicted requests to their consumers as
        the typed engine_fault failure (not a generic stream break):
        relay consumers get the _EngineFault sentinel, RPC fan-in gets
        a finished RequestOutput with an INTERNAL engine_fault status
        carrying the blame verdict."""
        to_service: List[RequestOutput] = []
        for rid in rids:
            with self._live_lock:
                live = self._live.get(rid)
            if live is None:
                continue
            self.spans.record(live.service_request_id, "faulted",
                              plane="worker")
            if live.stream_to_service:
                to_service.append(RequestOutput(
                    request_id=rid,
                    service_request_id=live.service_request_id,
                    status=Status(StatusCode.INTERNAL,
                                  f"engine_fault: {verdict}"),
                    finished=True))
            else:
                self._post_end(live, _EngineFault(verdict))
            # Cancels sibling choices still in the engine and clears
            # the live maps; the blamed rid itself is already evicted
            # (a cancel on it is benign).
            self._finalize_live(live)
        if to_service and self.service_addr:
            self._push_outputs_to_service(to_service)

    def _flush_engine_obs(self, rt: ModelRuntime, step_ms: float,
                          phase: Optional[str] = None) -> None:
        """Per-iteration flush of step-level engine stats into the
        registry: queue depths / KV utilization / preemptions (via
        ``_engine_load``, the single load_metrics assembly point), batch
        token occupancy split prefill vs decode, per-step wall time, and
        the phase/recompile ledger. Runs on the engine-loop thread right
        after ``step()`` — ``last_step_*`` are only written there.
        ``phase`` overrides the step-kind label: the fault boundary
        flushes the faulted iteration with ``phase="fault"`` (the flush
        used to be lost entirely when an exception skipped it)."""
        eng = rt.engine
        if eng is None:
            return
        lm = self._engine_load(rt)
        kind = phase or eng.last_step_kind
        if kind == "idle":
            return
        m = rt.model
        if self.steptrace.enabled:
            self._record_step(rt, lm, kind, step_ms)
        pf = eng.last_step_prefill_tokens
        dc = eng.last_step_decode_tokens
        self.obs.counter(
            "xllm_worker_steps_total",
            "engine iterations by phase "
            "(mixed = interleaved decode+prefill)",
            labelnames=("model", "phase")).inc(1, model=m, phase=kind)
        # Beside the step counts: 1 - uploads / (decode + mixed steps) is
        # the share of single-step decodes that were handed the block the
        # last step program left on the device.
        self.obs.counter(
            "xllm_worker_decode_block_uploads_total",
            "single-step decode iterations that uploaded their slot "
            "block (the others passed the one the last step handed back)",
            labelnames=("model",)).set_total(
            eng.phase_counts.get("decode.upload", 0), model=m)
        # (hit + tail_hit) / (decode + mixed steps) is the share of
        # single-step decodes that were already on the device when their
        # iteration began.
        ahead = self.obs.counter(
            "xllm_worker_decode_ahead_total",
            "single decode steps launched before the step before them "
            "was read (launched), taken by the next iteration in place "
            "of a pack and a dispatch (hit), or thrown away whole "
            "(discarded); tail_*: the same of steps dispatched from "
            "host truth at the tail of the iteration before theirs",
            labelnames=("model", "result"))
        for result, phase in (("launched", "decode.ahead_dispatch"),
                              ("hit", "decode.ahead_hit"),
                              ("discarded", "decode.ahead_discard"),
                              ("tail_launched", "decode.tail_dispatch"),
                              ("tail_hit", "decode.tail_hit"),
                              ("tail_discarded", "decode.tail_discard")):
            ahead.set_total(eng.phase_counts.get(phase, 0), model=m,
                            result=result)
        self.obs.counter(
            "xllm_worker_decode_ahead_dropped_rows_total",
            "rows of taken launched-ahead steps whose result was dropped "
            "(the request finished, was cancelled or preempted at the "
            "step before)",
            labelnames=("model",)).set_total(
            eng.phase_counts.get("decode.ahead_dropped_rows", 0), model=m)
        if eng.cfg.is_moe:
            self._flush_moe(rt)
        self.obs.gauge(
            "xllm_worker_kv_pool_bytes",
            "bytes of the pools of keys and values (a latent model's one "
            "pool of rows): 0 for a model no layer of which attends, "
            "whose pages are bookkeeping and whose memory is "
            "xllm_worker_state_pool_bytes",
            labelnames=("model",)).set(
            sum(int(x.nbytes) for x in eng.kv[:2]), model=m)
        if eng.keeps_state:
            self._flush_state(rt)
        if eng.window_model:
            self._flush_window(rt)
        if eng.cfg.looped:
            self._flush_loop(rt)
        tok = self.obs.counter(
            "xllm_worker_step_tokens_total",
            "batch token occupancy: prompt tokens computed (prefill) / "
            "tokens sampled (decode); mixed iterations split per phase",
            labelnames=("model", "phase"))
        if pf:
            tok.inc(pf, model=m, phase="prefill")
        if dc:
            tok.inc(dc, model=m, phase="decode")
        self.obs.histogram(
            "xllm_worker_step_ms", "wall time of one engine step",
            labelnames=("model", "phase")).observe(
            step_ms, model=m, phase=kind)
        if pf:
            # Measured prefill tok/s for the heartbeat's cost-model
            # signal (LatencyMetrics.prefill_tok_s). The engine times
            # the prefill section itself so mixed iterations don't
            # charge decode time to the prefill rate.
            self._prefill_tok_cum += pf
            self._prefill_s_cum += eng.last_step_prefill_s
        if pf or dc:
            # Prefill-token share of the iteration: 1.0 = prompt-only,
            # 0.0 = decode-only; in between is the interleaver at work.
            self.obs.gauge(
                "xllm_worker_interleave_mix",
                "prefill-token share of the last engine iteration",
                labelnames=("model",)).set(pf / (pf + dc), model=m)
        if eng.queue_waits_ms:
            h = self.obs.histogram(
                "xllm_worker_queue_wait_ms",
                "arrival at the engine to the first slot in a step: "
                "the worker's queue wait, measured where it ends",
                labelnames=("model",))
            for w in eng.queue_waits_ms:
                h.observe(w, model=m)
            eng.queue_waits_ms.clear()
        if eng.last_step_prefill_windows:
            h = self.obs.histogram(
                "xllm_worker_prefill_quantum_tokens",
                "scheduled prefill window sizes (the staggered-admission "
                "quantum shrinks under decode load)",
                labelnames=("model",),
                buckets=_PREFILL_QUANTUM_BUCKETS)
            for w in eng.last_step_prefill_windows:
                h.observe(w, model=m)
        # One-dispatch mixed iterations (XLLM_RAGGED_ATTN). Materialized
        # at 0 so a scrape can tell "ragged off / never fired" from
        # "not exported"; the ragged.pack/dispatch/post phase wall time
        # rides the phase ledger below like every other engine phase.
        self.obs.counter(
            "xllm_worker_ragged_dispatches_total",
            "mixed prefill+decode iterations served by the single "
            "ragged attention program (XLLM_RAGGED_ATTN)",
            labelnames=("model",)).set_total(
            eng.phase_counts.get("ragged.dispatch", 0), model=m)
        self._flush_phase_ledger(rt)
        self._flush_prefix_cache(rt)

    def _record_step(self, rt: ModelRuntime, lm: LoadMetrics,
                     kind: str, step_ms: float) -> None:
        """Append one flight-recorder record for the iteration that just
        ran (engine-loop thread; call-site gated on
        ``steptrace.enabled`` so the OFF path builds nothing). Per-step
        phase/speculation/prefix/page deltas come from snapshot-diffing
        the engine's cumulative ledgers."""
        eng = rt.engine
        m = rt.model
        # Phase-ms delta against the previous iteration's snapshot —
        # includes the <phase>.device_wait / .host_copy splits.
        snap = self._st_phase_snap.get(m, {})
        cur = {k: v for k, v in eng.phase_times.items()}
        phases = {}
        for k, v in cur.items():
            d = (v - snap.get(k, 0.0)) * 1e3
            if d > 0.0005:
                phases[k] = round(d, 3)
        self._st_phase_snap[m] = cur
        om = eng.overlap_metrics()
        sspec = self._st_spec_snap.get(m, {})
        spec = {k: int(om[k] - sspec.get(k, 0))
                for k in ("ahead_dispatches", "ahead_hits",
                          "ahead_discards")}
        self._st_spec_snap[m] = {k: int(om[k]) for k in spec}
        hit_cum = int(eng.prefix_cache_stats()["hit_tokens_total"])
        hit_delta = hit_cum - self._st_prefix_snap.get(m, 0)
        self._st_prefix_snap[m] = hit_cum
        free = int(eng.allocator.num_free)
        pages_delta = free - self._st_free_pages.get(m, free)
        self._st_free_pages[m] = free
        passes, exit_cdf = (_loop_record(eng.last_step_loop)
                            if eng.cfg.looped else (None, None))
        state = None
        if eng.state_model:
            cur = eng.state_stats()
            was = self._st_state_snap.get(m, {})
            state = {"live": cur["live"], "snapshots": cur["snapshots"],
                     **{k: cur[k] - was.get(k, 0)
                        for k in ("restored", "snapshotted", "evicted")}}
            self._st_state_snap[m] = cur
        self.steptrace.record(
            model=m, kind=kind, step_ms=round(step_ms, 3),
            prefill_tokens=eng.last_step_prefill_tokens,
            decode_tokens=eng.last_step_decode_tokens,
            prefill_windows=eng.last_step_prefill_windows,
            ragged=eng.last_step_ragged,
            attn_dispatches=eng.last_step_attn_dispatches,
            members=eng.step_members,
            phases=phases, spec=spec,
            kv_usage=round(float(lm.kv_cache_usage), 4),
            pages_delta=pages_delta,
            cache_hit_tokens=hit_delta,
            compiled=tuple(eng.last_step_compiled),
            moe=_moe_record(eng.last_step_moe),
            passes=passes, exit_cdf=exit_cdf,
            state_restored=(tuple(eng.last_step_state_restored)
                            if eng.keeps_state else None),
            state=state)

    def _flush_moe(self, rt: ModelRuntime) -> None:
        """What the sparse layers counted on the device (``Engine.
        moe_stats``: the dropless layer's, whichever family runs it; all
        zeros where a family's layer still buckets and counts its drops
        alone)."""
        st, m = rt.engine.moe_stats, rt.model
        for name, key, text in (
                ("xllm_worker_moe_assignments_total", "assignments",
                 "(valid row, expert) assignments the sparse layers "
                 "computed, summed over layers and steps"),
                ("xllm_worker_moe_experts_touched_total", "experts_touched",
                 "experts that received at least one row, summed over "
                 "sparse layers and steps: over layers x experts it is "
                 "the share of expert weights a step reads"),
                ("xllm_worker_moe_dropped_assignments_total", "dropped",
                 "assignments the gate made and no expert computed "
                 "(requested - computed; 0 under the dropless layer)"),
                ("xllm_worker_moe_elsewhere_assignments_total", "elsewhere",
                 "assignments the gate made to experts that another chip "
                 "of the deployment holds (a held share of a wider "
                 "router: computed by nobody here; 0 where every expert "
                 "is held)")):
            self.obs.counter(name, text, labelnames=("model",)).set_total(
                st[key], model=m)

    def _flush_state(self, rt: ModelRuntime) -> None:
        """The ledger of a model whose cached state is more than its
        pages (``Engine.state_stats``): the convolution tails', and the
        slots' of a state that lives by slot."""
        state, m = rt.engine.state_stats(), rt.model
        if state is not None:
            c = self.obs.counter(
                "xllm_worker_state_rows_total",
                "convolution tails by event: restored = admissions whose "
                "first computed position read a cached page's tails (a "
                "prefix hit); written = pages whose row a prefill window "
                "wrote (one row a convolution layer each)",
                labelnames=("model", "event"))
            c.set_total(state["restored"], model=m, event="restored")
            c.set_total(state["written"], model=m, event="written")
            self.obs.gauge(
                "xllm_worker_state_pool_bytes",
                "bytes of the pool of convolution tails (one row a page "
                "a convolution layer) and, where the model keeps one, of "
                "the pool of matrix states by slot",
                labelnames=("model",)).set(state["pool_bytes"], model=m)
            if "live" in state:
                # a state that lives by slot (a mixer beside attention):
                # restored counts the admissions that began from a copy
                # of a snapshot
                c.set_total(state["snapshotted"], model=m,
                            event="snapshotted")
                c.set_total(state["evicted"], model=m, event="evicted")
                g = self.obs.gauge(
                    "xllm_worker_state_slots",
                    "slots of the pool of matrix states by kind: live = "
                    "rows that hold a state now (two slots each), "
                    "snapshot = states at page boundaries the prefix "
                    "index can resume from, free = snapshot slots unused",
                    labelnames=("model", "kind"))
                g.set(state["live"], model=m, kind="live")
                g.set(state["snapshots"], model=m, kind="snapshot")
                g.set(state["free"], model=m, kind="free")

    def _flush_window(self, rt: ModelRuntime) -> None:
        """The ledger of the window layers' pool and of the tails the
        prefix index keeps there (``Engine.window_stats``)."""
        w, m = rt.engine.window_stats(), rt.model
        g = self.obs.gauge(
            "xllm_worker_kv_window_pages",
            "pages of the window layers' pool (the second pair of pools "
            "of a model with sliding-window layers beside full ones) by "
            "kind: size = pages a row or a tail can hold, live = held "
            "now, peak = the most held at once",
            labelnames=("model", "kind"))
        g.set(w["pages"], model=m, kind="size")
        g.set(w["live"], model=m, kind="live")
        g.set(w["peak"], model=m, kind="peak")
        self.obs.gauge(
            "xllm_worker_kv_window_pool_bytes",
            "bytes of the window layers' pair of pools",
            labelnames=("model",)).set(w["pool_bytes"], model=m)
        self.obs.counter(
            "xllm_worker_kv_window_trimmed_pages_total",
            "window pages rows let go of behind their window as they "
            "advanced (prefill windows and decode steps alike)",
            labelnames=("model",)).set_total(w["trimmed"], model=m)
        self.obs.gauge(
            "xllm_worker_kv_window_tails",
            "tails held: cached prefixes whose last window of pages the "
            "prefix index keeps, so that a request can resume there",
            labelnames=("model",)).set(w["tails"], model=m)
        c = self.obs.counter(
            "xllm_worker_kv_window_tail_events_total",
            "tails by event: taken = attached to a finished prefill's "
            "deepest page boundary, hit = a request resumed at one, miss "
            "= the deepest matched boundary had no live tail (the row "
            "recomputed from a shallower one or from nothing), evicted = "
            "dropped for room or with its page",
            labelnames=("model", "event"))
        c.set_total(w["taken"], model=m, event="taken")
        c.set_total(w["hits"], model=m, event="hit")
        c.set_total(w["misses"], model=m, event="miss")
        c.set_total(w["evicted"], model=m, event="evicted")

    def _flush_loop(self, rt: ModelRuntime) -> None:
        """What a looped model's step programs counted on the device
        (``Engine.loop_stats``): the layer passes their own loop ran, and
        the decode rows' cumulative exit probabilities."""
        st, m = rt.engine.loop_stats, rt.model
        c = self.obs.counter(
            "xllm_worker_layer_passes_total",
            "passes of the whole layer stack the step programs ran, "
            "counted by the program's own loop: over the steps of a "
            "phase it is the model's total_ut_steps unless a change "
            "leaves work out",
            labelnames=("model", "phase"))
        for phase, n in st["passes"].items():
            c.set_total(n, model=m, phase=phase)
        c = self.obs.counter(
            "xllm_worker_exit_cdf_sum",
            "sum over decode rows of the cumulative exit probability "
            "after layer pass `pass` (every pass but the last, whose is "
            "1); over xllm_worker_exit_cdf_count it is the mean",
            labelnames=("model", "pass"))
        for i, x in enumerate(st["cdf_sum"]):
            c.set_total(x, model=m, **{"pass": str(i + 1)})
        self.obs.counter(
            "xllm_worker_exit_cdf_count",
            "decode rows the exit gate was read for",
            labelnames=("model",)).set_total(st["rows"], model=m)

    def _flush_prefix_cache(self, rt: ModelRuntime) -> None:
        """Prefix-reuse health (docs/KV_CACHE.md): lookup/hit-token
        totals, spill-tier traffic and cross-worker fetched blocks —
        the series the cluster-scale prefix-reuse loop is judged by."""
        eng = rt.engine
        if eng is None:
            return
        m = rt.model
        stats = eng.prefix_cache_stats()
        c = self.obs.counter(
            "xllm_worker_prefix_cache_hit_tokens_total",
            "prompt tokens served from the prefix cache (local hits, "
            "tier restores and cross-worker fetches alike)",
            labelnames=("model",))
        c.set_total(stats["hit_tokens_total"], model=m)
        self.obs.counter(
            "xllm_worker_prefix_cache_lookups_total",
            "admits that consulted the prefix cache",
            labelnames=("model",)).set_total(
            stats["lookups_total"], model=m)
        self.obs.counter(
            "xllm_worker_prefix_cache_spilled_pages",
            "HBM prefix pages parked in the host-DRAM tier instead of "
            "dropped (XLLM_KV_SPILL_MB)",
            labelnames=("model",)).set_total(
            stats["spilled_pages"], model=m)
        self.obs.counter(
            "xllm_worker_prefix_cache_restored_pages",
            "spilled pages restored to HBM on a later prefix hit",
            labelnames=("model",)).set_total(
            stats["restored_pages"], model=m)
        self.obs.counter(
            "xllm_worker_prefix_cache_fetched_blocks_total",
            "KV blocks adopted from a remote holder (cross-worker "
            "cached-block fetch)",
            labelnames=("model",)).set_total(
            stats["fetched_blocks_total"], model=m)
        self.obs.counter(
            "xllm_worker_prefix_cache_hashed_tokens_total",
            "tokens fed to the block hash by the prefix index (lookup, "
            "restore and registration alike); over step_tokens_total it "
            "stays near 1 while a page is hashed once",
            labelnames=("model",)).set_total(
            stats["hashed_tokens_total"], model=m)
        self.obs.counter(
            "xllm_worker_prefix_cache_walked_pages_total",
            "pages the prefix index's registration set out to look at (a "
            "row's full pages past its settled lead); over the sampled "
            "tokens it stays near the pages that fill (1 / page_size) "
            "plus an admission's own, whatever the rows' contexts",
            labelnames=("model",)).set_total(
            stats["walked_pages_total"], model=m)

    def _flush_phase_cpu(self, rt: ModelRuntime) -> None:
        """The phase ledger's CPU column, mirrored at scrape time alone:
        nothing reads it between two scrapes, and a dozen series more in
        every iteration's flush is engine-thread time."""
        c_cpu = self.obs.counter(
            "xllm_worker_phase_cpu_seconds_total",
            "the engine thread's own CPU time per engine phase "
            "(thread_time): over phase_seconds_total, the share of a "
            "phase's wall time that was its own work and not another "
            "thread's hold of the interpreter or a wait",
            labelnames=("model", "phase"))
        for name, cpu in list(rt.engine.phase_cpu.items()):
            c_cpu.set_total(cpu, model=rt.model, phase=name)

    def _flush_phase_ledger(self, rt: ModelRuntime) -> None:
        """Mirror the engine's phase wall-time ledger + post-warmup
        recompile counters into the registry (same series /metrics
        always exported; now they update every iteration too)."""
        eng = rt.engine
        if eng is None:
            return
        m = rt.model
        c_secs = self.obs.counter(
            "xllm_worker_phase_seconds_total",
            "host-side wall time per engine phase",
            labelnames=("model", "phase"))
        c_calls = self.obs.counter(
            "xllm_worker_phase_calls_total",
            labelnames=("model", "phase"))
        c_rec = self.obs.counter(
            "xllm_worker_recompiles_total",
            "post-warmup compiles per program (0 is the contract)",
            labelnames=("model", "program"))
        for name, entry in eng.phase_report().items():
            if isinstance(entry, dict):
                c_secs.set_total(entry["total_ms"] / 1e3,
                                 model=m, phase=name)
                c_calls.set_total(entry["calls"], model=m, phase=name)
            else:   # "<prog>.recompile" counters
                c_rec.set_total(entry, model=m,
                                program=name.rsplit(".", 1)[0])
        c_compiles = self.obs.counter(
            "xllm_worker_jit_compiles_total",
            "compiled variants per jit program, warmup included "
            "(steady growth = unbucketed shape / leaking static)",
            labelnames=("model", "program"))
        for name, total in eng.compile_report().items():
            c_compiles.set_total(total, model=m, program=name)

    def _dispatch_outputs(self, rt: ModelRuntime,
                          outs: List[StepOutput], step_ms: float) -> None:
        now = time.monotonic()
        with self._engine_lock:
            to_service: List[RequestOutput] = self._service_push_buffer
            self._service_push_buffer = []
        # What the stream writer gets of this iteration, as ONE item of
        # its queue: the outputs that carry a request's first token, then
        # the others, each list in emit order (a request's own stay in
        # order: its first output is the first of its outputs here).
        firsts: List[Tuple[_LiveRequest, StepOutput]] = []
        rest: List[Tuple[_LiveRequest, StepOutput]] = []
        for out in outs:
            if not self._dead and self.failpoints.fire(
                    "worker.die_after_n_tokens",
                    n=len(out.new_token_ids)) is not None:
                self._hand_to_writer(firsts, rest)   # ahead of the _ABORTs
                firsts, rest = [], []
                self._die()
            if self._dead:
                # Simulated death: outputs past the trip point — and
                # anything buffered for the fan-in — are lost, exactly
                # like a crashed process's socket buffers.
                return
            with self._live_lock:
                live = self._live.get(out.request_id)
            if live is None:
                continue
            batch = rest
            if live.first_out_time == 0.0:
                batch = firsts
                # Its own clock read: ``emit`` up to THIS request is the
                # end of its ``post_emit`` stage.
                t_first = live.first_out_time = time.monotonic()
                stamps = live.stamps
                if stamps is not None:
                    stamps.update(out.first_token_stamps or ())
                    live.stamp("first_token", t_first)
                self._latency.recent_max_ttft_ms = max(
                    self._latency.recent_max_ttft_ms, step_ms)
                self.spans.record(live.service_request_id, "first_token",
                                  plane="worker", t_mono=t_first)
                # Per-request prefix-reuse evidence on the span (rides
                # the heartbeat to /admin/trace/<id>): prompt tokens
                # whose KV was already resident when prefill started.
                self.spans.annotate(live.service_request_id,
                                    cache_hit_tokens=out.num_cached_tokens)
            else:
                self._latency.recent_max_tbt_ms = max(
                    self._latency.recent_max_tbt_ms, step_ms)
            if out.finished:
                # Engine-level finish (length/eos/cancel). The span goes
                # onto the heartbeat export queue here; consumer-side
                # finishes (stop strings) surface as the CANCELLED out
                # the engine emits after the consumer cancels.
                self.spans.record(live.service_request_id, "finished",
                                  plane="worker", t_mono=now)
            if live.stream_to_service:
                to_service.extend(self._process_step_output(live, out))
                if out.finished or live.choices[
                        live.choice_index(out.request_id)].finished:
                    self._drop_live(out.request_id)
                if live.all_finished:
                    # A flush may have finished choices whose engine rids
                    # were already dropped — complete the srid cleanup.
                    with self._live_lock:
                        self._live_srid.pop(live.service_request_id, None)
            else:
                # this call's one clock read: where the token's way to
                # the wire starts (xllm_worker_token_out_seconds_total)
                out.emit_t = now
                if live.push is not None:
                    batch.append((live, out))
                else:
                    live.q.put(out)
                if out.finished:
                    self._drop_live(out.request_id)
        self._hand_to_writer(firsts, rest)
        if to_service and self.service_addr:
            self._push_outputs_to_service(to_service)

    def _hand_to_writer(self, firsts: List[Tuple[Any, Any]],
                        rest: List[Tuple[Any, Any]]) -> None:
        """One ``put`` an iteration: it wakes one thread, whatever the
        number of streams (engine loop's thread)."""
        if firsts or rest:
            self._writer_batches += 1
            self._writer_batch_outs += len(firsts) + len(rest)
            self._writer_q.put(firsts + rest)

    def _post_end(self, live: _LiveRequest, end: Any) -> None:
        """Post one of a request's ends (None, ``_ABORT``, an
        ``_EngineFault``) to whoever consumes its outputs, behind every
        output posted before it."""
        if live.push is not None:
            self._writer_q.put(((live, end),))
        else:
            live.q.put(end)

    def _drop_live(self, request_id: str) -> None:
        with self._live_lock:
            live = self._live.pop(request_id, None)
            if live is not None and live.all_finished:
                self._live_srid.pop(live.service_request_id, None)

    def _finalize_live(self, live: _LiveRequest) -> None:
        """Consumer-side cleanup when a response completes or its client
        goes away. The engine thread's _drop_live alone leaked the srid
        entry in relay mode: it runs when the finish StepOutput is
        QUEUED, before the consumer marks the choice finished, so
        all_finished was still false there. Unfinished engine work whose
        consumer is gone (client disconnect mid-stream) is cancelled —
        otherwise the engine generates into dropped outputs for the rest
        of max_tokens and a drain waits on it."""
        with self._live_lock:
            self._live_srid.pop(live.service_request_id, None)
            for erid in live.engine_rids:
                if self._live.get(erid) is live:
                    self._live.pop(erid, None)
        unfinished = [erid for erid, ch
                      in zip(live.engine_rids, live.choices)
                      if not ch.finished]
        if unfinished:
            rt = self.runtimes.get(live.model) or self.primary_runtime()
            if rt.engine is not None:
                with self._engine_lock:
                    for erid in unfinished:
                        rt.engine.cancel(erid)
                self._work_event.set()
        if self._fault_marked:          # unguarded peek is benign: a
            with self._engine_lock:     # stale mark only re-marks
                self._fault_marked.difference_update(live.engine_rids)

    def _process_step_output(self, live: _LiveRequest,
                             out: StepOutput) -> List[RequestOutput]:
        """Convert one engine StepOutput into wire RequestOutputs.

        Usually 0 or 1 outputs; more when this step's output unblocks
        other choices: under echo+logprobs the prompt scoring rides
        candidate 0's first output, and every other candidate's deltas
        are held back until it lands (their logprob arrays must lead
        with the prompt tokens). The arrival of the scores flushes ALL
        held choices here — a held choice may never produce another
        delta of its own (it can already be finished)."""
        need_plp = (live.sampling.echo and live.sampling.logprobs
                    and not live.is_chat)
        arrived = out.prompt_logprobs is not None and live.prompt_lps is None
        # Candidate 0 finishing WITHOUT scores (cancelled before its
        # prefill scored the prompt) means scores will never arrive —
        # release every held choice with empty scores instead of hanging
        # the request forever.
        source_died = (need_plp and live.prompt_lps is None
                       and out.prompt_logprobs is None
                       and live.choice_index(out.request_id) == 0
                       and out.finish_reason != FinishReason.NONE)
        if arrived or source_died:
            live.prompt_lps = out.prompt_logprobs if arrived else []
            ros: List[RequestOutput] = []
            ro = self._to_request_output(live, out)
            if ro is not None:
                ros.append(ro)
            for other in live.choices:
                if other.pending:
                    pend, other.pending = other.pending, []
                    ro = self._to_request_output(
                        live, _merge_step_outputs(pend))
                    if ro is not None:
                        ros.append(ro)
            return ros
        ch = live.choices[live.choice_index(out.request_id)]
        if need_plp and not ch.echo_done and not ch.finished \
                and live.prompt_lps is None:
            ch.pending.append(out)
            return []
        if ch.pending:
            pend, ch.pending = ch.pending, []
            out = _merge_step_outputs(pend + [out])
        ro = self._to_request_output(live, out)
        return [ro] if ro is not None else []

    def _to_request_output(self, live: _LiveRequest,
                           out: StepOutput) -> Optional[RequestOutput]:
        """Convert one engine StepOutput into the wire RequestOutput.

        Handles the per-choice streaming state: incremental detokenize,
        OpenAI stop-string matching (with holdback; the engine request is
        cancelled once a stop fires), chosen-token + top-k logprobs, and
        all-choices-finished aggregation for n>1. Returns None when the
        output is for a choice that already stopped (nothing to emit)."""
        idx = live.choice_index(out.request_id)
        ch = live.choices[idx]
        if ch.finished:
            return None
        finish = out.finish_reason
        text = ch.decoder.feed(out.new_token_ids)
        if finish != FinishReason.NONE:
            text += ch.decoder.flush()
        if ch.stopper.stops:
            text = ch.stopper.feed(text)
            if ch.stopper.stopped:
                finish = FinishReason.STOP
                self._cancel_engine_request(live, out.request_id)
            elif finish != FinishReason.NONE:
                text += ch.stopper.flush()
        ch.completion_tokens += len(out.new_token_ids)
        ch.cum_logprob += sum(out.logprobs)
        echo_lps: List[LogProb] = []
        if live.sampling.echo and not ch.echo_done:
            # Completion-API echo: the first delta of each choice leads
            # with the prompt — its text, and (echo+logprobs) per-prompt-
            # token scores from the engine (first token null). Text and
            # LogProb entries are identical across choices: cached.
            ch.echo_done = True
            prefix_text, echo_lps = live.echo_prefix()
            text = prefix_text + text
        logprobs = list(echo_lps)
        if live.sampling.logprobs:
            for j, tid in enumerate(out.new_token_ids):
                top = []
                if out.top_logprobs and live.sampling.top_logprobs > 0:
                    top = [{"token": live.tokenizer.decode([e["token_id"]]),
                            "token_id": e["token_id"],
                            "logprob": e["logprob"]}
                           for e in out.top_logprobs[j]
                           [:live.sampling.top_logprobs]]
                logprobs.append(LogProb(
                    token=live.tokenizer.decode([tid]), token_id=tid,
                    logprob=out.logprobs[j] if j < len(out.logprobs)
                    else 0.0,
                    top_logprobs=top))
        if finish != FinishReason.NONE:
            ch.finished = True
        seq = SequenceOutput(
            index=idx, text=text, token_ids=list(out.new_token_ids),
            finish_reason=finish, logprobs=logprobs,
            # best_of ranking key, attached on the finish delta only.
            # Cancelled / zero-token candidates get None (ranked last by
            # the collector) — 0.0 would outrank every real candidate's
            # negative mean.
            mean_logprob=(ch.cum_logprob / ch.completion_tokens
                          if finish not in (FinishReason.NONE,
                                            FinishReason.CANCELLED)
                          and ch.completion_tokens > 0 else None))
        all_done = live.all_finished
        usage = None
        if all_done:
            usage = Usage(
                prompt_tokens=live.prompt_tokens or out.num_prompt_tokens,
                completion_tokens=sum(c.completion_tokens
                                      for c in live.choices))
        return RequestOutput(
            request_id=live.req.request_id,
            service_request_id=live.service_request_id,
            outputs=[seq], usage=usage, finished=all_done,
            cancelled=finish == FinishReason.CANCELLED)

    def _cancel_engine_request(self, live: _LiveRequest,
                               engine_rid: str) -> None:
        """Stop-string hit: the engine must stop generating this choice."""
        rt = self.runtimes.get(live.model) or self.primary_runtime()
        if rt.engine is not None:
            with self._engine_lock:
                rt.engine.cancel(engine_rid)
            self._work_event.set()

    # ------------------------------------------------------------------
    # Fault injection (obs/failpoints.py; docs/ROBUSTNESS.md)
    # ------------------------------------------------------------------
    def _die(self) -> None:
        """``worker.die_after_n_tokens`` tripped: make this worker LOOK
        dead without killing the (possibly shared) test process —
        refuse new work, stop liveness (store keepalive + master
        beats stop via the drop_heartbeats arming, so the lease expires
        like a crash), break every in-flight stream mid-frame (_ABORT),
        and stop pushing fan-in outputs."""
        if self._dead:
            return
        self._dead = True
        self._refuse_new = True
        logger.warning("failpoint worker.die_after_n_tokens tripped: "
                       "%s simulating death", self.name)
        self.failpoints.arm("worker.drop_heartbeats", mode="always")
        with self._live_lock:
            lives = list(self._live_srid.values())
        for live in lives:
            rt = self.runtimes.get(live.model) or self.primary_runtime()
            if rt.engine is not None:
                with self._engine_lock:
                    for erid in live.engine_rids:
                        rt.engine.cancel(erid)
            self._post_end(live, _ABORT)
        self._work_event.set()

    def _serve_failpoint(self, req: Request) -> Response:
        """Arm/disarm one failpoint (or a whole XLLM_FAILPOINTS-grammar
        spec) at runtime. Closed catalog: unknown names are a 400."""
        try:
            body = req.json()
        except Exception:  # noqa: BLE001 — the 400 carries the
            # verdict straight back to the caller
            return Response.error(400, "invalid JSON body")
        try:
            self.failpoints.arm_from_body(body)
        except (TypeError, ValueError) as e:
            return Response.error(400, str(e))
        return Response.json({"ok": True,
                              "state": self.failpoints.state()})

    def _serve_failpoints(self, req: Request) -> Response:
        return Response.json(self.failpoints.state())

    def _device_report(self) -> List[Dict[str, Any]]:
        """Identity and allocator statistics of each device this
        worker's engines live on, as the runtime reports them
        (``memory_stats()`` is None on backends that keep none)."""
        out = []
        for d in self._devices:
            ms = d.memory_stats() or {}
            out.append({
                "id": d.id, "process_index": d.process_index,
                "coords": list(getattr(d, "coords", ()) or ()),
                "bytes_in_use": ms.get("bytes_in_use"),
                "peak_bytes_in_use": ms.get("peak_bytes_in_use"),
                "bytes_limit": ms.get("bytes_limit"),
            })
        return out

    def _serve_steptrace(self, req: Request) -> Response:
        """The step flight recorder, raw: the ring tail (optionally
        clipped by ``?seconds=N`` / ``?n=N``) and the hot-path section
        tail — what the master's /admin/timeline pulls and merges."""
        try:
            window_s = float(req.param("seconds", "0") or 0)
        except ValueError:
            window_s = 0.0
        try:
            n = int(req.param("n", "0") or 0)
        except ValueError:
            n = 0
        from xllm_service_tpu.obs import profiler
        return Response.json({
            "name": self.name,
            "enabled": self.steptrace.enabled,
            "device_kind": self._device_kind,
            "platform": self._devices[0].platform,
            "device_count": len(self._devices),
            "devices": self._device_report(),
            "kv_pinned": {m: rt.engine.kv_pinned
                          for m, rt in self.runtimes.items()
                          if rt.engine is not None},
            "devtrace": self._devtrace_dir,
            "steps": self.steptrace.tail(n=n, window_s=window_s),
            "sections": profiler.recent_events(window_s=window_s),
        })

    def start_device_trace(self, out_dir: str,
                           python_tracer: bool = False) -> str:
        """Start ``jax.profiler`` in this process, the one that holds the
        chip, writing under ``out_dir``; then switch the program's
        ``xllm.*`` spans on (obs/steptrace.py), so that they land on the
        host plane of the same ``.xplane.pb`` as the device's
        operations. The Python tracer stays off unless asked for: it
        slows the very host whose gaps the trace is read for. One
        session at a time: a second start raises ``DeviceTraceError``."""
        if self._devtrace_dir is not None:
            raise DeviceTraceError(
                f"a device trace is already running "
                f"(into {self._devtrace_dir})")
        options = jax.profiler.ProfileOptions()
        if not python_tracer:
            options.python_tracer_level = 0
        try:
            jax.profiler.start_trace(out_dir, profiler_options=options)
        except RuntimeError as e:   # another session of this process
            raise DeviceTraceError(str(e)) from e
        self._devtrace_dir = out_dir
        steptrace.set_spans(True)
        return out_dir

    def stop_device_trace(self) -> str:
        """Switch the spans off, stop the profiler (it writes the trace
        now, which can take seconds) and return the directory."""
        if self._devtrace_dir is None:
            raise DeviceTraceError("no device trace is running")
        steptrace.set_spans(False)
        out_dir, self._devtrace_dir = self._devtrace_dir, None
        jax.profiler.stop_trace()
        return out_dir

    def _serve_devtrace(self, req: Request) -> Response:
        """``{"action": "start", "dir": ..., "python_tracer": false}`` or
        ``{"action": "stop"}``: the operator's handle on
        start_device_trace / stop_device_trace. 409 when the session's
        state does not allow the action."""
        try:
            body = req.json()
            action = body.get("action")
        except Exception:  # noqa: BLE001 — the 400 carries the
            # verdict straight back to the caller
            return Response.error(400, "invalid JSON body")
        try:
            if action == "start":
                if not body.get("dir"):
                    return Response.error(400, "start needs a dir")
                out_dir = self.start_device_trace(
                    str(body["dir"]), bool(body.get("python_tracer")))
            elif action == "stop":
                out_dir = self.stop_device_trace()
            else:
                return Response.error(
                    400, "action must be start or stop")
        except DeviceTraceError as e:
            return Response.error(409, str(e))
        return Response.json({"ok": True, "action": action,
                              "dir": out_dir})

    # ------------------------------------------------------------------
    # Serving
    # ------------------------------------------------------------------
    def _parse_generate(self, body: Dict[str, Any], is_chat: bool,
                        pd_prefill: bool = False) -> "_LiveRequest":
        srid = body.get("service_request_id") or f"req-{short_uuid()}"
        with steptrace.span("xllm.admit", rid=srid):
            return self._admit(body, srid, is_chat, pd_prefill)

    def _admit(self, body: Dict[str, Any], srid: str, is_chat: bool,
               pd_prefill: bool) -> "_LiveRequest":
        """From the parsed request to its sequences enqueued in the
        engine (the span ``xllm.admit`` on the handler's thread)."""
        model = body.get("model", self.opts.model)
        rt = self.runtimes.get(model) or self.primary_runtime()
        if rt.engine is None:
            raise RuntimeError(f"model {model} is asleep on this worker")
        token_ids = body.get("token_ids") or []
        if not token_ids:
            # Direct-to-worker use (no service in front): tokenize here.
            if is_chat:
                prompt = "\n".join(
                    str(m.get("content", ""))
                    for m in body.get("messages", []))
            else:
                prompt = body.get("prompt", "")
            token_ids = rt.tokenizer.encode(prompt)
        # Cross-worker cached-block fetch: execute the scheduler's plan
        # BEFORE admission so the admit's match_prefix hits the adopted
        # blocks (multimodal prompts never prefix-cache — skip).
        kvf = (body.get("routing") or {}).get("kv_fetch")
        if kvf and not body.get("mm_inputs"):
            try:
                self._maybe_fetch_blocks(rt, list(token_ids), kvf)
            except Exception as e:  # noqa: BLE001 — fetch is an
                # optimization; any surprise degrades to a cold prefill
                logger.warning("kv block fetch failed (%s); "
                               "recomputing", e)
        if body.get("sampling"):
            # Service-parsed SamplingParams travel in the rewritten body
            # (like token_ids/routing) — the single source of truth, so
            # fields the service normalized (max_completion_tokens, stop
            # strings, penalties) are never re-derived or lost here.
            sampling = SamplingParams.from_json(body["sampling"])
        else:
            sampling = parse_openai_sampling(body, is_chat)
        engine_sampling = sampling
        if pd_prefill:
            import dataclasses as _dc
            engine_sampling = _dc.replace(sampling, max_tokens=1,
                                          ignore_eos=False)
        mm_embeds = mm_positions = mm_rope_pos = None
        rope_delta = 0
        mm_inputs = body.get("mm_inputs") or []
        if mm_inputs:
            from xllm_service_tpu.nlp.chat_template import IMAGE_PLACEHOLDER
            from xllm_service_tpu.runtime.multimodal import (
                expand_image_placeholders, image_token_id)
            routing = body.get("routing") or {}
            embeds = self._resolve_mm_embeds(
                mm_inputs, routing.get("encode_name", ""),
                routing.get("encode_fallbacks", []), srid)
            n_img, tpi, _ = embeds.shape
            img_tok = image_token_id(rt.model_cfg.vocab_size)
            token_ids, mm_positions = expand_image_placeholders(
                list(token_ids), rt.tokenizer.encode(IMAGE_PLACEHOLDER),
                n_img, tpi, img_tok)
            mm_embeds = embeds.reshape(n_img * tpi, -1)
            if rt.model_cfg.is_mrope:
                # Qwen2-VL 3-D rope over the image spans. The merged
                # grid side comes from the EMBEDS the encode stage
                # produced (sqrt of tokens-per-image) — the only source
                # that stays correct when a remote ENCODE worker ran a
                # different resize target, and it needs no tower load
                # on a text-serving worker. mrope ids depend only on the
                # merged side, so the pre-merge (h, w, merge) pair below
                # is an arbitrary consistent factorization.
                from xllm_service_tpu.runtime.multimodal import (
                    mrope_positions)
                side = int(round(tpi ** 0.5))
                if side * side != tpi:
                    raise ValueError(
                        f"non-square image token count {tpi}; cannot "
                        f"derive the mrope grid")
                mm_rope_pos, rope_delta = mrope_positions(
                    token_ids, img_tok, [(1, 2 * side, 2 * side)] * n_img,
                    2)
        stream = bool(body.get("stream", False))
        validate_sampling(engine_sampling, stream)
        if engine_sampling.logit_bias:
            # Only the worker knows the model's vocab — reject typo'd /
            # wrong-tokenizer ids up front instead of silently ignoring
            # a "banned" token (OpenAI rejects invalid ids too).
            V = rt.model_cfg.vocab_size
            bad = [t for t in engine_sampling.logit_bias if t >= V]
            if bad:
                raise ValueError(
                    f"logit_bias token ids out of vocab range "
                    f"(< {V}): {bad[:5]}")
        # best_of: run the larger candidate pool; selection happens at
        # response assembly (ResponseCollector.target_n).
        n = 1 if pd_prefill else max(1, engine_sampling.n,
                                     engine_sampling.best_of or 0)
        include_usage = bool(
            (body.get("stream_options") or {}).get("include_usage", False))
        ereq = EngineRequest(
            request_id=srid,
            token_ids=list(token_ids),
            sampling=engine_sampling,
            offline=bool(body.get("offline", False)),
            priority=int(body.get("priority", 0)),
            eos_token_ids=rt.tokenizer.eos_token_ids,
            hold_after_finish=pd_prefill,
            mm_embeds=mm_embeds,
            mm_positions=mm_positions,
            mm_rope_pos=mm_rope_pos,
            rope_delta=rope_delta,
            prompt_logprobs=(sampling.echo and sampling.logprobs
                             and not is_chat and not pd_prefill))
        live = _LiveRequest(
            ereq, rt.tokenizer, srid, model, is_chat,
            stream, include_usage,
            stream_to_service=(not pd_prefill) and self._decode_to_service
            and bool(self.service_addr),
            n=n, stops=sampling.stop)
        live.sampling = sampling          # original (pre-pd) params
        live.prompt_tokens = len(token_ids)
        # Service-armed recovery ledger: emit per-frame token ids so
        # the relay can resume this stream exactly-once after a death.
        live.emit_token_ids = bool(body.get("ledger_tokens"))
        if not pd_prefill:
            live.target_n = max(1, sampling.n)
            self._writer_adopt(live)
        with self._live_lock:
            self._live_srid[srid] = live
            for erid in live.engine_rids:
                self._live[erid] = live
        # Poison-pill marking (worker.fault_step_req failpoint): a
        # non-firing peek at the armed value decides which requests
        # are marked. A string value marks prompts CONTAINING it (the
        # token ids are decoded — service relays ship ids, not text);
        # any other armed value marks every request.
        marked_rids: List[str] = []
        mark = self.failpoints.armed_value("worker.fault_step_req")
        if mark is not None:
            if isinstance(mark, str):
                try:
                    text = rt.tokenizer.decode(list(token_ids))
                except Exception:  # noqa: BLE001 — marking is chaos
                    text = ""      # plumbing, never a serving error
                if mark in text:
                    marked_rids = list(live.engine_rids)
            else:
                marked_rids = list(live.engine_rids)
        # As in the engine loop: the wait for the lock apart from the
        # stretch that holds it (which the loop's lock_wait sees).
        live.stamp("parsed")
        lock_wait = steptrace.span("xllm.admit.lock_wait", rid=srid)
        lock_wait.__enter__()
        with self._engine_lock:
            lock_wait.__exit__(None, None, None)
            live.stamp("locked")
            with steptrace.span("xllm.admit.locked", rid=srid):
                self._fault_marked.update(marked_rids)
                for k, erid in enumerate(live.engine_rids):
                    esp = engine_sampling
                    if n > 1:
                        # Distinct choices: seeded requests offset the
                        # seed per choice (identical streams otherwise),
                        # engine ids get a #k suffix.
                        seed = engine_sampling.seed
                        esp = dataclasses.replace(
                            engine_sampling,
                            seed=seed + k if seed is not None else None)
                    creq = ereq if n == 1 else dataclasses.replace(
                        ereq, request_id=erid, sampling=esp,
                        token_ids=list(token_ids),
                        # Prompt scores are candidate-independent:
                        # compute them once (candidate 0) and share via
                        # the live.
                        prompt_logprobs=ereq.prompt_logprobs and k == 0)
                    rt.engine.add_request(creq)
        self._work_event.set()
        return live

    def _guarded(self, inner, *args) -> Response:
        """Shared wrapper for every work-accepting handler: count the
        request in _inflight_parse BEFORE the refusal check (the inverse
        order races with drain_and_stop sampling the counters), refuse
        while draining, and always decrement. By the time a handler
        returns, its request is rejected, fully served, or registered in
        _live_srid / _relay_streams — the drain busy-check takes over."""
        with self._live_lock:
            self._inflight_parse += 1
        try:
            if self._refuse_new:
                return Response.error(503, "instance is draining",
                                      "unavailable")
            return inner(*args)
        finally:
            with self._live_lock:
                self._inflight_parse -= 1

    def _stream_response(self, stream: Iterator[bytes],
                         *cleanups) -> Response:
        """SSE response whose cleanups run exactly once when the server
        finishes with it — INCLUDING when the body generator is never
        started (a failed header write closes a never-started generator
        without running its finally, PEP 342), via Response.on_close."""
        done = [False]

        def on_close() -> None:
            if done[0]:
                return
            done[0] = True
            for c in cleanups:
                try:
                    c()
                except Exception as e:
                    # Every cleanup must run even when one fails — but
                    # the failure is counted, not dropped (a leaking
                    # cleanup here is a leaked live-request slot).
                    threads.record_callback_error(
                        "worker.stream_close", e)
        resp = Response.sse(stream)
        resp.on_close = on_close
        return resp

    def _serve_generate(self, req: Request, is_chat: bool) -> Response:
        return self._guarded(self._serve_generate_inner, req, is_chat)

    def _ingress_span(self, srid: str, t_recv: float,
                      headers: Dict[str, str]) -> None:
        """Open this worker's side of the request span under the SAME
        correlation id the service used (the ``x-xllm-request-id``
        header it stamped on the forward; the body's
        ``service_request_id`` is the fallback for direct-to-worker
        callers). Ships back on the heartbeat once finished."""
        corr = headers.get(REQUEST_ID_HEADER, "")
        if corr:
            self.spans.annotate(srid, correlation_header=corr)
        # The master's share of the time to the first token, where the
        # forward carries it (durations on the master's clock).
        front_ms = _header_ms(headers, FRONT_MS_HEADER)
        if front_ms is not None:
            self.spans.annotate(
                srid, front_ms=front_ms,
                schedule_ms=_header_ms(headers, SCHEDULE_MS_HEADER))
        self.spans.record(srid, "received", plane="worker", t_mono=t_recv)

    def _fold_first_token(self, live: _LiveRequest) -> None:
        """Close a request's first-token chain: one observation a stage
        into ``xllm_worker_first_token_stage_ms`` and one span event a new
        stamp, so that ``/admin/trace/<id>`` shows for one request what
        the histogram shows for all. On the thread that WRITES the first
        frame, once it is written (the stream writer's, or the handler's
        own on the pull path; or where the path's last stamp is taken:
        docs/OBSERVABILITY.md has the table); the engine thread only
        stamps. A path that lacks a stamp observes the stages it has."""
        stamps, live.stamps = live.stamps, None
        if not stamps:
            return
        h = self.obs.histogram(
            "xllm_worker_first_token_stage_ms",
            "a request's time to its first token by stage (obs/spans.py "
            "FIRST_TOKEN_STAMPS): master_in is the master's share as the "
            "forward carried it, total is received to first_frame, and "
            "the stages between sum to it",
            labelnames=("model", "stage"))
        m, srid = live.model, live.service_request_id
        if live.front_ms is not None:
            h.observe(live.front_ms, model=m, stage="master_in")
        for stage, ms in first_token_stages(stamps).items():
            h.observe(ms, model=m, stage=stage)
        # One offset for the chain: its order on the wall clock is its
        # order on this one.
        off = time.time() - time.monotonic()
        for name, t in list(stamps.items()):
            # (idempotent: ``received`` and ``first_token`` are events
            # already, recorded where they were taken; a copy, since at
            # the fan-in's ack the engine thread may still stamp)
            self.spans.record(srid, name, plane="worker", t_mono=t,
                              t_wall=t + off)

    def _fold_token_out(self, live: _LiveRequest) -> None:
        """Move a request's emit-to-wire sums into the counters: on the
        thread that writes its tokens, every 64 tokens and at the
        request's end (sums and a count; no histogram and no counter
        write on the token path)."""
        n, live.tok_n = live.tok_n, 0
        outs, live.out_n = live.out_n, 0
        if outs:
            self.obs.counter(
                "xllm_worker_stream_outputs_total",
                "engine outputs run through a streamed response's way "
                "out (Worker._stream_output) by who ran them: writer is "
                "the worker's one stream writer, handler the "
                "connection's own thread (a server whose chunk call can "
                "block)",
                labelnames=("model", "path")).inc(
                    outs, model=live.model, path=live.out_path)
        if not n:
            return
        secs = self.obs.counter(
            "xllm_worker_token_out_seconds_total",
            "every streamed or collected token's time after emit, summed:"
            " wake is from the emit that handed it out "
            "(Worker._dispatch_outputs' one clock read) until the thread "
            "that writes it has it in hand (the stream writer turns to "
            "it, or the handler's thread has it off the request's "
            "queue), write from there until its frame is written "
            "(collected, without a stream); over token_out_tokens_total, "
            "the mean a token",
            labelnames=("model", "stage"))
        secs.inc(live.tok_wake_s, model=live.model, stage="wake")
        secs.inc(live.tok_write_s, model=live.model, stage="write")
        live.tok_wake_s = live.tok_write_s = 0.0
        self.obs.counter(
            "xllm_worker_token_out_tokens_total",
            "tokens timed from emit to the wire",
            labelnames=("model",)).inc(n, model=live.model)

    def _serve_generate_inner(self, req: Request,
                              is_chat: bool) -> Response:
        t_recv = time.monotonic()
        # Injected faults first (no-ops unless armed): a delayed, hung,
        # or refused generate — the degraded-worker modes the service's
        # retry/redispatch/recovery machinery is tested against.
        v = self.failpoints.fire("worker.slow_response_ms")
        if v is not None:
            self._stop.wait((float(v) if v is not True else 100.0)
                            / 1000.0)
        v = self.failpoints.fire("worker.hang_rpc")
        if v is not None:
            # Hang for the armed seconds (default: effectively forever)
            # unless the worker shuts down first; then refuse.
            self._stop.wait(float(v) if v is not True else 3600.0)
            return Response.error(503, "hung rpc released (failpoint)",
                                  "unavailable")
        if self.failpoints.fire("worker.refuse_generate") is not None:
            return Response.error(503, "refused by failpoint",
                                  "unavailable")
        try:
            body = req.json()
        except Exception:  # noqa: BLE001 — the 400 carries the
            # verdict straight back to the caller
            return Response.error(400, "invalid JSON body")
        srid_hint = body.get("service_request_id") or ""
        if srid_hint:
            self._ingress_span(srid_hint, t_recv, req.headers)
        front_ms = _header_ms(req.headers, FRONT_MS_HEADER)
        routing = body.get("routing") or {}
        sp_body = body.get("sampling") or {}
        try:
            max_toks = int(sp_body.get("max_tokens",
                                       body.get("max_tokens", 16)))
            n_choices = int(sp_body.get("n", body.get("n", 1)))
        except (TypeError, ValueError) as e:
            # Direct-to-worker bodies get the same 400-not-500 treatment
            # as the service front door.
            return Response.error(400, f"invalid request: {e}")
        # best_of runs a candidate pool — like n>1, it decodes locally
        # (the PD handoff path migrates exactly one sequence). best_of is
        # a completion-API field; chat ignores it (parse_openai_sampling
        # nulls it), so a stray best_of on a chat body must not disable
        # the PD path.
        try:
            best_of = 1 if is_chat else int(
                sp_body.get("best_of") or body.get("best_of")
                or n_choices)
        except (TypeError, ValueError):
            best_of = 1     # _parse_generate rejects the body below
        # echo needs the prompt scored on the prefill engine and the
        # prepend handled by the worker that owns the live request —
        # decode it locally rather than through the PD handoff.
        echo = (not is_chat) and bool(
            sp_body.get("echo", body.get("echo", False)))
        if (routing.get("prefill_name") == self.name
                and routing.get("decode_name")
                and routing["decode_name"] != self.name
                and max_toks > 1 and n_choices == 1 and best_of <= 1
                and not echo):
            return self._serve_pd_prefill(body, is_chat,
                                          routing["decode_name"], t_recv,
                                          front_ms)
        try:
            live = self._parse_generate(body, is_chat)
        except (TypeError, ValueError, RuntimeError) as e:
            return Response.error(400, str(e))
        live.stamp("received", t_recv)
        live.front_ms = front_ms
        if not srid_hint:   # direct-to-worker: srid minted in the parse
            self._ingress_span(live.service_request_id, t_recv,
                               req.headers)
        self.spans.record(live.service_request_id, "scheduled",
                          plane="worker")
        if live.stream_to_service:
            # Topology 2: tokens flow worker → service RPC fan-in; the
            # relay response is a plain ack (rpc_service/service.h:67-79).
            # No handler's thread sees the first token: the chain is
            # folded here, as far as the admission took it.
            self._fold_first_token(live)
            return Response.json({"status": "accepted",
                                  "service_request_id":
                                      live.service_request_id})
        if live.stream:
            return self._sse_response(live)
        return self._collect_full(live)

    # ------------------------------------------------------------------
    # A streamed response's way out. Who runs it is decided by what the
    # code can see: where the response streams to its caller (not to the
    # master's fan-in) and the server's chunk call cannot block, the
    # worker's ONE writer does, woken once an iteration; else the
    # connection's own thread pulls from ``live.q``. The two paths share
    # ``_stream_output`` and nothing else.
    # ------------------------------------------------------------------
    def _writer_adopt(self, live: _LiveRequest) -> None:
        """Give ``live``'s stream to the writer where it may own it,
        BEFORE the engine can emit for it: ``_dispatch_outputs`` routes
        by ``live.push``."""
        if live.stream and not live.stream_to_service \
                and not self._srv.chunks_block:
            live.push = _Stream(self, live, "writer")

    def _sse_response(self, live: _LiveRequest,
                      initial: Optional[List[RequestOutput]] = None
                      ) -> Response:
        def finalize() -> None:
            self._finalize_live(live)
        st = live.push
        if st is None:
            return self._stream_response(
                self._stream_sse(live, initial), finalize)
        st.initial = initial or ()

        def release() -> None:
            # The server is done with a response it never served (a
            # failed header write): the writer forgets the stream.
            if not st.over.is_set():
                self._post_end(live, _ABORT)
        resp = self._stream_response(None, release, finalize)
        resp.push = st
        return resp

    def _stream_output(self, st: _Stream, out: StepOutput,
                       wake: float) -> Iterator[bytes]:
        """One engine output's way out of a streamed response, on the
        thread that writes it, which had it in hand at ``wake``: its
        frames, for the caller to write (the writer) or to yield on (the
        pull path), under the span ``xllm.stream.token``; once control
        is back from the last of them it is written, and the request's
        first-token chain and its emit-to-wire sums are booked."""
        live = st.live
        wrote = False
        with steptrace.span("xllm.stream.token"):
            for ro in self._process_step_output(live, out):
                for frame in st.asm.on_output(ro):
                    yield frame
                    wrote = True
                st.done = st.done or ro.finished
        st.last_t = written = time.monotonic()
        if wrote and live.stamps is not None and out.new_token_ids:
            # (the first frame that carries a token)
            live.stamp("first_frame", written)
            self._fold_first_token(live)
        live.out_n += 1
        if live.token_out(out, wake, written):
            self._fold_token_out(live)

    def _stream_sse(self, live: _LiveRequest,
                    initial: Optional[List[RequestOutput]] = None
                    ) -> Iterator[bytes]:
        """The pull path: the connection's own thread takes the
        request's outputs off ``live.q``, one wake a token."""
        st = _Stream(self, live, "handler")
        try:
            # The initial frames sit INSIDE the try: a client disconnect
            # while they stream must still run the finalizer.
            for ro in (initial or []):
                for frame in st.asm.on_output(ro):
                    yield frame
            while True:
                try:
                    out = live.q.get(
                        timeout=self.opts.request_timeout_s)
                except queue.Empty:
                    # (the finally cancels the unfinished engine work)
                    yield _timeout_frame(self.opts.request_timeout_s)
                    return
                if out is _ABORT:
                    # Simulated death: break the socket mid-stream (no
                    # [DONE]) so the relay sees what a crash looks like.
                    raise RuntimeError("worker died (failpoint)")
                if isinstance(out, _EngineFault):
                    yield _engine_fault_frame(out.verdict)
                    return
                if out is None:
                    yield SSE_DONE
                    return
                yield from self._stream_output(st, out, time.monotonic())
                if st.done:
                    return
        finally:
            self._finalize_live(live)
            self._fold_token_out(live)

    def _serve_pushed(self, st: _Stream, write) -> bool:
        """The handler's side of a stream the writer owns
        (``Response.push``): attach the sink, then park on ONE event
        until the writer ends the stream; a parked thread holds no
        interpreter. True: end it cleanly; False: break the socket. This
        thread also keeps the stream's watch: no output written for
        ``request_timeout_s`` and it posts ``_TIMEOUT``, which the
        writer checks again under the stream's one write order."""
        live = st.live
        st.write = write
        st.last_t = time.monotonic()
        with self._live_lock:
            closed = self._writer_closed
            if not closed:
                self._writer_q.put(((live, _ATTACH),))
        if closed:
            # The worker stopped before this stream began: what stop()'s
            # sentinel gives a stream.
            write(SSE_DONE)
            return True
        limit = wait = self.opts.request_timeout_s
        while not st.over.wait(timeout=wait):
            idle = time.monotonic() - st.last_t
            if idle >= limit:
                self._writer_q.put(((live, _TIMEOUT),))
                wait = limit
            else:
                wait = limit - idle
        return st.clean

    def _stream_writer_loop(self) -> None:
        """The worker's ONE stream writer (root ``worker.stream_writer``):
        takes an iteration's outputs as one item and runs each, in emit
        order, through ``_stream_output`` into its stream's sink, so that
        an iteration wakes one thread and not one a stream. The sink
        only queues a chunk for the server's event loop: one slow client
        stalls no other."""
        try:
            while True:
                try:
                    batch = self._writer_q.get(
                        timeout=self.opts.request_timeout_s)
                except queue.Empty:
                    continue
                if batch is None:
                    break
                self._writer_run(batch)
            with self._live_lock:
                self._writer_closed = True
            # Whatever was posted before the door closed, then the
            # streams still open: each gets stop()'s sentinel.
            while not self._writer_q.empty():
                self._writer_run(self._writer_q.get_nowait() or ())
            for st in list(self._writer_owned):
                if st.attached:
                    self._writer_take(st, None, 0.0)
            self._writer_owned.clear()
        except BaseException:
            # The crash is the supervised thread's (counted, restarted).
            # Every stream this thread owned is broken, none left
            # hanging: the ones it knew and the item in its hands.
            for st in list(self._writer_owned) + [
                    live.push for live, _ in self._writer_batch]:
                st.done, st.clean = True, False
                st.over.set()
            self._writer_owned.clear()
            raise

    def _writer_run(self, batch: Any) -> None:
        self._writer_batch = batch
        wake = time.monotonic()
        for live, out in batch:
            st = live.push
            if not st.done:
                wake = self._writer_take(st, out, wake)
        self._writer_batch = ()

    def _writer_take(self, st: _Stream, out: Any, wake: float) -> float:
        """One posted item of one stream, in the order posted (writer's
        thread). Returns the clock as last read: when the writer turns
        to the next output."""
        if out is _ABORT:
            # Simulated death, or a response never served: the socket
            # breaks with no [DONE], whatever is held.
            self._writer_end(st, clean=False)
            return wake
        if not st.attached:
            self._writer_owned.add(st)
            if out is not _ATTACH:
                # The engine was faster than the handler: held, and
                # written first at the attachment.
                st.held.append(out)
                return wake
            st.attached = True
            if any(st.write(frame) for ro in st.initial
                   for frame in st.asm.on_output(ro)):
                self._writer_end(st)        # the client is gone already
            held, st.held = st.held, []
            for o in held:
                if not st.done:
                    wake = self._writer_take(st, o, wake)
            return wake
        if isinstance(out, StepOutput):
            frames = self._stream_output(st, out, wake)
            for frame in frames:
                if st.write(frame):
                    # The client went away: as the pull path's server
                    # closes the generator at its yield.
                    frames.close()
                    st.done = True
                    break
            if st.done:
                self._writer_end(st)
            return st.last_t
        if out is _TIMEOUT:
            if wake - st.last_t < self.opts.request_timeout_s:
                return wake         # an output came meanwhile
            frame = _timeout_frame(self.opts.request_timeout_s)
        elif out is None:
            frame = SSE_DONE
        else:
            frame = _engine_fault_frame(out.verdict)
        st.write(frame)
        self._writer_end(st)
        return wake

    def _writer_end(self, st: _Stream, clean: bool = True) -> None:
        """The stream is over: its handler's thread wakes and runs what
        a handler runs at a response's end (the server's end or abort,
        ``_finalize_live``, which cancels what is unfinished)."""
        st.done, st.clean = True, clean
        self._writer_owned.discard(st)
        self._fold_token_out(st.live)
        st.over.set()

    def _collect_full(self, live: _LiveRequest,
                      initial: Optional[List[RequestOutput]] = None
                      ) -> Response:
        coll = ResponseCollector(live.service_request_id, live.model,
                                 live.is_chat, target_n=live.target_n)
        for ro in (initial or []):
            coll.add(ro)
        try:
            while True:
                try:
                    out = live.q.get(
                        timeout=self.opts.request_timeout_s)
                except queue.Empty:
                    # Same contract as the SSE path: a typed 504, and
                    # the finally cancels the unfinished engine work.
                    return Response.error(
                        504, f"no engine output within "
                             f"{self.opts.request_timeout_s:g}s",
                        "timeout")
                if out is _ABORT:
                    raise RuntimeError("worker died (failpoint)")
                if isinstance(out, _EngineFault):
                    # Typed 500: the service's redispatch path reads
                    # the engine_fault error type for its strike.
                    return Response.error(
                        500, f"engine_fault: {out.verdict}",
                        "engine_fault")
                if out is None:
                    break
                wake = time.monotonic()
                if live.stamps is not None:
                    # No frame is written before the last token: the
                    # chain ends at ``first_token``.
                    self._fold_first_token(live)
                done = False
                for ro in self._process_step_output(live, out):
                    coll.add(ro)
                    done = done or ro.finished
                # (no frame a token here: ``write`` is the collecting)
                if live.token_out(out, wake, time.monotonic()):
                    self._fold_token_out(live)
                if done:
                    break
        finally:
            self._finalize_live(live)
            self._fold_token_out(live)
        return Response.json(coll.body())

    # ------------------------------------------------------------------
    # Control surface
    # ------------------------------------------------------------------
    def _serve_models(self, req: Request) -> Response:
        return Response.json({
            "object": "list",
            "data": [{"id": m, "object": "model",
                      "owned_by": "xllm-service-tpu",
                      "state": rt.state}
                     for m, rt in self.runtimes.items()]})

    def _serve_metrics(self, req: Request) -> Response:
        """Refresh scrape-time mirrors, render the registry. Series
        names are unchanged from the hand-assembled exporter this
        replaced (the metrics-registry xlint rule keeps every line
        flowing through xllm_service_tpu/obs/)."""
        obs = self.obs
        for _m, rt in self.runtimes.items():
            if rt.engine is None:
                continue
            # Queue depths / KV utilization / preemptions + the
            # per-phase step-time attribution (pack / dispatch /
            # readback per program) and post-warmup recompile counters
            # — the engine's phase ledger, live per worker.
            self._engine_load(rt)
            self._flush_phase_ledger(rt)
            self._flush_phase_cpu(rt)
            self._flush_prefix_cache(rt)
        # Supervised-thread crash / swallowed-callback books
        # (utils/threads.py — process-global, root-labeled).
        threads.flush_metrics(obs)
        # Self-profiling mirrors on the worker plane too: hot-path
        # sections (sse.assemble/span.write/event.emit fire here),
        # sampled lock contention, per-root thread CPU, self-gauges.
        from xllm_service_tpu.obs import profiler
        profiler.flush_metrics(obs)
        # Keep-alive reuse pool, labeled with the exporting plane (the
        # pool is process-global — see the service-side exporter note).
        # In the separate-process deployment this is the worker→service
        # fan-in transport.
        from xllm_service_tpu.service.httpd import flush_conn_pool_metrics
        flush_conn_pool_metrics(obs, plane="worker")
        # This plane's view of the coordination store (store guard) —
        # the worker twin of the service-plane gauge; raw in-memory
        # stores report healthy.
        obs.gauge("xllm_store_health",
                  "coordination-store health as seen by this plane "
                  "(2 healthy / 1 flaky / 0 down)").set(
            int(getattr(self.store, "health", 2)))
        obs.counter(
            "xllm_worker_stream_writer_batch_outputs_sum",
            "outputs handed to the stream writer; over "
            "xllm_worker_stream_writer_batch_outputs_count, the "
            "outputs one wake of its thread carries").set_total(
            self._writer_batch_outs)
        obs.counter(
            "xllm_worker_stream_writer_batch_outputs_count",
            "iterations that handed outputs to the stream writer: one "
            "queue put each").set_total(self._writer_batches)
        obs.counter("xllm_worker_encode_seconds_total").set_total(
            self.encode_seconds)
        obs.counter("xllm_worker_encode_calls_total").set_total(
            self.encode_calls)
        obs.counter("xllm_worker_encode_images_total").set_total(
            self.encode_images_total)
        # Encode-plane books (docs/EPD.md): step ledger, embedding-cache
        # effectiveness, queue depth, staged-handoff tickets.
        obs.counter("xllm_worker_encode_steps_total",
                    "batched encode steps executed").set_total(
            self.encode_steps)
        obs.counter("xllm_encode_cache_hits_total",
                    "images served from the content-addressed "
                    "embedding cache").set_total(self.encode_cache_hits)
        obs.counter("xllm_encode_cache_misses_total",
                    "images that required a tower run").set_total(
            self.encode_cache_misses)
        obs.gauge("xllm_worker_encode_queue_depth",
                  "encode jobs waiting for the batched encode "
                  "loop").set(self._encode_q.qsize())
        with self._embed_mu:
            cache_len = len(self._embed_cache)
        obs.gauge("xllm_worker_embed_cache_entries",
                  "embeddings resident in the content-addressed "
                  "cache").set(cache_len)
        with self._encode_staged_mu:
            enc_staged = len(self._encode_staged)
        obs.gauge("xllm_worker_encode_staged",
                  "embedding tickets staged on the device wire "
                  "awaiting a requester pull").set(enc_staged)
        obs.counter("xllm_worker_kv_migration_bytes_total").set_total(
            self.kv_migration_bytes)
        obs.counter("xllm_worker_kv_migration_seconds_total").set_total(
            self.kv_migration_seconds)
        obs.counter("xllm_worker_kv_migration_direct_total").set_total(
            self.kv_migration_direct)
        obs.counter(
            "xllm_worker_kv_migration_device_wire_total").set_total(
            self.kv_migration_device_wire)
        obs.counter("xllm_worker_kv_migration_chunked_total").set_total(
            self.kv_migration_chunked)
        obs.counter("xllm_worker_kv_fetch_attempts_total",
                    "cross-worker cached-block fetches attempted "
                    "(requester side)").set_total(self.kv_fetch_attempts)
        obs.counter("xllm_worker_kv_fetch_failures_total",
                    "fetch attempts that fell back to recompute "
                    "(holder refusal, transport, failpoint)").set_total(
            self.kv_fetch_failures)
        obs.counter("xllm_worker_kv_fetch_bytes_total",
                    "KV bytes adopted from remote holders").set_total(
            self.kv_fetch_bytes)
        from xllm_service_tpu.runtime.kv_wire import peek_device_wire
        wire = peek_device_wire()
        if wire is not None:
            obs.gauge("xllm_worker_kv_wire_staged").set(
                wire.staged_count())
            obs.counter("xllm_worker_kv_wire_leaked_total").set_total(
                wire.leaked)
        if self.kv_migration_seconds > 0:
            obs.gauge("xllm_worker_kv_migration_gbps").set(
                self.kv_migration_bytes / self.kv_migration_seconds / 1e9)
        # Span-ring eviction visibility (same series name as the service
        # plane — each plane's registry owns its own ring).
        obs.counter(
            "xllm_span_evictions_total",
            "request spans dropped by ring overflow "
            "(size the ring with XLLM_SPAN_RING)").set_total(
            self.spans.eviction_count())
        return Response(body=obs.render().encode(),
                        content_type="text/plain; version=0.0.4")

    def _serve_sleep(self, req: Request) -> Response:
        model = req.json().get("model", "")
        rt = self.runtimes.get(model)
        if rt is None:
            return Response.error(404, f"model {model} not on this worker")
        with self._engine_lock:
            rt.sleep()
        return Response.json({"ok": True, "model": model,
                              "state": rt.state})

    def _serve_wakeup(self, req: Request) -> Response:
        if self._draining:       # refuse from the moment drain begins —
            # a wake mid-drain would re-advertise the model as awake.
            return Response.error(409, "instance is draining",
                                  "unavailable")
        model = req.json().get("model", "")
        rt = self.runtimes.get(model)
        if rt is None:
            return Response.error(404, f"model {model} not on this worker")
        with self._engine_lock:
            rt.wakeup()
            if self._should_warmup():
                # Scoped only (never the extended sweep): _engine_lock is
                # worker-wide, so this stalls every model on the worker
                # for its duration. Warm wakes re-load from the
                # persistent cache in seconds; a cold wake of a
                # fork-staged model compiles just the scoped handful,
                # and rarer shapes lazily compile as before (visible in
                # the recompile counters).
                rt.engine.warmup(extended=False)
        self._work_event.set()
        return Response.json({"ok": True, "model": model,
                              "state": rt.state})

    def _serve_fork_master(self, req: Request) -> Response:
        """Stage additional models asleep (weights on host, nothing in
        HBM until wakeup) — instance_mgr.cpp:229-260's engine side."""
        models = req.json().get("models", [])
        created = []
        for model in models:
            if model in self.runtimes:
                continue
            try:
                cfg = resolve_model_config(model)
            except ValueError as e:
                return Response.error(400, str(e))
            self.runtimes[model] = ModelRuntime(
                model, cfg, self.engine_cfg, self.tokenizer,
                mesh=self.mesh, seed=self.opts.seed,
                murmur_seed=self.opts.murmur_seed, start_asleep=True)
            created.append(model)
        return Response.json({"ok": True, "created": created})

    def _serve_flip_role(self, req: Request) -> Response:
        new_type = req.json().get("instance_type", "")
        try:
            self.instance_type = InstanceType(new_type)
        except ValueError:
            return Response.error(400, f"bad instance_type {new_type!r}")
        # Re-write the registration key so replicas learn the new role.
        if self._lease_id is not None:
            try:
                self._register_rewrite()
            except Exception as e:  # noqa: BLE001
                logger.warning("flip re-register failed: %s", e)
        return Response.json({"ok": True,
                              "instance_type": self.instance_type.value})

    def _register_rewrite(self) -> None:
        for itype in InstanceType:
            self.store.delete(instance_prefix(itype.value) + self.name)
        self._register()

    def _serve_cancel(self, req: Request) -> Response:
        srid = req.json().get("service_request_id", "")
        with self._live_lock:
            # The srid index survives individual choice completions, so a
            # cancel still reaches the remaining choices of an n>1 request.
            live = self._live_srid.get(srid) or self._live.get(srid)
        if live is None:
            return Response.json({"ok": False})
        rt = self.runtimes.get(live.model) or self.primary_runtime()
        if rt.engine is not None:
            with self._engine_lock:
                for erid in live.engine_rids:
                    rt.engine.cancel(erid)
            self._work_event.set()
        return Response.json({"ok": True})

    # ------------------------------------------------------------------
    # Embeddings (net-new vs the reference's "not support",
    # http_service/service.cpp:492): masked-mean-pool of the final hidden
    # states, served from the same weights as generation.
    # ------------------------------------------------------------------
    def _serve_embeddings(self, req: Request) -> Response:
        return self._guarded(self._serve_embeddings_inner, req)

    def _serve_embeddings_inner(self, req: Request) -> Response:
        import functools as _ft

        import jax.numpy as _jnp

        from xllm_service_tpu.models.transformer import forward_embedding
        body = req.json()
        inputs = body.get("input", [])
        if isinstance(inputs, str):
            inputs = [inputs]
        if not inputs:
            return Response.error(400, "input is required")
        model = body.get("model", self.opts.model)
        rt = self.runtimes.get(model) or self.primary_runtime()
        if rt.engine is None:
            return Response.error(503, f"model {model} asleep")
        embed_fn = self._embed_fns.get(rt.model)
        if embed_fn is None:
            embed_fn = jax.jit(_ft.partial(
                forward_embedding, cfg=rt.model_cfg))
            self._embed_fns[rt.model] = embed_fn
        # Over-limit inputs are REFUSED, not silently truncated: a
        # truncated embedding is a wrong answer that looks right
        # (VERDICT r5 weak #5). The limit is a per-input compile-shape
        # cap (pow2-bucketed T), independent of the engine's
        # max_model_len.
        id_lists = [rt.tokenizer.encode(t) or [0] for t in inputs]
        for i, ids in enumerate(id_lists):
            if len(ids) > self.EMBED_MAX_TOKENS:
                return Response.error(
                    400, f"input {i} is {len(ids)} tokens; the "
                         f"embeddings endpoint accepts at most "
                         f"{self.EMBED_MAX_TOKENS} tokens per input")
        B = 1 << max(len(id_lists) - 1, 0).bit_length()
        T = 1 << max(max(len(i) for i in id_lists) - 1, 0).bit_length()
        toks = np.zeros((B, T), np.int32)
        lens = np.zeros(B, np.int32)
        for i, ids in enumerate(id_lists):
            toks[i, :len(ids)] = ids
            lens[i] = len(ids)
        with self._engine_lock:
            out = np.asarray(embed_fn(
                rt.engine.params, tokens=_jnp.asarray(toks),
                lengths=_jnp.asarray(lens)))
        total = int(lens.sum())
        return Response.json({
            "object": "list",
            "model": model,
            "data": [{"object": "embedding", "index": i,
                      "embedding": out[i].tolist()}
                     for i in range(len(id_lists))],
            "usage": {"prompt_tokens": total, "total_tokens": total},
        })

    # ------------------------------------------------------------------
    # EPD multimodal encode stage (SURVEY.md §7.1 EPD row): the vision
    # encoder is its own AOT XLA computation, served by dedicated ENCODE
    # workers or run locally as fallback.
    # ------------------------------------------------------------------
    def _get_vision(self):
        with self._vision_lock:
            if self._vision is None:
                import functools as _ft

                # Real Qwen2-VL tower when the checkpoint carries one
                # (visual.* weights + vision_config, torch-oracle parity
                # in tests/test_qwen2vl_vision.py); synthetic ViT
                # fallback for registry models without a directory.
                if self.opts.model_dir:
                    from xllm_service_tpu.runtime.checkpoint import (
                        load_qwen2vl_vision)
                    # Fixed serve-time grid (one compiled tower shape);
                    # must be a multiple of patch_size·spatial_merge_size.
                    loaded = load_qwen2vl_vision(
                        self.opts.model_dir,
                        image_size=self._vision_image_size)
                    if loaded is not None:
                        vcfg, params = loaded
                        from xllm_service_tpu.models import (
                            qwen2vl_vision as _qv)
                        # params as a traced argument, NOT a closure —
                        # closed-over weights get baked into the program
                        # as constants (gigabytes at real tower sizes).
                        if isinstance(vcfg, _qv.Qwen25VLVisionConfig):
                            kind = "qwen25vl"
                            fn = jax.jit(
                                lambda p, patches, cos, sin, sf, sw, rev:
                                _qv.encode_patches_v25(
                                    p, vcfg, patches, cos, sin, sf, sw,
                                    rev))
                            entry = _qv.encode_images_fixed_grid_v25
                        else:
                            kind = "qwen2vl"
                            fn = jax.jit(
                                lambda p, patches, cos, sin, seg:
                                _qv.encode_patches(p, vcfg, patches, cos,
                                                   sin, seg))
                            entry = _qv.encode_images_fixed_grid
                        # One encode entry point regardless of variant:
                        # encode_images just calls it.
                        jit = fn
                        self._vision = (
                            kind, vcfg,
                            _ft.partial(entry, params, vcfg,
                                        jit_fn=lambda p, c, *a:
                                        jit(p, *a)))
                        return self._vision

                from xllm_service_tpu.models import vision as _vision
                cfg = self.primary_runtime().model_cfg
                vcfg = (_vision.VisionConfig.tiny(cfg.hidden_size)
                        if cfg.name.startswith("tiny")
                        else _vision.VisionConfig.for_model(cfg))
                params = _vision.init_vision_params(
                    vcfg, jax.random.PRNGKey(0))
                fn = jax.jit(_ft.partial(_vision.encode_image, params,
                                         vcfg))
                self._vision = ("synthetic", vcfg, fn)
            return self._vision

    def encode_images(self, mm_inputs: List[Any]) -> np.ndarray:
        """Run the vision encoder on this worker → [N, tokens_per_image,
        hidden] float32."""
        from xllm_service_tpu.runtime.multimodal import load_image
        kind, vcfg, fn = self._get_vision()
        t0 = time.monotonic()
        pixels = np.stack([load_image(m, vcfg.image_size)
                           for m in mm_inputs])
        if kind in ("qwen2vl", "qwen25vl"):
            out = fn(pixels)
        else:
            out = np.asarray(fn(pixels), np.float32)
        self.encode_seconds += time.monotonic() - t0
        self.encode_calls += 1
        self.encode_images_total += len(mm_inputs)
        return out

    def _encode_image_size(self) -> int:
        """Advertised serve-time image grid (registration): the
        compiled tower's side when it exists, 0 otherwise — peeks, never
        builds the tower (registration must not compile anything the
        deployment doesn't need)."""
        with self._vision_lock:
            if self._vision is None:
                return 0
            _kind, vcfg, _fn = self._vision
            return int(getattr(vcfg, "image_size", 0) or 0)

    # -- batched encode queue + step ledger (docs/EPD.md) --------------
    def _encode_loop(self) -> None:
        """Supervised root: drain the encode queue, one tower step per
        drain. Per-job failures (bad image specs) are attached to the
        job, never escape — a crash here means a bug, and the spawn
        harness restarts the loop so queued callers aren't stranded."""
        while not self._stop.is_set():
            try:
                job = self._encode_q.get(timeout=0.2)
            except queue.Empty:
                continue
            jobs = [job]
            while len(jobs) < 64:
                try:
                    jobs.append(self._encode_q.get_nowait())
                except queue.Empty:
                    break
            self._encode_step(jobs)

    def _encode_step(self, jobs: List[Dict[str, Any]]) -> None:
        """One encode step: resolve every job's digests against the
        embedding cache, run the tower ONCE over all missed images
        across jobs, fill the cache (recording the heartbeat delta),
        and hand each job its [N, tokens_per_image, hidden] result."""
        t0 = time.monotonic()
        # Cache lookups first (never hold the cache lock across the
        # tower call).
        need: List[Tuple[int, int]] = []     # (job idx, image idx)
        rows: List[List[Optional[np.ndarray]]] = []
        with self._embed_mu:
            for ji, job in enumerate(jobs):
                jrows: List[Optional[np.ndarray]] = []
                for ii, dig in enumerate(job["digests"]):
                    hit = self._embed_cache.get(dig)
                    if hit is not None:
                        self._embed_cache.move_to_end(dig)
                        self.encode_cache_hits += 1
                        jrows.append(hit)
                    else:
                        self.encode_cache_misses += 1
                        jrows.append(None)
                        need.append((ji, ii))
                rows.append(jrows)
        fresh: Dict[Tuple[int, int], np.ndarray] = {}
        if need:
            try:
                batch = [jobs[ji]["mm"][ii] for ji, ii in need]
                out = self.encode_images(batch)
            except Exception as e:  # noqa: BLE001 — per-job verdict:
                # a bad image spec is the CALLER's 400, not an encode-
                # loop crash stranding every queued job.
                for job in jobs:
                    job["err"] = e
                    job["ev"].set()
                return
            stored: List[str] = []
            evicted: List[str] = []
            with self._embed_mu:
                for pos, (ji, ii) in enumerate(need):
                    emb = np.asarray(out[pos], np.float32)
                    fresh[(ji, ii)] = emb
                    dig = jobs[ji]["digests"][ii]
                    if dig not in self._embed_cache:
                        self._embed_cache[dig] = emb
                        stored.append(dig)
                        while len(self._embed_cache) > \
                                self._embed_cache_cap:
                            old, _ = self._embed_cache.popitem(last=False)
                            evicted.append(old)
                self._embed_stored_pending.extend(stored)
                self._embed_removed_pending.extend(evicted)
        step_ms = 1000.0 * (time.monotonic() - t0)
        self.encode_steps += 1
        self.obs.histogram(
            "xllm_worker_encode_step_ms",
            "wall time of one batched encode step").observe(step_ms)
        with self._embed_mu:
            self._encode_recent_ms.append(step_ms)
            del self._encode_recent_ms[:-64]
        for ji, job in enumerate(jobs):
            try:
                emb_rows = [r if r is not None else fresh[(ji, ii)]
                            for ii, r in enumerate(rows[ji])]
                job["out"] = np.stack(emb_rows)
                job["hits"] = sum(1 for r in rows[ji] if r is not None)
            except Exception as e:  # noqa: BLE001 — shape mismatch
                job["err"] = e      # across cached towers is a verdict,
            job["ev"].set()         # not a loop crash

    def encode_via_queue(self, mm_inputs: List[Any],
                         timeout: Optional[float] = None
                         ) -> Tuple[np.ndarray, int]:
        """Encode through the batched queue + embedding cache. Returns
        (embeds, cache_hits). Raises the per-job error (bad specs) or
        TimeoutError when the loop couldn't serve within ``timeout``."""
        from xllm_service_tpu.runtime.multimodal import image_digest
        job: Dict[str, Any] = {
            "mm": list(mm_inputs),
            "digests": [image_digest(m, self.opts.murmur_seed)
                        for m in mm_inputs],
            "ev": threading.Event()}
        self._encode_q.put(job)
        if not job["ev"].wait(timeout if timeout and timeout > 0
                              else 300.0):
            raise TimeoutError("encode queue did not serve the job "
                               "in time")
        if "err" in job:
            raise job["err"]
        return job["out"], int(job.get("hits", 0))

    def _serve_encode(self, req: Request) -> Response:
        return self._guarded(self._serve_encode_inner, req)

    def _serve_encode_inner(self, req: Request) -> Response:
        from xllm_service_tpu.runtime.multimodal import (
            embeds_raw_meta, embeds_to_wire)
        # Chaos sites (docs/ROBUSTNESS.md): fail → the requester walks
        # its fallback chain; hang → exercises the requester's
        # XLLM_ENCODE_TIMEOUT_S deadline.
        hang = self.failpoints.fire("worker.hang_encode")
        if hang is not None:
            self._stop.wait(float(hang) if hang is not True else 30.0)
        if self.failpoints.fire("worker.fail_encode") is not None:
            return Response.error(
                500, "injected encode failure "
                     "(failpoint worker.fail_encode)")
        body = req.json()
        images = body.get("images") or body.get("mm_inputs") or []
        if not images:
            return Response.error(400, "no images")
        try:
            embeds, hits = self.encode_via_queue(images)
        except ValueError as e:
            return Response.error(400, str(e))
        except TimeoutError as e:
            return Response.error(503, str(e), "unavailable")
        # Embedding handoff (mirrors /kv/blocks): device-wire staged
        # ticket when the requester can pull, raw octet-stream (meta
        # line + float32 payload) otherwise; legacy base64-JSON only
        # for callers that asked for neither.
        if body.get("wire") and self.opts.pd_device_wire:
            from xllm_service_tpu.runtime.kv_wire import get_device_wire
            wire = get_device_wire()
            if wire is not None:
                try:
                    dev = jnp.asarray(embeds)
                    uuid = wire.stage_one(dev)
                except Exception as e:  # noqa: BLE001 — wire broke
                    logger.warning("embed staging failed (%s); serving "
                                   "raw", e)
                else:
                    with self._encode_staged_mu:
                        self._encode_staged[uuid] = (time.monotonic(),
                                                     wire)
                    return Response.json({
                        "status": "staged", "cache_hits": hits,
                        "transfer": {"addr": wire.address, "uuid": uuid,
                                     "shape": list(embeds.shape),
                                     "dtype": "float32"}})
        if body.get("raw"):
            meta = embeds_raw_meta(embeds)
            meta["cache_hits"] = hits
            payload = (json.dumps(stamp(meta)).encode("utf-8") + b"\n"
                       + np.ascontiguousarray(
                           embeds, dtype=np.float32).tobytes())
            return Response(body=payload,
                            content_type="application/octet-stream")
        out = embeds_to_wire(embeds)
        out["cache_hits"] = hits
        return Response.json(out)

    def _serve_encode_done(self, req: Request) -> Response:
        """Requester's pull acknowledgment for a staged embedding
        ticket — same release contract as /kv/blocks_done."""
        try:
            body = req.json()
            uuid = int(body.get("uuid"))
        except Exception:  # noqa: BLE001 — bad JSON / missing uuid
            return Response.error(400, "invalid body")
        outcome = body.get("outcome", "pulled")
        with self._encode_staged_mu:
            entry = self._encode_staged.pop(uuid, None)
        if entry is None:
            return Response.json({"ok": True, "known": False})
        _, wire = entry
        if outcome == "pulled":
            wire.release(uuid)
        elif outcome == "nopull":
            wire.release(uuid, drain=True)
        else:
            wire.release(uuid, leaked=True)
        return Response.json({"ok": True, "known": True})

    def _sweep_encode_staged(self, ttl: float = 60.0) -> None:
        """Heartbeat-cadence TTL sweep of embedding tickets whose
        requester never acknowledged (died mid-pull) — transfer state
        unknown, count the pin as leaked (kv_wire release contract)."""
        now = time.monotonic()
        with self._encode_staged_mu:
            stale = [(u, e) for u, e in self._encode_staged.items()
                     if now - e[0] > ttl]
            for u, _ in stale:
                del self._encode_staged[u]
        for u, (_, wire) in stale:
            wire.release(u, leaked=True)

    def _count_encode_fallback(self, reason: str, from_name: str,
                               to_name: str) -> None:
        """Satellite telemetry (docs/EPD.md): a routed encode stage not
        served by its chosen instance is COUNTED and an event — never
        just a log line."""
        self.obs.counter(
            "xllm_encode_fallback_total",
            "routed encode stages rerouted to a survivor or degraded "
            "to local encode, by reason",
            labelnames=("reason",)).inc(reason=reason)
        self.events.emit("encode_fallback", reason=reason,
                         source=from_name, target=to_name)
        logger.warning("encode fallback (%s): %s -> %s", reason,
                       from_name, to_name or "local")

    def _fetch_remote_embeds(self, target: str, mm_inputs: List[Any],
                             timeout: float
                             ) -> Tuple[np.ndarray, int]:
        """One remote /encode attempt against ``target``; understands
        all three response forms (staged wire ticket, raw octet-stream,
        legacy base64 JSON). Raises on any failure — the caller owns
        the fallback walk."""
        from xllm_service_tpu.runtime.kv_wire import (
            WireNoPull, WireUnsupported, get_device_wire, pull_one)
        from xllm_service_tpu.runtime.multimodal import (
            embeds_from_raw, embeds_from_wire)
        from xllm_service_tpu.service.httpd import http_stream_status
        can_pull = bool(self.opts.pd_device_wire
                        and target not in self._wire_refused
                        and get_device_wire() is not None)
        status, body_iter = http_stream_status(
            "POST", target, "/encode",
            obj=stamp({"images": mm_inputs, "raw": True,
                       "wire": can_pull}),
            timeout=timeout)
        raw = b"".join(body_iter)
        if status != 200:
            raise RuntimeError(f"/encode returned HTTP {status}")
        if raw.startswith(b"{") and b"\n" not in raw:
            head = json.loads(raw.decode("utf-8"))
            tr = head.get("transfer")
            if head.get("status") == "staged" and tr:
                outcome = "pulled"
                arr = None
                try:
                    arr = np.asarray(jax.device_get(pull_one(tr)),
                                     np.float32)
                except (WireUnsupported, WireNoPull):
                    outcome = "nopull"
                except Exception:  # noqa: BLE001 — failed mid-pull
                    outcome = "error"
                try:
                    # The done-notify rides inside the attempt budget:
                    # a fresh constant here could stack past the
                    # caller's XLLM_ENCODE_TIMEOUT_S deadline.
                    http_json("POST", target, "/encode_done",
                              {"uuid": tr.get("uuid"),
                               "outcome": outcome},
                              timeout=min(10.0, timeout))
                except Exception:  # noqa: BLE001 — holder TTL-sweeps it
                    pass
                if arr is None:
                    raise RuntimeError(
                        f"embed wire pull failed ({outcome})")
                return arr, int(head.get("cache_hits", 0))
            # Legacy base64-JSON body.
            return embeds_from_wire(head), int(head.get("cache_hits", 0))
        nl = raw.find(b"\n")
        if nl < 0:
            raise ValueError("malformed raw embed payload")
        meta = json.loads(raw[:nl].decode("utf-8"))
        return (embeds_from_raw(meta, raw[nl + 1:]),
                int(meta.get("cache_hits", 0)))

    def _resolve_mm_embeds(self, mm_inputs: List[Any],
                           encode_name: str,
                           fallbacks: Optional[List[str]] = None,
                           srid: str = "") -> np.ndarray:
        """EPD encode stage (docs/EPD.md): walk the routed encode
        instance then its ranked survivors under one
        XLLM_ENCODE_TIMEOUT_S deadline (jittered RetryPolicy pacing
        between attempts), then degrade to LOCAL encode — an encode-
        worker death is never a client-visible error. Every hop off the
        routed instance counts xllm_encode_fallback_total{reason} and
        emits an encode_fallback event; the resolved stage is recorded
        as the request's "encoded" span."""
        t_start = time.monotonic()
        total = self._encode_timeout_s
        deadline = t_start + total
        policy = RetryPolicy(max_attempts=1, base_delay_s=0.05,
                             max_delay_s=2.0, multiplier=2.0,
                             jitter=0.5)
        targets: List[str] = []
        for t in [encode_name] + list(fallbacks or []):
            if t and t != self.name and t not in targets:
                targets.append(t)
        for attempt, target in enumerate(targets):
            remaining = deadline - time.monotonic()
            if remaining <= 0.05:
                self._count_encode_fallback("deadline", target, "local")
                break
            try:
                embeds, hits = self._fetch_remote_embeds(
                    target, mm_inputs, timeout=remaining)
            except Exception as e:  # noqa: BLE001 — any transport /
                # holder failure walks the chain; the reason label
                # keeps the classes distinguishable.
                nxt = targets[attempt + 1] \
                    if attempt + 1 < len(targets) else "local"
                self._count_encode_fallback(
                    "unreachable" if isinstance(e, (OSError,
                                                    ConnectionError))
                    else "error", target, nxt)
                policy.sleep(attempt, deadline=deadline,
                             stop_event=self._stop)
                continue
            if srid:
                self.spans.record(
                    srid, "encoded", plane="worker", remote=target,
                    cache_hits=hits, images=len(mm_inputs),
                    ms=round(1000.0 * (time.monotonic() - t_start), 3))
            return embeds
        embeds, hits = self.encode_via_queue(
            mm_inputs, timeout=max(deadline - time.monotonic(), 5.0))
        if srid:
            self.spans.record(
                srid, "encoded", plane="worker", remote="",
                cache_hits=hits, images=len(mm_inputs),
                ms=round(1000.0 * (time.monotonic() - t_start), 3))
        return embeds

    # ------------------------------------------------------------------
    # PD disaggregation (SURVEY.md §7.2 step 7): prefill here, decode on
    # the routed decode instance. v0 transfer is the host shuttle
    # (device_get → HTTP octet-stream → device_put); the wire format is
    # one meta-JSON line + raw K bytes + raw V bytes.
    # ------------------------------------------------------------------
    def _serve_pd_prefill(self, body: Dict[str, Any], is_chat: bool,
                          decode_name: str, t_recv: float,
                          front_ms: Optional[float]) -> Response:
        try:
            live = self._parse_generate(body, is_chat, pd_prefill=True)
        except (ValueError, RuntimeError) as e:
            return Response.error(400, str(e))
        live.stamp("received", t_recv)
        live.front_ms = front_ms
        rt = self.runtimes.get(live.model) or self.primary_runtime()
        srid = live.service_request_id
        self.spans.record(srid, "scheduled", plane="worker")
        try:
            first = live.q.get(
                timeout=self.opts.request_timeout_s)   # prefill StepOutput
        except queue.Empty:
            # Saturated prefill queue: cancel so the held entry can never
            # leak pages when the request eventually completes.
            with self._engine_lock:
                if rt.engine is not None:
                    rt.engine.cancel(srid)
                    rt.engine.drop_held(srid)
            self._drop_live(srid)
            self._finalize_live(live)
            return Response.error(504, "prefill timed out")
        if first is _ABORT:
            self._drop_live(srid)
            self._finalize_live(live)
            return Response.error(503, "worker died (failpoint)",
                                  "unavailable")
        # The first token is made here and its frame written by whoever
        # decodes: the chain ends at ``first_token``.
        self._fold_first_token(live)
        self._drop_live(srid)
        if first is None or first.finish_reason == FinishReason.STOP \
                or first.finish_reason == FinishReason.CANCELLED:
            # EOS on the very first token (or cancel): nothing to migrate.
            with self._engine_lock:
                rt.engine.drop_held(srid)
            outs = [self._to_request_output(live, first)] if first else []
            outs = [o for o in outs if o is not None]
            self._finalize_live(live)
            if self._topology2():
                self._push_outputs_to_service(outs)
                return Response.json({"status": "accepted",
                                      "service_request_id": srid})
            return self._respond_outputs(live, outs)
        # The prefill-side live is only a metadata carrier from here on
        # (assembly uses the decode side's outputs) — finalize it now or
        # its srid entry outlives the request and blocks drains. The
        # relay/migrate streams below are tracked by _relay_streams.
        live.choices[0].finished = True
        self._finalize_live(live)
        if self.failpoints.fire("worker.fail_kv_transfer") is not None:
            # Injected transport failure: every migration path is
            # skipped as if the decode peer were unreachable, proving
            # the local-decode fallback keeps the request alive.
            with self._engine_lock:
                exported = rt.engine.export_held(srid, device=True)
            if exported is None:
                return Response.error(500, "prefill KV export failed")
            tokens, k, v = exported
            logger.warning("failpoint worker.fail_kv_transfer: decoding "
                           "%s locally", srid)
            return self._local_decode_fallback(
                live, tokens, np.asarray(jax.device_get(k)),
                np.asarray(jax.device_get(v)))
        peer = (_LOCAL_WORKERS.get(decode_name)
                if self.opts.pd_direct_kv else None)
        if peer is not None and peer is not self:
            return self._migrate_direct(live, rt, srid, peer)

        wire = self._kv_wire_for(decode_name)
        # Export stays ON DEVICE for every transport: the wire pulls it
        # directly, and the chunked shuttle needs device slices to
        # overlap its D2H copies with the socket sends.
        with self._engine_lock:
            exported = rt.engine.export_held(srid, device=True)
        if exported is None:
            return Response.error(500, "prefill KV export failed")
        tokens, k, v = exported
        if wire is not None:
            resp = self._migrate_device_wire(live, decode_name, srid,
                                             tokens, k, v, wire)
            if resp is not None:
                return resp
            # Wire handshake failed or the peer can't pull — fall
            # through to the host shuttle (the held entry is already
            # released, so a re-export is not possible; k/v stay valid
            # device arrays).

        t0 = time.monotonic()
        meta = {
            "service_request_id": srid,
            "model": live.model,
            "tokens": tokens,
            "prompt_len": len(live.req.token_ids),
            "rope_delta": live.req.rope_delta,
            "mm": _mm_meta(live.req),
            "sampling": live.sampling.to_json(),
            "shape": list(k.shape),
            "dtype": str(k.dtype),
            "stream": live.stream,
        }
        from xllm_service_tpu.service.httpd import http_stream

        # Pipelined chunked shuttle first: every D2H copy is started
        # async up front, each chunk POSTs as its bytes land, and the
        # decode side device_puts chunks on arrival — both
        # directions stay busy instead of one monolithic get→send→put
        # chain. Falls back to the monolithic shuttle on any miss.
        k_host = v_host = None
        total, chunk_bytes = self._shuttle_send_chunks(
            decode_name, srid, k, v)
        if total:
            head = b""
            chunks = iter(())
            try:
                chunks = http_stream(
                    "POST", decode_name, "/kv/import",
                    obj=stamp({**meta, "chunked": {"total": total}}),
                    timeout=self.opts.request_timeout_s)
                head = next(chunks, b"")
            except Exception as e:  # noqa: BLE001 — peer unreachable
                logger.warning("chunked kv import to %s failed (%s); "
                               "decoding locally", decode_name, e)
                k_host = np.asarray(jax.device_get(k))
                v_host = np.asarray(jax.device_get(v))
                return self._local_decode_fallback(live, tokens, k_host,
                                                   v_host)
            parsed = self._parse_import_head(head)
            err = ((parsed or {}).get("error") or {})
            msg = err.get("message", "") if isinstance(err, dict) else ""
            if parsed is None or parsed.get("status") == "accepted":
                self.kv_migration_bytes += chunk_bytes
                self.kv_migration_seconds += time.monotonic() - t0
                self.kv_migration_chunked += 1
                return self._finish_migration(
                    live, decode_name, tokens, head, chunks, parsed,
                    lambda: (np.asarray(jax.device_get(k)),
                             np.asarray(jax.device_get(v))))
            if not msg.startswith("chunks-missing"):
                # Genuine refusal (no capacity / model asleep) — the
                # monolithic retry would meet the same answer.
                logger.warning("kv import rejected by %s (%r); decoding "
                               "locally", decode_name, head[:120])
                k_host = np.asarray(jax.device_get(k))
                v_host = np.asarray(jax.device_get(v))
                return self._local_decode_fallback(live, tokens, k_host,
                                                   v_host)
            logger.warning("chunked staging incomplete on %s; retrying "
                           "monolithic", decode_name)

        if k_host is None:
            k_host = np.asarray(jax.device_get(k))
            v_host = np.asarray(jax.device_get(v))
        # Host copies made: drop the device refs now instead of pinning
        # 2x block-size of HBM through the POST + stream-head wait (and,
        # for concurrent migrations, each other).
        k = v = None
        payload = (json.dumps(stamp(meta)).encode("utf-8") + b"\n"
                   + k_host.tobytes() + v_host.tobytes())
        head = b""
        chunks = iter(())
        try:
            chunks = http_stream("POST", decode_name, "/kv/import",
                                 raw=payload,
                                 timeout=self.opts.request_timeout_s)
            head = next(chunks, b"")
        except Exception as e:  # noqa: BLE001 — decode instance unreachable
            logger.warning("kv migration to %s failed (%s); decoding "
                           "locally", decode_name, e)
            return self._local_decode_fallback(live, tokens, k_host,
                                               v_host)
        self.kv_migration_bytes += len(payload)
        self.kv_migration_seconds += time.monotonic() - t0
        return self._finish_migration(
            live, decode_name, tokens, head, chunks,
            self._parse_import_head(head),
            lambda: (k_host, v_host))

    def _shuttle_send_chunks(self, decode_name: str, srid: str,
                             k, v) -> Tuple[int, int]:
        """Pipelined half of the host shuttle: slice the exported device
        block along the layer axis, start EVERY device→host copy async
        up front, then POST each chunk to the decode side's /kv/chunk as
        its bytes land (which device_puts on arrival, overlapping the
        opposite direction). Returns (chunk count, bytes sent) on
        success, (0, 0) when chunking is off / not worthwhile / any POST
        failed (the caller then takes the monolithic path; TTL eviction
        clears any partially-staged chunks on the peer). The byte count
        is the CALLER's to commit, and only on an accepted import — a
        fallback to the monolithic shuttle after these sends must not
        count the same KV block twice in the bandwidth gauge."""
        chunk_mb = self._kv_shuttle_chunk_mb
        if chunk_mb <= 0 or not hasattr(k, "copy_to_host_async"):
            return 0, 0
        L = int(k.shape[0])
        layer_bytes = 2 * int(np.prod(k.shape[1:])) * k.dtype.itemsize
        per_chunk = max(1, int(chunk_mb * 1e6) // max(layer_bytes, 1))
        n = (L + per_chunk - 1) // per_chunk
        if n < 2:
            return 0, 0         # one chunk ⇒ nothing to overlap
        bounds = [(i * per_chunk, min(L, (i + 1) * per_chunk))
                  for i in range(n)]
        try:
            parts = [(k[lo:hi], v[lo:hi]) for lo, hi in bounds]
            for pk, pv in parts:
                pk.copy_to_host_async()
                pv.copy_to_host_async()
        except Exception as e:  # noqa: BLE001 — backend quirk → monolith
            logger.info("chunked shuttle slicing failed (%s); "
                        "monolithic", e)
            return 0, 0
        from xllm_service_tpu.service.httpd import http_stream_status
        sent = 0
        for idx, (lo, hi) in enumerate(bounds):
            pk, pv = parts[idx]
            parts[idx] = None                 # free each slice post-copy
            k_host = np.asarray(pk)           # completes the async D2H
            v_host = np.asarray(pv)
            pk = pv = None
            meta = stamp({
                "service_request_id": srid,
                "idx": idx, "total": n, "lo": lo, "hi": hi,
                "shape": list(k_host.shape), "dtype": str(k_host.dtype),
            })
            payload = (json.dumps(meta).encode("utf-8") + b"\n"
                       + k_host.tobytes() + v_host.tobytes())
            try:
                status, body = http_stream_status(
                    "POST", decode_name, "/kv/chunk", raw=payload,
                    timeout=self.opts.request_timeout_s)
                body.close()
            except Exception as e:  # noqa: BLE001 — peer miss → monolith
                logger.info("kv chunk %d/%d to %s failed (%s)",
                            idx + 1, n, decode_name, e)
                return 0, 0
            if status != 200:
                # Older peer (404) or refusal: monolithic fallback.
                logger.info("kv chunk %d/%d refused by %s (HTTP %d)",
                            idx + 1, n, decode_name, status)
                return 0, 0
            sent += len(payload)
        return n, sent

    def _serve_kv_chunk(self, req: Request) -> Response:
        """Decode-side staging of one pipelined-shuttle chunk: bytes →
        device_put (async H2D — the upload proceeds while the prefill
        side reads its next chunk) under (srid, idx). The final
        /kv/import with a ``chunked`` manifest assembles and adopts."""
        return self._guarded(self._serve_kv_chunk_inner, req)

    def _serve_kv_chunk_inner(self, req: Request) -> Response:
        nl = req.body.find(b"\n")
        if nl < 0:
            return Response.error(400, "missing meta line")
        try:
            meta = json.loads(req.body[:nl].decode("utf-8"))
        except (ValueError, UnicodeDecodeError) as e:
            return Response.error(400, f"bad meta: {e}")
        check_version(meta, "kv_chunk")
        try:
            k_np, v_np = _decode_kv_blob(meta, req.body[nl + 1:])
        except ValueError as e:
            return Response.error(400, str(e))
        # device_put is async: the H2D upload overlaps the prefill
        # side's next D2H + send. (np arrays are copied by the runtime,
        # so the request body buffer may be freed immediately.)
        k_dev = jax.device_put(k_np)
        v_dev = jax.device_put(v_np)
        srid = meta["service_request_id"]
        now = time.monotonic()
        with self._kv_chunk_mu:
            self._evict_stale_chunks_locked(now)
            entry = self._kv_chunk_staging.setdefault(
                srid, {"t": now, "total": int(meta["total"]),
                       "parts": {}})
            entry["t"] = now
            entry["parts"][int(meta["idx"])] = (k_dev, v_dev)
        return Response.json({"status": "staged"})

    def _evict_stale_chunks_locked(self, now: float,
                                   ttl: float = 60.0) -> None:
        """Drop staging entries whose final /kv/import never came (a
        prefill worker that died mid-send must not pin device buffers).
        Caller holds _kv_chunk_mu."""
        for srid in [s for s, e in self._kv_chunk_staging.items()
                     if now - e["t"] > ttl]:
            del self._kv_chunk_staging[srid]
            logger.warning("evicted stale kv-chunk staging for %s", srid)

    def _pop_staged_chunks(self, srid: str, total: int):
        """Assemble a completed chunk set into (k, v) device arrays, or
        None when any part is missing (prefill retries monolithic)."""
        with self._kv_chunk_mu:
            entry = self._kv_chunk_staging.pop(srid, None)
        if entry is None or entry["total"] != total \
                or len(entry["parts"]) != total:
            return None
        parts = [entry["parts"][i] for i in range(total)]
        k = jnp.concatenate([p[0] for p in parts], axis=0)
        v = jnp.concatenate([p[1] for p in parts], axis=0)
        return k, v

    @staticmethod
    def _parse_import_head(head: bytes) -> Optional[Dict[str, Any]]:
        """The decode side's /kv/import answer: a dict when the head is
        a JSON verdict ({} when unparseable), None when it is an SSE
        stream to relay."""
        if not head.startswith(b"{"):
            return None
        try:
            return json.loads(head.decode("utf-8")) or {}
        except (ValueError, UnicodeDecodeError):
            return {}

    def _finish_migration(self, live: "_LiveRequest", decode_name: str,
                          tokens: List[int], head: bytes, chunks,
                          parsed: Optional[Dict[str, Any]],
                          to_host) -> Response:
        """Shared tail of both /kv/import transports: act on the decode
        side's verdict. ``to_host()`` materializes (k, v) as host arrays
        when a refusal (no capacity / model asleep) means decoding
        locally; a stream head relays the decode instance's SSE."""
        if parsed is None:
            # Relay topology: decode streams raw RequestOutput SSE
            # frames back on this same connection; re-assemble
            # client-facing chunks here.
            return self._relay_decode_stream(live, head, chunks)
        if parsed.get("status") == "accepted":
            return Response.json(parsed)
        logger.warning("kv import rejected by %s (%r); decoding "
                       "locally", decode_name, head[:120])
        k, v = to_host()
        return self._local_decode_fallback(live, tokens, k, v)

    def _kv_wire_for(self, decode_name: str):
        """The process's PJRT device wire, or None when gated off, the
        local backend failed its loopback probe, or this decode peer
        already proved unable to pull (remembered 424)."""
        if not self.opts.pd_device_wire \
                or decode_name in self._wire_refused:
            return None
        from xllm_service_tpu.runtime.kv_wire import get_device_wire
        return get_device_wire()

    def _migrate_device_wire(self, live: "_LiveRequest", decode_name: str,
                             srid: str, tokens: List[int], k, v,
                             wire) -> Optional[Response]:
        """PD migration over the PJRT transfer server: stage the exported
        device block, hand the decode side a pull ticket inside the
        ``/kv/import`` meta (no KV bytes on the HTTP body), and relay its
        response. Returns None to tell the caller to retry over the host
        shuttle — the staged block stays valid as device arrays."""
        t0 = time.monotonic()
        try:
            uuid = wire.stage(k, v)
        except Exception as e:  # noqa: BLE001 — wire broke post-probe
            logger.warning("kv device-wire staging failed (%s)", e)
            return None
        meta = {
            "service_request_id": srid,
            "model": live.model,
            "tokens": tokens,
            "prompt_len": len(live.req.token_ids),
            "rope_delta": live.req.rope_delta,
            "mm": _mm_meta(live.req),
            "sampling": live.sampling.to_json(),
            "stream": live.stream,
            "transfer": {"addr": wire.address, "uuid": uuid,
                         "shape": list(k.shape), "dtype": str(k.dtype)},
        }
        from xllm_service_tpu.service.httpd import http_stream
        head = b""
        chunks = iter(())
        try:
            chunks = http_stream(
                "POST", decode_name, "/kv/import",
                raw=json.dumps(stamp(meta)).encode("utf-8") + b"\n",
                timeout=self.opts.request_timeout_s)
            head = next(chunks, b"")
        except Exception as e:  # noqa: BLE001 — peer unreachable
            logger.warning("kv device-wire handshake to %s failed (%s)",
                           decode_name, e)
            # Connection refused = the ticket never arrived, safe to
            # drain; anything later (e.g. a read timeout) is ambiguous —
            # the peer may be mid-pull, so the block stays pinned.
            refused = isinstance(e, ConnectionRefusedError)
            wire.release(uuid, drain=refused, leaked=not refused)
            return None
        parsed = self._parse_import_head(head)
        err = (parsed or {}).get("error") or {}
        if err.get("code") == 424:
            msg = str(err.get("message", ""))
            if msg.startswith("wire-unsupported:"):
                # The peer's backend can never pull device transfers:
                # remember and stop offering (so this logs once a peer).
                self._wire_refused.add(decode_name)
                logger.warning("decode %s cannot pull device wire; host "
                               "shuttle from now on", decode_name)
            wire.release(uuid, drain=not msg.startswith("wire-pull:"),
                         leaked=msg.startswith("wire-pull:"))
            return None
        code = err.get("code")
        if code == 400:
            # Meta rejected before pull_block ever ran (bad/missing meta
            # line): the staged block is provably untouched — drain it.
            # A plain release here would leave it pinned server-side and
            # uncounted (round-3 advisor finding).
            wire.release(uuid, drain=True)
        elif code is None or code == 503:
            # Success (accepted / SSE stream) or post-pull refusal (503
            # no-capacity / model-asleep happens after the peer's pull
            # completed): the staged block was consumed.
            wire.release(uuid)
        else:
            # Unknown failure (e.g. a 500 mid-handler): pull state is
            # ambiguous — keep the pinned-block metric truthful.
            wire.release(uuid, leaked=True)
        if code is None:
            self.kv_migration_bytes += 2 * int(k.nbytes)
            self.kv_migration_seconds += time.monotonic() - t0
            self.kv_migration_device_wire += 1
        return self._finish_migration(
            live, decode_name, tokens, head, chunks, parsed,
            lambda: (np.asarray(jax.device_get(k)),
                     np.asarray(jax.device_get(v))))

    def _migrate_direct(self, live: "_LiveRequest", rt: ModelRuntime,
                        srid: str, peer: "Worker") -> Response:
        """PD migration to a decode worker in THIS process: the exported
        page block stays a device array end to end (export_held(device=
        True) → peer adopt → donated scatter) — no host copy, no wire.
        The data plane the reference runs over NCCL stays on-device here."""
        with self._engine_lock:
            exported = rt.engine.export_held(srid, device=True)
        if exported is None:
            return Response.error(500, "prefill KV export failed")
        tokens, k, v = exported
        t0 = time.monotonic()
        meta = {
            "service_request_id": srid,
            "model": live.model,
            "tokens": tokens,
            "prompt_len": len(live.req.token_ids),
            "rope_delta": live.req.rope_delta,
            "mm": _mm_meta(live.req),
            "sampling": live.sampling.to_json(),
            "stream": live.stream,
        }
        ok, dlive, first_out, drt = peer.adopt_migrated(meta, k, v)
        if not ok:
            if dlive is not None and dlive.stream_to_service:
                # Idempotent duplicate: the earlier adoption is live and
                # streaming to the service already.
                return Response.json({"status": "accepted",
                                      "service_request_id": srid})
            # Nothing actually transferred — don't pollute the gbps gauge.
            logger.warning("direct kv migration to %s refused; decoding "
                           "locally", peer.name)
            k = np.asarray(jax.device_get(k))
            v = np.asarray(jax.device_get(v))
            return self._local_decode_fallback(live, tokens, k, v)
        try:
            jax.block_until_ready(drt.engine.kv[0])
        except Exception:  # noqa: BLE001 — engine may be stepping
            pass
        self.kv_migration_bytes += 2 * int(k.nbytes)
        self.kv_migration_seconds += time.monotonic() - t0
        self.kv_migration_direct += 1
        if dlive.stream_to_service:
            # Topology 2 — judged by the DECODE side's actual mode (its
            # engine loop pushes to the service): a topology mismatch
            # between co-hosted workers must not strand outputs in a
            # queue nobody drains.
            return Response.json({"status": "accepted",
                                  "service_request_id": srid})
        # Relay topology: consume the peer's live queue in-process (the
        # wire path would re-assemble the same outputs from its SSE).
        if live.stream:
            asm = (ChatStreamAssembler if live.is_chat
                   else CompletionStreamAssembler)(
                srid, live.model, live.include_usage)

            def gen() -> Iterator[bytes]:
                try:
                    for frame in asm.on_output(first_out):
                        yield frame
                    for ro in peer._iter_live_outputs(drt, dlive, srid):
                        for frame in asm.on_output(ro):
                            yield frame
                finally:
                    peer._finalize_live(dlive)
            # on_close backstop: the gen-level finally cannot run if the
            # body is never started.
            return self._tracked_relay(
                gen(), lambda: peer._finalize_live(dlive))
        coll = ResponseCollector(srid, live.model, live.is_chat)
        coll.add(first_out)
        for ro in peer._iter_live_outputs(drt, dlive, srid):
            coll.add(ro)
        return Response.json(coll.body())

    def _tracked_relay(self, stream: Iterator[bytes],
                       *cleanups) -> Response:
        """SSE response for a proxied (PD relay) stream, counted toward
        the drain busy-check: incremented EAGERLY (while the handler
        still holds _inflight_parse, closing the handoff window) and
        decremented exactly once via the response's guaranteed cleanup
        (generator finallies never run for never-started bodies)."""
        with self._live_lock:
            self._relay_streams += 1

        def dec() -> None:
            with self._live_lock:
                self._relay_streams -= 1
        return self._stream_response(stream, dec, *cleanups)

    def _topology2(self) -> bool:
        return self._decode_to_service and bool(self.service_addr)

    def _push_outputs_to_service(self, outs: List[RequestOutput]) -> None:
        if not outs or self._dead:
            return
        try:
            # "from" = sender identity: the scheduler's exactly-once
            # guard drops straggler pushes from a deposed instance
            # after a mid-stream recovery retargets the request.
            status, _ = http_json(
                "POST", self.service_addr, "/rpc/generations",
                stamp({"outputs": [o.to_json() for o in outs],
                       "from": self.name}),
                timeout=30.0)
            if status != 200:
                logger.warning("generations push refused: %d (%d outputs "
                               "lost)", status, len(outs))
        except Exception as e:  # noqa: BLE001
            logger.warning("generations push failed: %s", e)

    def _respond_outputs(self, live: "_LiveRequest",
                         outs: List[RequestOutput]) -> Response:
        if live.stream:
            asm = (ChatStreamAssembler if live.is_chat
                   else CompletionStreamAssembler)(
                live.service_request_id, live.model, live.include_usage,
                emit_token_ids=live.emit_token_ids)
            frames: List[bytes] = []
            for ro in outs:
                frames.extend(asm.on_output(ro))
            return Response.sse(iter(frames))
        coll = ResponseCollector(live.service_request_id, live.model,
                                 live.is_chat)
        for ro in outs:
            coll.add(ro)
        return Response.json(coll.body())

    def _relay_decode_stream(self, live: "_LiveRequest", head: bytes,
                             chunks) -> Response:
        from xllm_service_tpu.service.httpd import iter_sse_events

        def all_chunks():
            if head:
                yield head
            for c in chunks:
                yield c

        if live.stream:
            asm = (ChatStreamAssembler if live.is_chat
                   else CompletionStreamAssembler)(
                live.service_request_id, live.model, live.include_usage,
                emit_token_ids=live.emit_token_ids)

            def gen() -> Iterator[bytes]:
                for payload in iter_sse_events(all_chunks()):
                    if payload == "[DONE]":
                        return
                    ro = RequestOutput.from_json(json.loads(payload))
                    for frame in asm.on_output(ro):
                        yield frame
            return self._tracked_relay(gen())
        outs = []
        for payload in iter_sse_events(all_chunks()):
            if payload == "[DONE]":
                break
            outs.append(RequestOutput.from_json(json.loads(payload)))
        return self._respond_outputs(live, outs)

    def _local_decode_fallback(self, live: "_LiveRequest",
                               tokens: List[int], k, v) -> Response:
        """Decode here when the decode instance refused the migration."""
        rt = self.runtimes.get(live.model) or self.primary_runtime()
        srid = live.service_request_id
        ereq = EngineRequest(
            request_id=srid, token_ids=list(live.req.token_ids),
            sampling=live.sampling,
            eos_token_ids=live.req.eos_token_ids)
        new_live = _LiveRequest(
            ereq, rt.tokenizer, srid, live.model,
            live.is_chat, live.stream, live.include_usage,
            stream_to_service=self._topology2(),
            stops=live.sampling.stop)
        new_live.sampling = live.sampling
        new_live.prompt_tokens = len(live.req.token_ids)
        new_live.emit_token_ids = live.emit_token_ids
        # The migrated first token reaches the client via first_out below,
        # outside _to_request_output — count it here.
        new_live.choices[0].completion_tokens = 1
        self._writer_adopt(new_live)
        first_out = RequestOutput(
            request_id=srid, service_request_id=srid,
            outputs=[SequenceOutput(
                index=0, text=new_live.decoder.feed([tokens[-1]]),
                token_ids=[tokens[-1]])])
        with self._live_lock:
            self._live[srid] = new_live
            self._live_srid[srid] = new_live
        with self._engine_lock:
            ok = rt.engine.import_sequence(ereq, tokens, k, v)
            if ok and new_live.stream_to_service:
                self._service_push_buffer.append(first_out)
        if not ok:
            self._drop_live(srid)
            return Response.error(503, "no local capacity for fallback")
        self._work_event.set()
        if new_live.stream_to_service:
            return Response.json({"status": "accepted",
                                  "service_request_id": srid})
        if live.stream:
            return self._sse_response(new_live, initial=[first_out])
        return self._collect_full(new_live, initial=[first_out])

    def adopt_migrated(self, meta: Dict[str, Any], k, v):
        """Decode-side adoption of a migrated sequence (shared by the HTTP
        wire handler and the same-process device-to-device path — ``k``/``v``
        may be host numpy or device arrays).

        Returns (ok, live, first_out, runtime); runtime is None when the
        target model is asleep."""
        # Counted like every other work-accepting entry point: the
        # in-process PD handoff calls this directly (no HTTP wrapper),
        # and the window between the refusal check and _live_srid
        # registration must be covered or a concurrent drain declares
        # idle, stops the engine loop, and strands the adopted request.
        with self._live_lock:
            self._inflight_parse += 1
        try:
            return self._adopt_migrated_inner(meta, k, v)
        finally:
            with self._live_lock:
                self._inflight_parse -= 1

    def _adopt_migrated_inner(self, meta: Dict[str, Any], k, v):
        if self._refuse_new:
            # Same refusal as the /kv/import wire path — the prefill
            # side falls back to local decode.
            return False, None, None, None
        model = meta.get("model", self.opts.model)
        rt = self.runtimes.get(model) or self.primary_runtime()
        if rt.engine is None:
            return False, None, None, None
        tokens = list(meta["tokens"])
        srid = meta["service_request_id"]
        sampling = SamplingParams.from_json(meta.get("sampling"))
        prompt = tokens[:int(meta.get("prompt_len", len(tokens) - 1))]
        mm = meta.get("mm") or None
        mm_embeds = mm_positions = mm_rope_pos = None
        if mm:
            # Multimodal state must survive migration: preemption on THIS
            # worker re-prefills from it (wrong rope ids / placeholder
            # embeddings otherwise), and its presence keeps the migrated
            # sequence out of the content-addressed prefix cache (same
            # text + different image must never share KV).
            from xllm_service_tpu.runtime.multimodal import (
                embeds_from_wire)
            mm_embeds = embeds_from_wire(mm["embeds"])
            mm_positions = list(mm.get("positions") or [])
            if mm.get("rope_pos") is not None:
                mm_rope_pos = np.asarray(mm["rope_pos"], np.int32)
        ereq = EngineRequest(
            request_id=srid, token_ids=prompt, sampling=sampling,
            eos_token_ids=rt.tokenizer.eos_token_ids,
            mm_embeds=mm_embeds, mm_positions=mm_positions,
            mm_rope_pos=mm_rope_pos,
            rope_delta=int(meta.get("rope_delta", 0)))
        live = _LiveRequest(
            ereq, rt.tokenizer, srid, model,
            is_chat=False, stream=bool(meta.get("stream")),
            include_usage=False,
            stream_to_service=self._decode_to_service
            and bool(self.service_addr),
            stops=sampling.stop)
        live.sampling = sampling
        live.prompt_tokens = len(prompt)
        live.choices[0].completion_tokens = 1   # migrated first token

        with self._live_lock:
            if srid in self._live_srid:
                # A transport ambiguity (e.g. prefill-side timeout, then
                # host-shuttle retry) must not adopt the same sequence
                # twice — two running slots would stream duplicate
                # outputs for one request. The existing live is returned
                # so callers can answer idempotently when it is already
                # streaming to the service (a 503 would push the prefill
                # side into a competing local decode).
                logger.warning("duplicate kv import for %s refused", srid)
                return False, self._live_srid[srid], None, rt
            self._live[srid] = live
            self._live_srid[srid] = live
        first_out = RequestOutput(
            request_id=srid, service_request_id=srid,
            outputs=[SequenceOutput(
                index=0, text=live.decoder.feed([tokens[-1]]),
                token_ids=[tokens[-1]])])
        with self._engine_lock:
            ok = rt.engine.import_sequence(ereq, tokens, k, v)
            if ok and live.stream_to_service:
                # Topology 2: buffering under the engine lock puts the
                # first token ahead of any later step output; the engine
                # loop drains the buffer in order, off this lock.
                self._service_push_buffer.append(first_out)
        if not ok:
            self._drop_live(srid)
            return False, None, None, rt
        self._work_event.set()
        # Decode-side span: a migrated sequence is received+scheduled in
        # one adoption; merged at the service alongside the prefill
        # worker's stages (distinct heartbeat source).
        self.spans.record(srid, "received", plane="worker")
        self.spans.record(srid, "scheduled", plane="worker")
        return True, live, first_out, rt

    def _serve_kv_import(self, req: Request) -> Response:
        """Decode-side adoption of a migrated sequence (HTTP wire path).
        The prefill side falls back to local decode on a 503."""
        return self._guarded(self._serve_kv_import_inner, req)

    def _serve_kv_import_inner(self, req: Request) -> Response:
        # Two body forms: meta-line + raw KV bytes (monolithic shuttle),
        # or a bare JSON object (device-wire ticket / chunked manifest —
        # no bytes on this request).
        nl = req.body.find(b"\n")
        head = req.body[:nl] if nl >= 0 else req.body
        try:
            meta = json.loads(head.decode("utf-8"))
        except (ValueError, UnicodeDecodeError) as e:
            return Response.error(400, f"bad meta: {e}")
        check_version(meta, "kv_import")
        chunked = meta.get("chunked")
        tr = meta.get("transfer")
        if chunked is not None:
            # Pipelined shuttle: the KV arrived earlier as /kv/chunk
            # parts already device_put; assemble them. A 424 with the
            # chunks-missing prefix tells the prefill side a monolithic
            # retry is worthwhile (vs a capacity refusal, which is not).
            got = self._pop_staged_chunks(meta["service_request_id"],
                                          int(chunked.get("total", 0)))
            if got is None:
                return Response.error(
                    424, "chunks-missing: staging incomplete or expired")
            k, v = got
        elif tr is not None:
            # Device wire: the body carries a pull ticket, not bytes —
            # fetch the staged block device-to-device from the prefill
            # worker's transfer server. A 424 tells the prefill side to
            # fall back to the raw-bytes shuttle; its message prefix
            # says what to do with the staged block (see kv_wire docs).
            from xllm_service_tpu.runtime.kv_wire import (
                WireNoPull, WireUnsupported, pull_block)
            try:
                k, v = pull_block(tr)
            except WireUnsupported as e:
                return Response.error(424, f"wire-unsupported: {e}")
            except WireNoPull as e:
                return Response.error(424, f"wire-nopull: {e}")
            except Exception as e:  # noqa: BLE001 — failed mid-pull
                return Response.error(424, f"wire-pull: {e}")
        else:
            try:
                k, v = _decode_kv_blob(meta, req.body[nl + 1:])
            except ValueError as e:
                return Response.error(400, str(e))

        ok, live, first_out, rt = self.adopt_migrated(meta, k, v)
        if rt is None:
            return Response.error(503,
                                  f"model {meta.get('model')!r} asleep")
        if not ok:
            if live is not None and live.stream_to_service:
                # Duplicate import whose original adoption is live and
                # already streaming to the service: idempotent accept —
                # that adoption serves the request (round-3 advisor
                # finding: a 503 here spawned a competing local decode,
                # one request → two output streams).
                return Response.json({
                    "status": "accepted",
                    "service_request_id": meta["service_request_id"]})
            return Response.error(503, "no capacity on decode instance")
        srid = meta["service_request_id"]
        if live.stream_to_service:
            return Response.json({"status": "accepted",
                                  "service_request_id": srid})

        # Relay topology: stream raw RequestOutput frames back to the
        # prefill worker on this response.
        def gen() -> Iterator[bytes]:
            try:
                yield sse_frame(first_out.to_json())
                for ro in self._iter_live_outputs(rt, live, srid):
                    yield sse_frame(ro.to_json())
                    if ro.finished:
                        yield SSE_DONE
                        return
            finally:
                self._finalize_live(live)
        # on_close backstop for the never-started-body case.
        return self._stream_response(
            gen(), lambda: self._finalize_live(live))

    def _iter_live_outputs(self, rt: ModelRuntime, live: "_LiveRequest",
                           srid: str) -> Iterator[RequestOutput]:
        """Drain a live request's engine outputs as RequestOutputs,
        cancelling on timeout. Shared by the wire and same-process
        migration response paths.

        Cleanup sits in a finally: consumers abandon this generator at
        ``yield`` (the wire relay returns after the finished frame, so a
        bare post-yield finalize would be skipped via GeneratorExit) —
        without it the srid entry leaks and drain never sees idle."""
        try:
            while True:
                try:
                    out = live.q.get(timeout=self.opts.request_timeout_s)
                except queue.Empty:
                    with self._engine_lock:
                        if rt.engine is not None:
                            rt.engine.cancel(srid)
                    self._drop_live(srid)
                    return
                if out is _ABORT:
                    raise RuntimeError("worker died (failpoint)")
                if isinstance(out, _EngineFault):
                    # Blamed by the step fault boundary. This generator
                    # yields RequestOutputs, which carry no error: break
                    # the stream with the verdict in the log, not with
                    # an AttributeError further down.
                    raise RuntimeError(f"engine_fault: {out.verdict}")
                if out is None:
                    return
                done = False
                for ro in self._process_step_output(live, out):
                    yield ro
                    done = done or ro.finished
                if done:
                    return
        finally:
            self._finalize_live(live)

    # ------------------------------------------------------------------
    # Cross-worker cached-block fetch (docs/KV_CACHE.md). A worker
    # placed on a request whose prefix some OTHER worker holds pulls
    # those KV blocks from the holder and starts prefill at the first
    # uncached token. Transport mirrors the PD handoff: the PJRT device
    # wire (kv_wire.stage/pull_block) when both sides can serve it, a
    # raw meta-line + K/V-bytes response otherwise. Every failure falls
    # back to prefilling from token zero — the fetch is an optimization,
    # never a new failure mode.
    # ------------------------------------------------------------------
    def _serve_kv_blocks(self, req: Request) -> Response:
        return self._guarded(self._serve_kv_blocks_inner, req)

    def _serve_kv_blocks_inner(self, req: Request) -> Response:
        """Holder side: gather a contiguous digest run out of the pool
        (and/or the spill tier) and hand it to the requester — staged on
        the device wire ({"status": "staged", "transfer": ...}), or raw
        octet-stream (meta line + K bytes + V bytes)."""
        try:
            body = req.json()
        except Exception:  # noqa: BLE001 — the 400 carries the
            # verdict straight back to the caller
            return Response.error(400, "invalid JSON body")
        check_version(body, "kv_blocks")
        model = body.get("model", self.opts.model)
        # STRICT model resolution — no primary fallback: digests hash
        # token ids only, so a wrong-model engine could hold the
        # requested digests and serve another model's KV as a 200.
        rt = self.runtimes.get(model)
        if rt is None:
            return Response.error(404, f"model {model!r} not served "
                                       f"here")
        if rt.engine is None:
            return Response.error(503, f"model {model!r} asleep")
        try:
            hashes = [bytes.fromhex(h) for h in body.get("hashes", [])]
        except (TypeError, ValueError):
            return Response.error(400, "bad digest hex")
        if not hashes:
            return Response.error(400, "no hashes requested")
        wire = None
        if body.get("wire") and self.opts.pd_device_wire:
            from xllm_service_tpu.runtime.kv_wire import get_device_wire
            wire = get_device_wire()
        with self._engine_lock:
            exported = rt.engine.export_blocks(
                hashes, device=wire is not None)
        if exported is None:
            # Evicted since the cluster index last heard from us —
            # the requester recomputes; the next heartbeat's removals
            # catch the index up.
            return Response.error(404, "blocks no longer held")
        n, k, v = exported
        if wire is not None and not isinstance(k, np.ndarray):
            try:
                uuid = wire.stage(k, v)
            except Exception as e:  # noqa: BLE001 — wire broke post-probe
                logger.warning("kv block staging failed (%s); serving "
                               "raw", e)
            else:
                with self._kv_fetch_mu:
                    self._kv_fetch_staged[uuid] = (time.monotonic(),
                                                   wire)
                return Response.json({
                    "status": "staged", "blocks": n,
                    "transfer": {"addr": wire.address, "uuid": uuid,
                                 "shape": list(k.shape),
                                 "dtype": str(k.dtype)}})
        if not isinstance(k, np.ndarray):
            k = np.asarray(jax.device_get(k))
            v = np.asarray(jax.device_get(v))
        from xllm_service_tpu.runtime.kv_cache import encode_kv_block
        payload = encode_kv_block(k, v, extra=stamp({"blocks": n}))
        return Response(body=payload,
                        content_type="application/octet-stream")

    def _serve_kv_blocks_done(self, req: Request) -> Response:
        """Requester's pull acknowledgment: release the staged wire
        ticket (drain on a provably-untouched block, count a leak on an
        ambiguous one — kv_wire release contract)."""
        try:
            body = req.json()
            uuid = int(body.get("uuid"))
        except Exception:  # noqa: BLE001 — bad JSON / missing uuid
            return Response.error(400, "invalid body")
        outcome = body.get("outcome", "pulled")
        with self._kv_fetch_mu:
            entry = self._kv_fetch_staged.pop(uuid, None)
        if entry is None:
            return Response.json({"ok": True, "known": False})
        _, wire = entry
        if outcome == "pulled":
            wire.release(uuid)
        elif outcome == "nopull":
            wire.release(uuid, drain=True)
        else:
            wire.release(uuid, leaked=True)
        return Response.json({"ok": True, "known": True})

    def _sweep_kv_fetch_staged(self, ttl: float = 60.0) -> None:
        """Heartbeat-cadence TTL sweep of wire tickets whose requester
        never acknowledged (died mid-pull): transfer state unknown, so
        the block counts as leaked (kv_wire release contract)."""
        now = time.monotonic()
        with self._kv_fetch_mu:
            stale = [(u, e) for u, e in self._kv_fetch_staged.items()
                     if now - e[0] > ttl]
            for u, _ in stale:
                del self._kv_fetch_staged[u]
        for u, (_, wire) in stale:
            wire.release(u, leaked=True)

    def _maybe_fetch_blocks(self, rt: ModelRuntime,
                            token_ids: List[int],
                            kvf: Dict[str, Any]) -> None:
        """Requester side: execute the scheduler's Routing.kv_fetch plan
        before prefill admission. Pulls the planned leading blocks from
        the holder, adopts them content-addressed into the local pool,
        and lets the normal admit path hit them like any local prefix.
        Best-effort end to end: ANY failure (holder refusal, transport,
        layout mismatch, armed ``worker.fail_kv_fetch``) degrades to
        prefilling from token zero."""
        eng = rt.engine
        if eng is None or not eng.prefix_cache.enable:
            return
        holder = kvf.get("holder") or ""
        holder_addr = kvf.get("holder_addr") or holder
        try:
            end = int(kvf.get("blocks", 0))
            bs = int(kvf.get("block_size", 0))
        except (TypeError, ValueError):
            return
        if not holder_addr or holder == self.name or end <= 0:
            return
        if bs != self.engine_cfg.page_size:
            # Plan priced on a different block granularity than this
            # engine's pages — adopted blocks would be mis-keyed.
            logger.warning("kv fetch plan block_size=%d != engine "
                           "page_size=%d; recomputing", bs,
                           self.engine_cfg.page_size)
            return
        hashes = eng.prefix_cache.block_hashes(token_ids)
        end = min(end, len(hashes))
        with self._engine_lock:
            start = 0
            while start < end and (
                    eng.prefix_cache.page_of(hashes[start]) is not None
                    or (eng.host_tier is not None
                        and hashes[start] in eng.host_tier)):
                start += 1
        if start >= end:
            return              # local tiers already cover the plan
        self.kv_fetch_attempts += 1
        if self.failpoints.fire("worker.fail_kv_fetch") is not None:
            self.kv_fetch_failures += 1
            logger.warning("failpoint worker.fail_kv_fetch: recomputing "
                           "%d planned blocks", end - start)
            return
        from xllm_service_tpu.runtime.kv_wire import (
            WireNoPull, WireUnsupported, get_device_wire, pull_block)
        can_pull = bool(self.opts.pd_device_wire
                        and get_device_wire() is not None)
        from xllm_service_tpu.service.httpd import http_stream_status
        # The fetch is an optimization: it must never stall TTFT behind
        # a hung/partitioned holder for anything like the full request
        # timeout — recompute is always milliseconds away. Bounded by
        # its own short deadline.
        fetch_timeout = self._kv_fetch_timeout_s
        t0 = time.monotonic()
        try:
            status, body_iter = http_stream_status(
                "POST", holder_addr, "/kv/blocks",
                obj=stamp({"model": rt.model, "wire": can_pull,
                           "hashes": [h.hex()
                                      for h in hashes[start:end]]}),
                timeout=fetch_timeout)
            raw = b"".join(body_iter)
        except Exception as e:  # noqa: BLE001 — holder unreachable
            self.kv_fetch_failures += 1
            logger.warning("kv block fetch from %s failed (%s); "
                           "recomputing", holder_addr, e)
            return
        if status != 200:
            self.kv_fetch_failures += 1
            logger.info("kv block fetch refused by %s (HTTP %d); "
                        "recomputing", holder_addr, status)
            return
        k = v = None
        n = 0
        if raw.startswith(b"{") and b"\n" not in raw:
            # JSON verdict: a staged wire ticket.
            try:
                head = json.loads(raw.decode("utf-8"))
            except (ValueError, UnicodeDecodeError):
                head = {}
            tr = head.get("transfer")
            if head.get("status") != "staged" or not tr:
                self.kv_fetch_failures += 1
                return
            n = int(head.get("blocks", 0))
            outcome = "pulled"
            try:
                k, v = pull_block(tr)
            except (WireUnsupported, WireNoPull):
                outcome = "nopull"
            except Exception:  # noqa: BLE001 — failed mid-pull
                outcome = "error"
            try:
                http_json("POST", holder_addr, "/kv/blocks_done",
                          {"uuid": tr.get("uuid"), "outcome": outcome},
                          timeout=10.0)
            except Exception:  # noqa: BLE001 — holder TTL-sweeps it
                pass
            if k is None:
                self.kv_fetch_failures += 1
                logger.info("kv block wire pull from %s failed (%s); "
                            "recomputing", holder_addr, outcome)
                return
        else:
            nl = raw.find(b"\n")
            if nl < 0:
                self.kv_fetch_failures += 1
                return
            try:
                meta = json.loads(raw[:nl].decode("utf-8"))
                n = int(meta.get("blocks", 0))
                k, v = _decode_kv_blob(meta, raw[nl + 1:])
            except (ValueError, UnicodeDecodeError) as e:
                self.kv_fetch_failures += 1
                logger.warning("bad kv block payload from %s: %s",
                               holder_addr, e)
                return
        with self._engine_lock:
            adopted = eng.adopt_blocks(token_ids, start, k, v)
        if adopted:
            self.kv_fetch_bytes += 2 * int(k.nbytes)
            logger.info("adopted %d cached blocks from %s in %.1f ms",
                        adopted, holder_addr,
                        1e3 * (time.monotonic() - t0))
        else:
            self.kv_fetch_failures += 1

    # ------------------------------------------------------------------
    # Heartbeats
    # ------------------------------------------------------------------
    def _fetch_service_config(self) -> bool:
        """Learn decode-response-to-service mode from the service's config
        (GetConfig, rpc_service/service.cpp:215-223). Re-run after every
        retarget — the takeover master may run a different topology.
        Returns True only when the fetched config still belongs to the
        CURRENT target: a retarget that lands mid-fetch must not let the
        old master's topology answer clear the stale flag."""
        addr = self.service_addr
        if not addr:
            return False
        try:
            status, cfg = http_json("GET", addr, "/rpc/config", timeout=5.0)
        except Exception as e:
            # Transient by design (the hb loop re-tries via the stale
            # flag) — but debug-visible, not silent.
            logger.debug("service config fetch from %s failed: %s",
                         addr, e)
            return False
        if status == 200 and cfg is not None and addr == self.service_addr:
            self._decode_to_service = bool(
                cfg.get("enable_decode_response_to_service"))
            return True
        return False

    def _heartbeat_loop(self) -> None:
        self._refresh_service_config()
        hb_failures = 0
        next_hb = 0.0
        while not self._stop.wait(self.opts.heartbeat_interval_s):
            # Injected thread crash, deliberately OUTSIDE the try below:
            # proves the supervised-restart path end to end (the spawn
            # handler must log + count + emit thread_crashed, then
            # restart this loop with backoff — docs/ROBUSTNESS.md).
            if self.failpoints.fire("worker.crash_heartbeat") is not None:
                raise RuntimeError(
                    "injected heartbeat-loop crash "
                    "(failpoint worker.crash_heartbeat)")
            try:
                # Periodic sweep of orphaned chunked-shuttle staging —
                # lazy eviction alone never fires on an idle decode
                # worker, pinning a dead prefill's device KV forever.
                with self._kv_chunk_mu:
                    self._evict_stale_chunks_locked(time.monotonic())
                self._sweep_kv_fetch_staged()
                self._sweep_encode_staged()
                if self.failpoints.fire(
                        "worker.drop_heartbeats") is not None:
                    # Simulated crash/partition: no store keepalive, no
                    # master beat — the lease expires exactly as if the
                    # process were gone.
                    continue
                # Store heal (guard callback set the flag): re-register
                # BEFORE the keepalive check so the keepalive below
                # runs against the fresh lease instead of double-
                # registering off its own False.
                if self._heal_pending.is_set():
                    self._heal_pending.clear()
                    try:
                        self._register()
                        logger.info("store healed: lease + registration "
                                    "re-established for %s", self.name)
                    except Exception as e:  # noqa: BLE001 — store
                        # flapping; retry next tick
                        self._heal_pending.set()
                        logger.warning("post-heal re-registration "
                                       "failed: %s", e)
                    else:
                        if self.opts.service_addr:
                            self._adopt_advertised_addr()
                # Keepalive isolated from beat accounting: a store
                # EXCEPTION is a store outage — the worker keeps
                # serving and keeps beating the master directly (the
                # degraded-mode liveness signal) instead of
                # self-fencing; the guard re-registers us on heal. A
                # clean False means the store is reachable and the
                # lease is dead (it expired during an outage shorter
                # than detection): re-establish it NOW, idempotently.
                lease_id = self._lease_id
                if lease_id is not None:
                    try:
                        lease_alive = self.store.lease_keepalive(lease_id)
                    except StoreOutageError as e:
                        logger.debug("store keepalive unreachable "
                                     "(outage?): %s", e)
                        lease_alive = True   # frozen — not a beat failure
                    if not lease_alive and lease_id == self._lease_id:
                        try:
                            self._register()
                            logger.warning(
                                "lease %d expired under a live worker; "
                                "re-registered with a fresh lease",
                                lease_id)
                        except Exception as e:  # noqa: BLE001 — store
                            # flapping; the next tick (or the guard's
                            # heal callback) retries
                            logger.warning("lease re-establishment "
                                           "failed: %s", e)
                if self._service_config_stale:
                    self._refresh_service_config()
                # The loop keeps ticking at the base cadence (the store
                # keepalive above MUST — a down master is not a dead
                # worker), but beat SENDS back off exponentially with
                # full jitter so a restarting master isn't
                # thundering-herded by its whole fleet at once. The
                # gate must not skip the advertised-address re-read
                # below: a NEW master's advertisement has to be adopted
                # at tick cadence, not at the backoff cadence.
                if time.monotonic() >= next_hb:
                    if self._send_heartbeat():
                        hb_failures = 0
                        next_hb = 0.0
                    else:
                        hb_failures += 1
                        next_hb = time.monotonic() + \
                            self._hb_backoff.delay(hb_failures - 1)
            except Exception as e:  # noqa: BLE001
                hb_failures += 1
                next_hb = time.monotonic() + \
                    self._hb_backoff.delay(hb_failures - 1)
                logger.warning("heartbeat failed: %s", e)
            if hb_failures >= 2 and self.opts.service_addr:
                # The master may have moved while we missed the watch
                # event (boot race, watch compaction): re-read the
                # advertisement directly.
                if self._adopt_advertised_addr():
                    hb_failures = 0
                    next_hb = 0.0

    def _send_heartbeat(self) -> bool:
        """→ True when the service acknowledged (HTTP 200) — the drain
        handshake needs that distinction; a 500 must not count."""
        if not self.service_addr:
            return False
        with self._hb_lock:
            return self._send_heartbeat_locked()

    def _engine_load(self, rt: ModelRuntime) -> LoadMetrics:
        """THE single assembly point of ``engine.load_metrics()`` — the
        heartbeat, ``/metrics``, and the per-step registry flush all go
        through here (two hand-assembled copies used to live at the
        heartbeat and /metrics sites and could drift). Mirrors every
        load key into the registry as ``xllm_worker_<key>{model=...}``
        and returns the heartbeat's ``LoadMetrics``."""
        eng = rt.engine
        if eng is None:
            return LoadMetrics()
        lm = eng.load_metrics()
        for k, v in lm.items():
            self.obs.gauge(f"xllm_worker_{k}",
                           labelnames=("model",)).set(v, model=rt.model)
        return LoadMetrics(
            waiting_requests=lm["waiting_requests"],
            running_requests=lm["running_requests"],
            kv_cache_usage=lm["kv_cache_usage"],
            num_preemptions=lm["num_preemptions"],
            moe_dropped_tokens=lm.get("moe_dropped_tokens", 0),
            engine_alive=int(self._engine_loop_alive))

    def _recent_step_p99(self, rt: ModelRuntime):
        """p99 of ``xllm_worker_step_ms`` over the samples recorded
        since the last DELIVERED heartbeat, merged across
        prefill+decode — computed from the same registry buckets
        /metrics exports (the delta of cumulative bucket counts is
        itself a histogram). Returns ``(p99, pending_baseline)``; the
        caller commits the baseline only after the service acks the
        beat, so a failed send folds its interval into the next one
        instead of silently dropping a regression window. p99 0.0 = no
        steps ran in the interval (no signal)."""
        h = self.obs.histogram(
            "xllm_worker_step_ms", "wall time of one engine step",
            labelnames=("model", "phase"))
        pending: Dict[Any, List[Any]] = dict(self._hb_step_cum)
        merged: Optional[List[Any]] = None
        for phase in ("prefill", "decode", "mixed"):
            cur = h.cumulative(model=rt.model, phase=phase)
            if cur is None:
                continue
            prev = self._hb_step_cum.get((rt.model, phase))
            pending[(rt.model, phase)] = cur
            delta = cur if prev is None else \
                [(le, c - p) for (le, c), (_le, p) in zip(cur, prev)]
            merged = delta if merged is None else \
                [(le, a + b) for (le, a), (_le, b) in zip(merged, delta)]
        if not merged or merged[-1][1] <= 0:
            return 0.0, pending
        return quantile_from_buckets(merged, 0.99) or 0.0, pending

    def _send_heartbeat_locked(self) -> bool:
        rt = self.primary_runtime()
        load = LoadMetrics()
        stored: List[str] = []
        removed: List[str] = []
        offloaded: List[str] = []
        offloaded_ssd: List[str] = []
        model_states = {
            m: (MODEL_DRAINING if self._draining else r.state)
            for m, r in self.runtimes.items()}
        cache_ev = None
        if rt.engine is not None:
            load = self._engine_load(rt)
            # The engine-side drain is a swap (concurrent appends land
            # in the old or the new event object, both retained); an
            # UNDELIVERED delta is kept in this worker-side buffer
            # (touched only under _hb_lock — the heartbeat must never
            # block on the engine lock, which is held for whole
            # compiles) and folded into the next beat's drain.
            cache_ev = rt.engine.drain_kvcache_event()
            if self._hb_cache_pending is not None:
                self._hb_cache_pending.merge(cache_ev)
                cache_ev = self._hb_cache_pending
                self._hb_cache_pending = None
            stored = [h.hex() for h in cache_ev.stored]
            removed = [h.hex() for h in cache_ev.removed]
            offloaded = [h.hex() for h in cache_ev.offloaded]
            offloaded_ssd = [h.hex() for h in cache_ev.offloaded_ssd]
        # Recent step-time p99 rides the existing latency payload so the
        # service watchdog can baseline per-instance step regressions;
        # the bucket baseline commits only on a delivered beat (below).
        self._latency.step_ms_p99, step_baseline = \
            self._recent_step_p99(rt)
        # Cost-model signals for the service's fetch-vs-recompute
        # planner (docs/KV_CACHE.md): measured prefill throughput and
        # measured KV-transfer bandwidth. 0.0 = no signal yet (the
        # planner falls back to XLLM_KV_FETCH_{TOKS,GBPS}).
        self._latency.prefill_tok_s = (
            self._prefill_tok_cum / self._prefill_s_cum
            if self._prefill_s_cum > 0 else 0.0)
        self._latency.kv_gbps = (
            self.kv_migration_bytes / self.kv_migration_seconds / 1e9
            if self.kv_migration_seconds > 0 else 0.0)
        # Prefill backlog (prompt tokens queued, not yet computed): the
        # SLO-aware policy's predicted-TTFT term consumes this so
        # admission staggers across workers instead of piling prompts
        # onto one already-deep queue (P/D-Serve backlog awareness).
        if rt.engine is not None:
            self._latency.waiting_prefill_tokens = \
                int(rt.engine.waiting_prefill_tokens())
        # Finished request spans ride the heartbeat to the service's
        # span ring (same correlation id); an undelivered batch is
        # requeued so the next beat retries it.
        # Step-record tail since the last DELIVERED beat (bounded; the
        # seq baseline commits only on an acked beat below, so an
        # undelivered tail is re-shipped — StepBooks dedupes on seq).
        # Built BEFORE the span drain: nothing may raise between the
        # drain and its requeue-protected try block.
        steps_tail: List[Dict[str, Any]] = []
        steps_seq = self._hb_steps_seq
        if self.steptrace.enabled:
            steps_tail = self.steptrace.tail(
                n=64, since_seq=self._hb_steps_seq)
            if steps_tail:
                steps_seq = int(steps_tail[-1].get("seq", steps_seq))
        span_batch = self.spans.drain_finished()
        # Encode-plane beat payload (docs/EPD.md): queue depth + step
        # latency feed the scheduler's cost-aware encode pick; the
        # embedding-cache digest delta feeds its hit estimator. Same
        # delivery contract as spans — an undelivered delta is requeued.
        with self._embed_mu:
            embed_stored = self._embed_stored_pending
            embed_removed = self._embed_removed_pending
            enc_ms = self._encode_recent_ms
            self._embed_stored_pending = []
            self._embed_removed_pending = []
            self._encode_recent_ms = []
        load.encode_queue_depth = self._encode_q.qsize()
        if enc_ms:
            self._latency.encode_ms = sum(enc_ms) / len(enc_ms)
            self._latency.encode_ms_samples = list(enc_ms)
        # EVERYTHING between the drain and a delivered beat sits inside
        # the try: a Heartbeat construction or serialization that
        # raises must requeue the drained batch exactly like a failed
        # send, or those finished spans silently vanish (xlint rule
        # resource-leak pins the drain→requeue pairing).
        try:
            hb = Heartbeat(
                name=self.name, instance_type=self.instance_type,
                load=load, latency=self._latency,
                cache_stored=stored, cache_removed=removed,
                cache_offloaded=offloaded,
                cache_offloaded_ssd=offloaded_ssd,
                model_states=model_states, spans=span_batch,
                embed_stored=embed_stored, embed_removed=embed_removed,
                steps=steps_tail)
            self._latency = LatencyMetrics()
            status, ack = http_json("POST", self.service_addr,
                                    "/rpc/heartbeat", stamp(hb.to_json()),
                                    timeout=10.0)
        except Exception:
            self.spans.requeue(span_batch)
            if cache_ev is not None and not cache_ev.empty:
                self._hb_cache_pending = cache_ev
            self._requeue_encode_hb(embed_stored, embed_removed, enc_ms)
            raise
        if status == 200 and isinstance(ack, dict):
            ack_epoch = int(ack.get("epoch", 0) or 0)
            if ack_epoch < self._master_epoch:
                # A deposed master is still answering on this address:
                # its ack is REJECTED (fenced epochs, docs/ROBUSTNESS.md)
                # and counts as a failed beat, so the backoff + the
                # advertised-address re-read retarget us to the real
                # master. Requeue the payload — delivery to a stale
                # master's books is not delivery.
                self.spans.requeue(span_batch)
                if cache_ev is not None and not cache_ev.empty:
                    self._hb_cache_pending = cache_ev
                self._requeue_encode_hb(embed_stored, embed_removed,
                                        enc_ms)
                logger.warning(
                    "rejected beat-ack from deposed master at %s "
                    "(epoch %d < acked %d)", self.service_addr,
                    ack_epoch, self._master_epoch)
                return False
            if ack_epoch > self._master_epoch:
                self._master_epoch = ack_epoch
        if status != 200:
            self.spans.requeue(span_batch)
            if cache_ev is not None and not cache_ev.empty:
                self._hb_cache_pending = cache_ev
            self._requeue_encode_hb(embed_stored, embed_removed, enc_ms)
        else:
            self._hb_step_cum = step_baseline
            self._hb_steps_seq = steps_seq
        return status == 200

    def _requeue_encode_hb(self, stored: List[str], removed: List[str],
                           ms: List[float]) -> None:
        """Fold an undelivered encode-plane beat payload back into the
        pending buffers (front, preserving delta order) so the next
        beat retries it — the service's digest set would silently drift
        from the cache otherwise."""
        if not (stored or removed or ms):
            return
        with self._embed_mu:
            self._embed_stored_pending[:0] = stored
            self._embed_removed_pending[:0] = removed
            self._encode_recent_ms[:0] = ms

    def heartbeat_once(self) -> None:
        """Test helper: one synchronous heartbeat."""
        self._send_heartbeat()


def main(argv: Optional[List[str]] = None) -> int:
    import argparse
    import signal

    # Before the first compilation (utils/jaxcache.py says where the
    # cache lives and who may move it). A sharded engine's programs and
    # whatever is compiled before an engine exists are then loaded, not
    # recompiled, by the next boot; a single-device engine switches the
    # cache off again for its pinned programs (Engine.__init__).
    from xllm_service_tpu.utils.jaxcache import enable_compile_cache
    enable_compile_cache()

    parser = argparse.ArgumentParser(
        description="xllm-service-tpu worker (TPU engine instance)")
    parser.add_argument("--host", default="127.0.0.1")
    parser.add_argument("--port", type=int, default=0)
    parser.add_argument("--instance-type", default="MIX",
                        choices=[t.value for t in InstanceType])
    parser.add_argument("--role", default="",
                        choices=["", "encode"],
                        help="'encode' = dedicated encode worker: the "
                             "vision tower is the only compiled graph, "
                             "no LM runtime is built (docs/EPD.md)")
    parser.add_argument("--service-addr", default="",
                        help="service RPC host:port for heartbeats")
    parser.add_argument("--store-addr", default="",
                        help="coordination store host:port "
                             "('' = private in-process store)")
    parser.add_argument("--model", default="tiny")
    parser.add_argument("--model-dir", default="")
    parser.add_argument("--heartbeat-interval-s", type=float, default=3.0)
    parser.add_argument("--enable-profiling", action="store_true")
    # 128 = the reference's block-size default AND half the decode-
    # attention grid cells of 64 (per-cell overhead is first-order at
    # large batch — docs/PERF_NOTES.md round 3).
    parser.add_argument("--page-size", type=int, default=128)
    # Must equal the service's --murmur-hash3-seed or this worker's
    # prefix-cache digests are quarantined at registration
    # (cache_digest_mismatch, docs/KV_CACHE.md).
    parser.add_argument("--murmur-seed", type=int, default=0)
    parser.add_argument("--num-pages", type=int, default=256)
    parser.add_argument("--max-model-len", type=int, default=2048)
    parser.add_argument("--max-batch-size", type=int, default=8)
    parser.add_argument("--tp", type=int, default=1)
    parser.add_argument("--warmup", dest="warmup", default=None,
                        action="store_true",
                        help="pre-compile all engine programs before "
                             "registration (default: auto — on for TPU)")
    parser.add_argument("--no-warmup", dest="warmup",
                        action="store_false")
    parser.add_argument("--dp", type=int, default=1)
    parser.add_argument("--sp", type=int, default=1)
    parser.add_argument("--ep", type=int, default=1)
    # Multi-host SPMD: one worker PROCESS per host of a multi-host TPU
    # slice, all running this same command. jax.distributed.initialize
    # wires the hosts into one runtime; the mesh below then spans every
    # chip of the slice and pjit/shard_map insert ICI/DCN collectives
    # (SURVEY.md §2.3 consequence; the reference's NCCL/MPI analog).
    parser.add_argument("--dist-coordinator", default="",
                        help="host:port of process 0 "
                             "(multi-host slice; '' = single host)")
    parser.add_argument("--dist-num-processes", type=int, default=0)
    parser.add_argument("--dist-process-id", type=int, default=-1)
    args = parser.parse_args(argv)

    logging.basicConfig(level=logging.INFO)
    if args.dist_coordinator:
        import jax
        jax.distributed.initialize(
            coordinator_address=args.dist_coordinator,
            num_processes=(args.dist_num_processes or None),
            process_id=(args.dist_process_id
                        if args.dist_process_id >= 0 else None))
        logger.info("joined distributed runtime: process %d/%d, "
                    "%d local / %d global devices",
                    jax.process_index(), jax.process_count(),
                    jax.local_device_count(), jax.device_count())
    from xllm_service_tpu.service.coordination_net import connect_store
    store = connect_store(args.store_addr)
    engine_cfg = EngineConfig(
        page_size=args.page_size, num_pages=args.num_pages,
        max_model_len=args.max_model_len,
        max_batch_size=args.max_batch_size, tp=args.tp, dp=args.dp,
        sp=args.sp)
    mesh = None
    if args.tp * args.dp * args.sp * args.ep > 1:
        from xllm_service_tpu.parallel.mesh import MeshSpec, make_mesh
        mesh = make_mesh(MeshSpec(dp=args.dp, ep=args.ep, sp=args.sp,
                                  tp=args.tp))
    opts = WorkerOptions(
        host=args.host, port=args.port,
        instance_type=InstanceType(args.instance_type),
        service_addr=args.service_addr, model=args.model,
        model_dir=args.model_dir,
        heartbeat_interval_s=args.heartbeat_interval_s,
        lease_ttl_s=3 * args.heartbeat_interval_s,
        enable_profiling=args.enable_profiling, warmup=args.warmup,
        murmur_seed=args.murmur_seed,
        encode_only=(args.role == "encode"))
    worker = Worker(opts, store, engine_cfg=engine_cfg, mesh=mesh).start()
    logger.info("worker %s serving model %s (type %s)",
                worker.name, args.model, args.instance_type)

    stop = threading.Event()

    def on_signal(signum, frame) -> None:
        stop.set()

    signal.signal(signal.SIGINT, on_signal)
    signal.signal(signal.SIGTERM, on_signal)
    stop.wait()
    worker.stop()
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
