"""Headline benchmark: continuous-batching decode throughput + MFU on one chip.

Runs the flagship model (Llama-3.2-1B shapes, random weights) through the
real serving engine — paged KV cache, fused sampling, donated buffers — and
measures steady-state decode throughput, per-token latency (TPOT), and MFU
(model FLOPs utilization against the chip's bf16 peak).

The reference publishes no benchmark numbers (BASELINE.md); its implicit
performance envelope is the SLO default ``target_tpot`` = 50 ms/token
(reference common/global_gflags.cpp:100-102). ``vs_baseline`` is therefore
measured-TPOT headroom against that 50 ms SLO: value N means each token
arrives N× faster than the reference's own default target.

The run happens once, in this process, on the device JAX gives it, and
the result names that device (``detail.platform`` / ``device_kind`` /
``device_count``). There is no probe, no child process and no fallback:
a phase that fails raises, and the exit code is non-zero. ``BENCH_TINY=1``
selects the tiny model (what the CPU tests run); a number from a CPU run
is a count of what the program did, never a device metric.

Warmup inside the bench is scoped to exactly the programs its schedule
hits. The engine's step programs are compiled fresh in every run: they
pin the KV pools' layout, and an executable loaded from JAX's persistent
cache does not keep it (xllm_service_tpu/utils/jaxcache.py).

Prints one JSON line:
  {"metric": "decode_throughput", "value": ..., "unit": "tokens/s",
   "vs_baseline": ..., "detail": {..., "mfu": ..., "tpot_ms": ...}}
"""

from __future__ import annotations

import json
import os
import time


def _matmul_params(params, cfg) -> int:
    """Parameters that each decoded token multiplies against (embedding
    gather excluded; tied lm_head counted once, as the head matmul)."""
    import jax
    total = sum(int(x.size) for x in jax.tree_util.tree_leaves(params))
    if not cfg.tie_word_embeddings:
        total -= cfg.vocab_size * cfg.hidden_size   # embed is a gather
    return total


def scoped_warmup_shapes(ecfg, batch: int, prompt_len: int, gen_len: int):
    """Predict exactly the (prefill, decode) programs the bench schedule
    compiles, for Engine.warmup's scoped mode. The prediction mirrors the
    engine: prefill batches fill max_prefill_tokens at one prompt_len
    window each (pow2-padded batch, table wide enough for the sampled
    token's page); decode table widths are pow2(pages(live context))
    across the whole decoded trajectory including the fused burst's page
    lookahead (covered by the range endpoint prompt+gen). A missed shape
    is not a correctness problem — it compiles lazily and shows up in
    detail.phases recompile counters. Unit-tested against the real engine
    in tests/test_engine.py (zero post-warmup recompiles)."""
    pages = lambda n: -(-n // ecfg.page_size)   # noqa: E731
    pow2 = lambda n: 1 << max(n - 1, 0).bit_length()  # noqa: E731
    # The engine buckets each prefill window's T (engine._bucket): predict
    # with the bucketed value or a non-bucket-aligned prompt_len warms a
    # program the engine never runs.
    t_pf = next((b for b in ecfg.prefill_buckets if b >= prompt_len), None)
    if t_pf is None:
        raise ValueError(
            f"prompt_len {prompt_len} exceeds largest prefill bucket "
            f"{ecfg.prefill_buckets[-1]} — fix the bench shape, don't "
            "let it silently fall back to CPU")
    n_pf = min(batch, max(ecfg.max_prefill_tokens // prompt_len, 1))
    mp_pf = pow2(max(pages(prompt_len + 1), pages(t_pf)))
    sizes = {n_pf}
    if getattr(ecfg, "interleave", None) is not False:
        # Token-budget interleaving (engine._step_interleaved): once the
        # first batch is decoding, every iteration's fused decode burst
        # consumes part of the step budget, so later prefill batches
        # shrink down a batch-size ladder the warmup must cover too.
        # Bucket-snapped quanta keep T and MP fixed — only B varies.
        # Mirror the bench drain: full prompt_len windows, decode burst
        # of decode_steps tokens per running sequence, and the
        # starvation-deadline floor (engine._starvation_quantum)
        # admitting one prompt when the residual fits no window.
        waiting, running = batch - n_pf, n_pf
        while waiting > 0:
            budget = ecfg.max_prefill_tokens - running * ecfg.decode_steps
            n = min(waiting, max(budget // prompt_len, 0),
                    ecfg.max_batch_size - running)
            if n <= 0:
                n = 1
            sizes.add(n)
            waiting -= n
            running += n
    widths = sorted({
        min(pow2(pages(t)), ecfg.max_pages_per_seq)
        for t in range(prompt_len + 1, prompt_len + gen_len + 1)})
    return sorted({(pow2(n), t_pf, mp_pf) for n in sizes}), widths


def _run_bench(tiny: bool) -> dict:
    import jax

    from xllm_service_tpu.config import EngineConfig, ModelConfig
    from xllm_service_tpu.obs import (
        default_registry, histogram_fraction_le, histogram_quantile)
    from xllm_service_tpu.obs import steptrace
    from xllm_service_tpu.obs.slo import SloConfig
    from xllm_service_tpu.runtime.engine import Engine, EngineRequest
    from xllm_service_tpu.utils.jaxcache import enable_compile_cache
    from xllm_service_tpu.utils.types import FinishReason, SamplingParams

    enable_compile_cache()
    dev = jax.devices()[0]
    platform = dev.platform
    # Raises for a device the peaks table does not hold: an MFU against
    # a made-up peak is not a number.
    peak, _ = steptrace.peaks_for(dev.device_kind)
    if tiny:
        cfg = ModelConfig.tiny(vocab_size=1024)
        batch, prompt_len, gen_len, pages = 4, 32, 64, 64
        # BENCH_TINY_GEN trims the decode loop (same BENCH_* override
        # idiom as the TPU shape knobs) — the tier-1 provenance test
        # shrinks it so the full-suite budget doesn't pay 64 steps of
        # tiny-model decode for fields that 8 steps prove identically.
        gen_len = int(os.environ.get("BENCH_TINY_GEN", str(gen_len)))
        ecfg = EngineConfig(page_size=16, num_pages=pages,
                            max_model_len=256, max_batch_size=batch,
                            max_prefill_tokens=256,
                            prefill_buckets=(32, 64),
                            # Honored on the tiny path too so a CPU run
                            # can demonstrate the decode-pipeline
                            # overlap counters (default stays 1).
                            decode_steps=int(os.environ.get(
                                "BENCH_DECODE_STEPS", "1")))
    else:
        cfg = ModelConfig.llama3_1b()
        # Throughput shape: decode is weight-read-bound, so tokens/s (and
        # MFU) scale ~linearly with batch until HBM pressure; 64-step
        # fused bursts amortize the host round-trip per dispatch.
        batch, prompt_len, gen_len = 64, 128, 256
        # max_prefill_tokens covers the whole prompt set in ONE call
        # (round-3 hardware data showed ~5.6 s/prefill-call where the
        # math says tens of ms). Override to A/B:
        # BENCH_PREFILL_TOKENS=4096 restores the two-call split.
        # page_size 128 = the reference's own block-size default
        # (global_gflags.cpp:87-89) and HALVES the decode-attention
        # pallas grid (B x pages x layers cells/step) vs 64 — per-cell
        # overhead is a first-order term at B=64. Same pool bytes.
        ecfg = EngineConfig(page_size=int(os.environ.get(
                                "BENCH_PAGE_SIZE", "128")),
                            num_pages=int(os.environ.get(
                                "BENCH_NUM_PAGES", "512")),
                            max_model_len=1024, max_batch_size=batch,
                            max_prefill_tokens=int(os.environ.get(
                                "BENCH_PREFILL_TOKENS", "8192")),
                            prefill_buckets=(128,),
                            decode_steps=int(os.environ.get(
                                "BENCH_DECODE_STEPS", "64")))
    t_boot0 = time.monotonic()
    engine = Engine(cfg, ecfg, seed=0)
    tw0 = time.monotonic()
    pf_shapes = widths = None
    if tiny:
        engine.warmup()
    else:
        # Scoped warmup: exactly the programs this schedule compiles.
        # The full pow2 sweep belongs to serving startup, not a bench.
        pf_shapes, widths = scoped_warmup_shapes(
            ecfg, batch, prompt_len, gen_len)
        engine.warmup(prefill_shapes=pf_shapes, decode_widths=widths)
    warmup_s = time.monotonic() - tw0
    # Cold boot = engine construction + first warmup of this process
    # (detail.warmup_s vs boot_warm_s shows the compile share).
    boot_cold_s = time.monotonic() - t_boot0

    # Per-request latency trajectory, recorded into the SAME
    # service-plane histogram series (names + log buckets) the front
    # door exports, then scraped back out of the rendered exposition
    # with obs.histogram_quantile — the arithmetic a dashboard would
    # run, so BENCH_*.json percentiles and /metrics cannot drift apart.
    lat = default_registry()
    h_ttft = lat.histogram("xllm_service_ttft_ms")
    h_tpot = lat.histogram("xllm_service_tpot_ms")
    h_queue = lat.histogram("xllm_service_queue_wait_ms")
    h_e2e = lat.histogram("xllm_service_e2e_ms")

    sp = SamplingParams(max_tokens=gen_len, temperature=0.0, ignore_eos=True)
    t_add = {}
    t_submit = {}       # survives the first-token pop: e2e needs it
    for i in range(batch):
        # Distinct prompts: identical ones would prefix-cache-hit after
        # the first batch, silently benchmarking cache lookups instead of
        # prefill compute (and shifting later batch shapes off the scoped
        # warmup's prediction).
        engine.add_request(EngineRequest(
            request_id=f"bench-{i}",
            token_ids=[(i + j) % (cfg.vocab_size - 1) + 1
                       for j in range(prompt_len)],
            sampling=sp))
        t_add[f"bench-{i}"] = t_submit[f"bench-{i}"] = time.monotonic()
    # Prefill outside the timed window: the metric is steady-state decode.
    # Still measured — prefill is the compute-bound phase, so its MFU shows
    # what the matmul path achieves when not weight-read-bound.
    tp0 = time.monotonic()
    while engine.waiting:
        t_step = time.monotonic()
        step_outs = engine.step()
        now = time.monotonic()
        for out in step_outs:
            # First output of a request = its first sampled token:
            # TTFT from submission; queue wait = time spent waiting for
            # the step that scheduled its prefill to begin.
            ta = t_add.pop(out.request_id, None)
            if ta is not None:
                h_ttft.observe(1000.0 * (now - ta))
                h_queue.observe(1000.0 * (t_step - ta))
            if out.finish_reason != FinishReason.NONE:
                h_e2e.observe(1000.0 * (now - t_submit[out.request_id]))
    prefill_s = time.monotonic() - tp0
    prefill_tokens = batch * prompt_len
    t0 = time.monotonic()
    tokens = 0
    while engine.has_work():
        t_step = time.monotonic()
        step_outs = engine.step()
        step_el = time.monotonic() - t_step
        for out in step_outs:
            tokens += len(out.new_token_ids)
            if out.new_token_ids:
                # Per-token latency of this sequence in this step; a
                # fused burst amortizes one step across N tokens.
                h_tpot.observe(1000.0 * step_el / len(out.new_token_ids))
            if out.finish_reason != FinishReason.NONE:
                h_e2e.observe(1000.0 * (time.monotonic()
                                        - t_submit[out.request_id]))
    elapsed = time.monotonic() - t0

    # Prefix-reuse health, through the SAME rendered-exposition path a
    # live worker exports (scrape-don't-peek: the detail number comes
    # from parsing the text exposition, so it is the dashboard's number,
    # not a parallel bookkeeping path).
    pc = engine.prefix_cache_stats()
    lat.counter("xllm_worker_prefix_cache_hit_tokens_total").set_total(
        pc["hit_tokens_total"])
    lat.counter("xllm_worker_prefix_cache_lookups_total").set_total(
        pc["lookups_total"])

    lat_scrape = lat.render()

    def _q(family: str, q: float):
        v = histogram_quantile(lat_scrape, family, q)
        return round(v, 3) if v is not None else None

    # SLO attainment against the configured targets (XLLM_SLO_* env,
    # same defaults as the live /admin/slo engine), from the SAME
    # scraped buckets as the percentiles above — BENCH_*.json tracks
    # the fraction of requests under target per round.
    slo_thr = {o.name: o.threshold_ms
               for o in SloConfig.from_env().objectives}

    def _attainment(family: str, threshold_ms: float):
        v = histogram_fraction_le(lat_scrape, family, threshold_ms)
        return round(v, 4) if v is not None else None

    def _counter(family: str) -> float:
        from xllm_service_tpu.obs.expfmt import parse_exposition
        samples, _types, _errs = parse_exposition(lat_scrape)
        return sum(v for name, _labels, v in samples if name == family)

    # Fraction of prompt tokens the prefix cache covered this run
    # (local hits + tier restores + cross-worker fetches over ALL
    # prompt tokens the run admitted) — scraped back out of the
    # rendered exposition like the latency percentiles.
    pc_hit = _counter("xllm_worker_prefix_cache_hit_tokens_total")
    prefix_cached_token_ratio = (
        round(pc_hit / prefill_tokens, 4) if prefill_tokens else None)

    # "No routed request ever pays a compile", proven per round: the
    # post-warmup recompile counters after the measured run, and the
    # warm re-boot cost (same warmup sweep with every program already
    # compiled — dispatch-only, so seconds of delta vs boot_cold_s IS
    # the compile bill warmup absorbed).
    recompiles_post_warmup = sum(
        v for k, v in engine.phase_counts.items()
        if k.endswith(".recompile"))
    tb0 = time.monotonic()
    if tiny:
        engine.warmup()
    else:
        engine.warmup(prefill_shapes=pf_shapes, decode_widths=widths)
    boot_warm_s = time.monotonic() - tb0

    throughput = tokens / elapsed
    steps = tokens / batch              # decode iterations per sequence
    tpot_ms = 1000.0 * elapsed / max(steps, 1)
    # Pipelined-decode overlap health (speculative next-burst dispatch,
    # XLLM_DECODE_PIPELINE): how often burst k+1 was consumed as
    # speculated, and the host-side device-idle bubble per burst
    # boundary the pipeline did not cover — with the split
    # device_wait/host_copy readback phases (detail.phases below) this
    # is what proves the overlap win on the next BENCH_*.json.
    overlap = engine.overlap_metrics()

    # MFU: FLOPs each decoded token costs = 2 * matmul params + attention
    # reads over the mean live context (2 FLOPs/MAC; QK^T and PV each touch
    # Hq*Dh*context per layer).
    n_matmul = _matmul_params(engine.params, cfg)
    mean_ctx = prompt_len + gen_len / 2.0
    attn_flops = 4.0 * cfg.num_layers * cfg.num_heads * cfg.head_dim \
        * mean_ctx
    flops_per_token = 2.0 * n_matmul + attn_flops
    achieved = flops_per_token * throughput
    mfu = achieved / peak

    burst = None
    if tiny or os.environ.get("BENCH_BURST") == "1":
        burst = _burst_goodput_section(
            engine, cfg, ecfg, prompt_len, gen_len,
            target_ttft_ms=slo_thr["ttft"])

    mixed = None
    if tiny or os.environ.get("BENCH_MIXED") == "1":
        mixed = _mixed_step_section(cfg, ecfg, prompt_len, gen_len)

    kv_probe = None
    if not tiny and platform != "cpu":
        # BASELINE.md north-star row: KV-migration GB/s on the real chip,
        # folded into the headline artifact (BENCH_KV_PROBE=0 skips it).
        kv_probe = _maybe_kv_probe(engine, cfg, ecfg)

    return {
        "metric": "decode_throughput",
        "value": round(throughput, 2),
        "unit": "tokens/s",
        "vs_baseline": round(50.0 / tpot_ms, 3),
        "detail": {
            "model": cfg.name, "platform": platform,
            "device_kind": dev.device_kind,
            "device_count": len(jax.devices()),
            # Which gated kernels this run used (A/B bookkeeping).
            # XLLM_PALLAS_KV / XLLM_WRITE_THEN_ATTEND default to AUTO
            # (follow XLLM_PALLAS), not off — recording unset as "0"
            # would claim a feature-off run for a feature-on number.
            "kernel_flags": {
                **{k: os.environ.get(k, "0") for k in
                   ("XLLM_PALLAS", "XLLM_PALLAS_PREFILL",
                    "XLLM_RAGGED_ATTN")},
                **{k: os.environ.get(k, "auto") for k in
                   ("XLLM_PALLAS_KV", "XLLM_WRITE_THEN_ATTEND",
                    "XLLM_DECODE_PIPELINE")}},
            "batch": batch, "prompt_len": prompt_len, "gen_len": gen_len,
            # Same precision as boot_cold_s: boot_cold ⊇ warmup must
            # survive rounding (boot_cold_s >= warmup_s is asserted in
            # tests/test_engine.py).
            "warmup_s": round(warmup_s, 2),
            "boot_cold_s": round(boot_cold_s, 2),
            "boot_warm_s": round(boot_warm_s, 2),
            "recompiles_post_warmup": recompiles_post_warmup,
            "tpot_ms": round(tpot_ms, 3),
            "decode_overlap_hit_ratio": round(overlap["hit_ratio"], 4),
            "decode_device_idle_ms_per_burst": round(
                overlap["device_idle_ms_per_burst"], 3),
            "decode_overlap_spec": {
                "dispatches": overlap["spec_dispatches"],
                "hits": overlap["spec_hits"],
                "rollbacks": overlap["spec_rollbacks"]},
            # Latency trajectory, scraped from the service-plane
            # histogram series recorded above (log-bucket interpolated
            # — dashboard-faithful, not exact order statistics).
            "ttft_ms_p50": _q("xllm_service_ttft_ms", 0.50),
            "ttft_ms_p90": _q("xllm_service_ttft_ms", 0.90),
            "ttft_ms_p99": _q("xllm_service_ttft_ms", 0.99),
            "tpot_ms_p50": _q("xllm_service_tpot_ms", 0.50),
            "tpot_ms_p90": _q("xllm_service_tpot_ms", 0.90),
            "tpot_ms_p99": _q("xllm_service_tpot_ms", 0.99),
            "queue_wait_ms_p99": _q("xllm_service_queue_wait_ms", 0.99),
            "e2e_ms_p99": _q("xllm_service_e2e_ms", 0.99),
            "prefix_cached_token_ratio": prefix_cached_token_ratio,
            "slo_ttft_attainment": _attainment(
                "xllm_service_ttft_ms", slo_thr["ttft"]),
            "slo_e2e_attainment": _attainment(
                "xllm_service_e2e_ms", slo_thr["e2e"]),
            "slo_targets_ms": {"ttft": slo_thr["ttft"],
                               "e2e": slo_thr["e2e"]},
            "mfu": round(mfu, 4),
            "prefill_tokens_per_s": round(prefill_tokens / prefill_s, 1),
            # Prefill runs the lm_head only on the LAST position per
            # sequence (forward_prefill return_all_logits=False), so
            # per-prompt-token FLOPs exclude the head matmul.
            "prefill_mfu": round(
                2.0 * (n_matmul - cfg.vocab_size * cfg.hidden_size)
                * (prefill_tokens / prefill_s) / peak, 4),
            "model_flops_per_token": flops_per_token,
            "chip_peak_flops": peak,
            # Host/device wall-time attribution per engine phase
            # (dispatch is async-call time; the former conflated
            # readback is split into device_wait — wait for the
            # producing computation — vs host_copy — the residual
            # device→host materialization).
            "phases": engine.phase_report(),
            # Burst goodput through the loadgen summarizer (same verdict
            # arithmetic as the closed-loop harness); the top-level
            # goodput key tracks the burst scenario — the number the
            # interleaver is accountable for.
            **({"goodput_under_slo": burst["goodput_under_slo"],
                "burst": burst} if burst else {}),
            # One-dispatch mixed-iteration A/B (XLLM_RAGGED_ATTN);
            # dispatches_per_mixed_step is the headline pair — 1.0 on
            # the ragged path vs >=2 on the split per-phase path.
            **({"mixed_step": mixed,
                "dispatches_per_mixed_step":
                    mixed["dispatches_per_mixed_step"]}
               if mixed else {}),
            **({"kv_migration": kv_probe} if kv_probe else {}),
            "reference_baseline": "target_tpot=50ms SLO default "
                                  "(no published numbers)",
        },
    }


def _burst_goodput_section(engine, cfg, ecfg, prompt_len: int,
                           gen_len: int, target_ttft_ms: float) -> dict:
    """Goodput-under-SLO under a prompt burst, at the engine level.

    Short decode streams run steady, then a wave of long prompts lands
    mid-decode — the scenario the token-budget interleaver exists for.
    Per-request TTFT/TPOT feed benchmarks.loadgen.summarize_results, the
    SAME verdict + percentile arithmetic as the closed-loop HTTP
    harness, so BENCH_*.json and loadgen cannot drift. Tiny/CPU runs
    only by default (BENCH_BURST=1 forces): its small prefill batches
    are outside the scoped warmup's shape prediction."""
    from benchmarks.loadgen import RequestResult, summarize_results
    from xllm_service_tpu.runtime.engine import EngineRequest
    from xllm_service_tpu.utils.types import SamplingParams

    n = min(ecfg.max_batch_size, 4)
    vocab = cfg.vocab_size - 1
    t_sub: dict = {}
    first: dict = {}
    last: dict = {}
    ntok: dict = {}

    def _add(rid: str, plen: int, max_tokens: int, salt: int) -> None:
        engine.add_request(EngineRequest(
            request_id=rid,
            token_ids=[(salt + j) % vocab + 1 for j in range(plen)],
            sampling=SamplingParams(max_tokens=max_tokens,
                                    temperature=0.0, ignore_eos=True)))
        t_sub[rid] = time.monotonic()

    def _drain_steps(stop_when_idle: bool, steps: int = 0) -> None:
        done = 0
        while engine.has_work() if stop_when_idle else done < steps:
            outs = engine.step()
            now = time.monotonic()
            done += 1
            for out in outs:
                if out.new_token_ids:
                    rid = out.request_id
                    first.setdefault(rid, now)
                    last[rid] = now
                    ntok[rid] = ntok.get(rid, 0) + len(out.new_token_ids)

    t0 = time.monotonic()
    for i in range(n):
        _add(f"stream-{i}", max(prompt_len // 4, 4),
             min(gen_len, 32), salt=7000 + 31 * i)
    _drain_steps(stop_when_idle=False, steps=4)
    for i in range(n):
        _add(f"burst-{i}", prompt_len, 8, salt=9000 + 53 * i)
    _drain_steps(stop_when_idle=True)
    wall = time.monotonic() - t0

    results = []
    for rid, ts in t_sub.items():
        f, l, k = first.get(rid), last.get(rid), ntok.get(rid, 0)
        r = RequestResult(ok=f is not None, num_tokens=k)
        if f is not None:
            r.ttft_ms = 1000.0 * (f - ts)
            r.total_ms = 1000.0 * (l - ts)
            if k > 1:
                r.tpot_ms = 1000.0 * (l - f) / (k - 1)
        results.append(r)
    s = summarize_results(results, wall, target_ttft_ms=target_ttft_ms,
                          target_tpot_ms=50.0)
    return {"goodput_under_slo": s["goodput_under_slo"],
            "num_ok": s["num_ok"],
            "ttft_ms_p99": s["ttft_ms"]["p99"],
            "tpot_ms_p99_under_burst": s["tpot_ms"]["p99"]}


def _mixed_step_section(cfg, ecfg, prompt_len: int,
                        gen_len: int) -> dict:
    """One-dispatch ragged mixed iterations vs the split per-phase
    path, at the engine level (XLLM_RAGGED_ATTN A/B).

    Two fresh engines — identical except ``ragged_attn`` — each drive
    decode streams and land a prompt mid-decode, and every MIXED
    iteration logs its attention-dispatch count
    (``last_step_attn_dispatches``) and wall ms. The ragged leg must
    average exactly 1.0 dispatches per mixed step; the split leg pays
    one decode program plus one prefill program (>= 2). Tiny/CPU runs
    only by default (BENCH_MIXED=1 forces): like the burst section,
    its small shapes sit outside a hardware run's scoped warmup."""
    import dataclasses

    from xllm_service_tpu.runtime.engine import Engine, EngineRequest
    from xllm_service_tpu.utils.types import SamplingParams

    vocab = cfg.vocab_size - 1
    plen = max(prompt_len // 4, 4)

    def drive(ragged: bool) -> dict:
        e2 = dataclasses.replace(ecfg, ragged_attn=ragged)
        # Defeat any XLLM_RAGGED_ATTN env override __post_init__
        # applied — the A/B must flip the gate regardless of env.
        e2.ragged_attn = ragged
        eng = Engine(cfg, e2, seed=0)
        toks: dict = {}
        mixed_ms: list = []
        dispatches: list = []
        ragged_steps = 0

        def _step():
            nonlocal ragged_steps
            t0 = time.monotonic()
            outs = eng.step()
            ms = 1000.0 * (time.monotonic() - t0)
            if eng.last_step_kind == "mixed":
                mixed_ms.append(ms)
                dispatches.append(eng.last_step_attn_dispatches)
                if eng.last_step_ragged:
                    ragged_steps += 1
            for o in outs:
                toks.setdefault(o.request_id, []).extend(o.new_token_ids)

        sp = SamplingParams(max_tokens=min(gen_len, 16),
                            temperature=0.0, ignore_eos=True)
        eng.add_request(EngineRequest(
            request_id="stream-0",
            token_ids=[(7001 + j) % vocab + 1 for j in range(plen)],
            sampling=sp))
        for _ in range(2):
            _step()
        # Prompts landing mid-decode — the mixed iterations under test.
        for i in range(max(min(ecfg.max_batch_size, 4) - 1, 1)):
            eng.add_request(EngineRequest(
                request_id=f"mid-{i}",
                token_ids=[(9001 + 53 * i + j) % vocab + 1
                           for j in range(plen)],
                sampling=sp))
        steps = 0
        while eng.has_work() and steps < 500:
            _step()
            steps += 1
        n = len(dispatches)
        return {
            "mixed_steps": n,
            "ragged_steps": ragged_steps,
            "dispatches_per_mixed_step":
                round(sum(dispatches) / n, 3) if n else None,
            "mixed_step_ms_mean":
                round(sum(mixed_ms) / n, 3) if n else None,
            "tokens": toks,
        }

    on = drive(True)
    off = drive(False)
    # Temperature-0 streams must not depend on the dispatch plan.
    identical = on.pop("tokens") == off.pop("tokens")
    return {
        "ragged_on": on, "ragged_off": off,
        "streams_identical": identical,
        "dispatches_per_mixed_step": {
            "ragged_on": on["dispatches_per_mixed_step"],
            "ragged_off": off["dispatches_per_mixed_step"]},
    }


def _maybe_kv_probe(engine, cfg, ecfg) -> dict:
    """KV GB/s (direct + host-shuttle) using the bench engine as source
    and a fresh pool-identical engine as destination."""
    if os.environ.get("BENCH_KV_PROBE", "1") == "0":
        return {"skipped": "BENCH_KV_PROBE=0"}
    from xllm_service_tpu.runtime.engine import Engine
    from xllm_service_tpu.runtime.kv_transfer import probe_kv_migration
    dst = Engine(cfg, ecfg, seed=1)
    out = probe_kv_migration(engine, dst,
                             n_pages=min(128, ecfg.num_pages // 2),
                             iters=3)
    return {"direct_gbps": round(out["direct_gbps"], 2),
            "host_shuttle_gbps": round(out["host_gbps"], 2),
            "host_pipelined_gbps": round(
                out["host_pipelined_gbps"], 2),
            "block_mb": round(out["bytes"] / 1e6, 1),
            "pages": int(out["pages"])}


def main() -> int:
    print(json.dumps(_run_bench(tiny=bool(os.environ.get("BENCH_TINY")))),
          flush=True)
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
