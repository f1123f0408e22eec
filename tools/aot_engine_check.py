"""Offline v5e compile + cost-model sweep of the ENGINE's hot programs.

Extends the kernel-form probes (tools/aot_kernel_probes.py) to the real
serving programs at the headline bench geometry (llama3-1b, B=64
decode / B=32xT=128 prefill): the fused 64-step decode burst, the
single decode step, and the prefill step on BOTH attention paths (XLA
gather vs the Pallas kernel). For each program: does it compile for
v5e at all (a crash here is a crash on the chip), does donation alias
the KV pool (input_output_alias at TPU lowering — the donation probe's
question, answered offline), and what does XLA's cost model charge in
bytes/flops (the analytic budget; the scan body is counted ONCE — see
docs/PERF_NOTES.md — so per-step figures derive from the single-step
program, and the burst's value is compile validity + aliasing).

Run: python tools/aot_engine_check.py   (pins CPU; needs no chip)
Prints one verdict line per program + a JSON summary.
"""

from __future__ import annotations

import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

from tools.aot_tpu import aot_compile, sds  # noqa: E402  (pins CPU)

import jax  # noqa: E402
import jax.numpy as jnp  # noqa: E402


def _llama3_1b_sds():
    from xllm_service_tpu.config import ModelConfig
    cfg = ModelConfig.llama3_1b()
    L, Hq, Hkv, D = (cfg.num_layers, cfg.num_heads, cfg.num_kv_heads,
                     cfg.head_dim)
    V, H, I = cfg.vocab_size, cfg.hidden_size, cfg.intermediate_size
    bf = jnp.bfloat16
    layers = {
        "input_norm": sds((L, H), bf), "post_norm": sds((L, H), bf),
        "q_proj": sds((L, H, Hq * D), bf),
        "k_proj": sds((L, H, Hkv * D), bf),
        "v_proj": sds((L, H, Hkv * D), bf),
        "o_proj": sds((L, Hq * D, H), bf),
        "gate_proj": sds((L, H, I), bf), "up_proj": sds((L, H, I), bf),
        "down_proj": sds((L, I, H), bf),
    }
    params = {"embed": sds((V, H), bf), "final_norm": sds((H,), bf),
              "layers": layers}
    return cfg, params


def main() -> int:
    from xllm_service_tpu.models import transformer

    cfg, params = _llama3_1b_sds()
    L, Hkv, D = cfg.num_layers, cfg.num_kv_heads, cfg.head_dim
    ps, P = 64, 1024
    kv = (sds((L, P, ps, Hkv, D), jnp.bfloat16),
          sds((L, P, ps, Hkv, D), jnp.bfloat16))
    results = {}

    def check(name, fn, args, donate=()):
        try:
            compiled = aot_compile(fn, args, donate_argnums=donate)
            ca = compiled.cost_analysis()
            if isinstance(ca, list):
                ca = ca[0]
            mem = compiled.memory_analysis()
            row = {
                "ok": True,
                "gflops": round(ca.get("flops", 0) / 1e9, 2),
                "gbytes": round(ca.get("bytes accessed", 0) / 1e9, 3),
                "alias_gb": round(
                    getattr(mem, "alias_size_in_bytes", 0) / 1e9, 3),
                "temp_gb": round(
                    getattr(mem, "temp_size_in_bytes", 0) / 1e9, 3),
            }
            print(f"{name}: COMPILE OK  gflops={row['gflops']} "
                  f"gbytes={row['gbytes']} alias_gb={row['alias_gb']} "
                  f"temp_gb={row['temp_gb']}")
        except Exception as e:  # noqa: BLE001 — verdicts
            msg = str(e).replace("\n", " ")[:300]
            row = {"ok": False, "error": msg}
            print(f"{name}: FAIL: {msg}")
        results[name] = row

    # ---- decode: single step + 64-step burst, B=64, ctx 384 ----
    B, ctx = 64, 384
    need = -(-(ctx + 1) // ps)
    MP = 1 << max(need - 1, 0).bit_length()
    tok = sds((B,), jnp.int32)
    pos = sds((B,), jnp.int32)
    act = sds((B,), jnp.bool_)
    pt = sds((B, MP), jnp.int32)

    # The two paths as plans (attend-then-scatter, as these checks have
    # always compiled them). interpret=False: real Mosaic lowering even
    # though the RUNTIME platform is the pinned CPU (tools/aot_tpu.py) —
    # interpreted, the kernels lower as interpreter ops and the analysis
    # describes a program the TPU never runs.
    from xllm_service_tpu.ops.plan import KernelPlan
    kernel_plan = KernelPlan(decode_attn=True, prefill_attn=True,
                             kv_writers=True, interpret=False)
    paths = (("gather", KernelPlan()), ("pallas_kernel", kernel_plan))

    def decode_step(plan):
        def fn(params, tok, pos, act, kv, pt):
            logits, kv = transformer.forward_decode(
                params, cfg, tok, pos, act, kv, pt, plan=plan)
            return jnp.argmax(logits, -1).astype(jnp.int32), kv
        return fn

    for label, plan in paths:
        check(f"decode_single B=64 ctx=384 [{label}]", decode_step(plan),
              (params, tok, pos, act, kv, pt), donate=(4,))

    def decode_burst(params, tok, pos, act, kv, pt):
        def body(carry, _):
            t, p, kv = carry
            logits, kv = transformer.forward_decode(
                params, cfg, t, p, act, kv, pt, plan=kernel_plan)
            t2 = jnp.argmax(logits, -1).astype(jnp.int32)
            return (t2, p + 1, kv), t2
        (t, p, kv), toks = jax.lax.scan(
            body, (tok, pos, kv), None, length=64)
        return toks, t, p, kv

    check("decode_burst64 B=64 ctx=384 [pallas_kernel]", decode_burst,
          (params, tok, pos, act, kv, pt), donate=(4,))

    # ---- prefill: B=32, T=128, both attention paths ----
    Bp, T = 32, 128
    needp = -(-(T + 1) // ps)
    MPp = 1 << max(needp - 1, 0).bit_length()
    tokens = sds((Bp, T), jnp.int32)
    start = sds((Bp,), jnp.int32)
    lens = sds((Bp,), jnp.int32)
    ptp = sds((Bp, MPp), jnp.int32)

    def prefill_step(plan):
        def fn(params, tokens, start, lens, kv, ptp):
            last, lps, kv = transformer.forward_prefill(
                params, cfg, tokens, start, lens, kv, ptp, plan=plan)
            return last, kv
        return fn

    for label, plan in paths:
        check(f"prefill B=32 T=128 [{label}]", prefill_step(plan),
              (params, tokens, start, lens, kv, ptp), donate=(4,))

    print(json.dumps({"aot_target": "v5e:1x1 (local libtpu)",
                      "results": results}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
