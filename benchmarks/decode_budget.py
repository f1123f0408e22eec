"""Scan-slope microbench of the decode step's cost components.

A single dispatch+readback pays a fixed host round-trip that swamps a
sub-millisecond op, so this uses SCAN-SLOPE: run the op N times inside
one jitted `lax.scan` with a data dependency between iterations, read
back once, time at two N values, and take the slope — the fixed cost
cancels out.

Measures, at the headline bench shape (llama3-1b geometry, B=64,
ctx≈384, table width 8):

- paged decode attention per layer-call: XLA gather reference vs the
  three Pallas kernels (grid (B,pages); its transpose-free fold; the
  grid-(B,) double-buffered row kernel) — the kernel A/B the PERF_NOTES
  runbook wants, without burning a full bench per variant;
- the all-layer KV scatter (`write_decode_kv_all_layers`);
- the lm_head matmul + greedy sampling tail.

Run (any backend; Pallas kernels interpret off-TPU):
    python -m benchmarks.decode_budget [--batch 64] [--ctx 384]
        [--small] [--n-lo 4] [--n-hi 16]

Prints ONE JSON line: {"metric": "decode_budget", ...,
"detail": {<component>: ms_per_call, ...}}.
"""

from __future__ import annotations

import argparse
import functools
import json
import time

import jax
import jax.numpy as jnp
import numpy as np

from xllm_service_tpu.utils.jaxcache import enable_compile_cache


def _mark(name, value) -> None:
    """Stream each component's result to stderr AS IT LANDS: a full run
    is ~30 compiles, and partial lines make every completed slope
    durable if the run is cut short."""
    import sys
    print(f"PARTIAL {name} = {value}", file=sys.stderr, flush=True)


def _scan_slope(build_fn, n_lo: int, n_hi: int) -> float:
    """ms per iteration of ``body`` = slope between a ``n_lo``- and a
    ``n_hi``-iteration scan of it, one host readback each.

    ``build_fn(n)`` must return a zero-arg jitted callable whose result
    is a small array depending on every iteration. Each length is
    compiled AND run once for warmup before timing, so compile time and
    the first-dispatch cost stay out of the slope."""
    times = {}
    for n in (n_lo, n_hi):
        fn = build_fn(n)
        np.asarray(fn())                      # compile + warm
        t0 = time.monotonic()
        np.asarray(fn())
        times[n] = time.monotonic() - t0
    return 1e3 * (times[n_hi] - times[n_lo]) / (n_hi - n_lo)


def _page_table(B: int, n_tokens: int, ps: int, P: int):
    """Per-row distinct live pages covering ``n_tokens`` KV slots PLUS
    the next write position (the +1 page: a decode at position
    n_tokens-1 writes into the last mapped page; forgetting the +1 maps
    the write to NULL page 0 where mode="drop" silently discards it —
    the degeneracy main() used to work around ad hoc). Page 0 = NULL
    padding; width rounded to pow2 like the engine's table buckets."""
    need = -(-(n_tokens + 1) // ps)
    MP = 1 << max(need - 1, 0).bit_length()
    pt = np.zeros((B, MP), np.int32)
    for b in range(B):
        pt[b, :need] = 1 + ((np.arange(need) + b * need) % (P - 1))
    return jnp.asarray(pt), MP


def _prefill_budget(args, rng) -> dict:
    """Decompose one prefill call at the headline bench shape (B=32
    prompts x T=128 tokens; llama3-1b geometry): the full jitted program
    vs its parts — per-layer attention (XLA gather+overlay vs the gated
    Pallas kernel), the post-scan all-layer scatter, and a pure matmul
    tower as the MXU reference. Whatever the parts don't explain is
    glue (rope, norms, ys stacking, lm_head tail)."""
    from xllm_service_tpu.config import EngineConfig, ModelConfig
    from xllm_service_tpu.models import transformer
    from xllm_service_tpu.ops import attention as att
    from xllm_service_tpu.ops import pallas as pallas_mod
    from xllm_service_tpu.ops.pallas.prefill_attention import (
        paged_prefill_attention_pallas)
    from xllm_service_tpu.runtime.engine import Engine

    import dataclasses as dc
    if args.small:
        cfg = dc.replace(ModelConfig.tiny(), dtype="float32")
        ecfg = EngineConfig(page_size=8, num_pages=64, max_model_len=64,
                            max_batch_size=4, max_prefill_tokens=64,
                            prefill_buckets=(16,))
        B, T = 2, 16
    else:
        cfg = ModelConfig.llama3_1b()
        ecfg = EngineConfig(page_size=64, num_pages=1024,
                            max_model_len=2048, max_batch_size=64,
                            max_prefill_tokens=4096,
                            prefill_buckets=(128,))
        B, T = 32, 128
    eng = Engine(cfg, ecfg, seed=0)
    params, kv0 = eng.params, eng.kv
    ps = ecfg.page_size
    P = ecfg.num_pages
    L, Hq, Hkv = cfg.num_layers, cfg.num_heads, cfg.num_kv_heads
    D = cfg.head_dim
    pt, MP = _page_table(B, T, ps, P)
    tokens = jnp.asarray(
        rng.integers(0, cfg.vocab_size, size=(B, T)), jnp.int32)
    start = jnp.zeros((B,), jnp.int32)
    lens = jnp.full((B,), T, jnp.int32)
    dt = jnp.dtype(cfg.dtype)
    out = {"shape": {"B": B, "T": T, "table_width": MP}}

    def full_build(n):
        @jax.jit
        def run():
            def body(kv, _):
                last, _, kv2 = transformer.forward_prefill(
                    params, cfg, tokens, start, lens, kv, pt)
                return kv2, last[0, 0]
            kv_fin, lasts = jax.lax.scan(body, kv0, None, length=n)
            return lasts[-1] + kv_fin[0][0, 1, 0, 0, 0].astype(jnp.float32)
        return run

    out["full_step_ms"] = round(
        _scan_slope(full_build, 1, max(args.n_lo, 3)), 2)
    _mark("prefill.full_step_ms", out["full_step_ms"])

    # The COMPOSED decode step at the DECODE bench shape (--batch/--ctx
    # — deliberately NOT the prefill-leg shape above; it reads the
    # random-init pool through its own larger table, which prices the
    # same HBM traffic): the number the standalone decode component
    # slopes must explain. Residue = this − (L × attn_layer +
    # kv_scatter + lm_head + weight reads) = glue (rope, norms,
    # sampling, ys stacking). Lives here only because this leg owns the
    # Engine; main() re-parents it to the detail top level.
    if not args.no_decode:
        Bd = args.batch if not args.small else 4
        ctx_d = args.ctx if not args.small else 24
        ptd, _ = _page_table(Bd, ctx_d, ps, P)
        tok_d = jnp.asarray(
            rng.integers(0, cfg.vocab_size, size=(Bd,)), jnp.int32)
        # Last WRITTEN position (page mapped by the +1 in _page_table);
        # position ctx_d with an unmapped page would silently drop the
        # KV scatter and understate the step.
        pos_d = jnp.full((Bd,), ctx_d - 1, jnp.int32)
        act_d = jnp.ones((Bd,), bool)

        def dec_build(n):
            @jax.jit
            def run():
                def body(carry, _):
                    tok, kv = carry
                    logits, kv2 = transformer.forward_decode(
                        params, cfg, tok, pos_d, act_d, kv, ptd)
                    return (jnp.argmax(logits, -1).astype(jnp.int32),
                            kv2), ()
                (tok_fin, kv_fin), _ = jax.lax.scan(
                    body, (tok_d, kv0), None, length=n)
                return tok_fin[0] + kv_fin[0][0, 1, 0, 0, 0].astype(
                    jnp.int32)
            return run

        out["decode_full_step_ms"] = round(
            _scan_slope(dec_build, args.n_lo, args.n_hi), 3)
        _mark("decode_full_step_ms", out["decode_full_step_ms"])

    # One layer's attention, both paths, q/k/v random at layer shapes.
    q = jnp.asarray(rng.normal(size=(B, T, Hq, D)), dt)
    kf = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), dt)
    vf = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), dt)
    kp, vp = kv0[0][0], kv0[1][0]
    kv_lens = start + lens

    def gather_attn(qi):
        k_all = att.overlay_fresh_kv(att.gather_pages(kp, pt), kf, start)
        v_all = att.overlay_fresh_kv(att.gather_pages(vp, pt), vf, start)
        return att.mha_prefill_auto(qi, k_all, v_all, kv_lens, start)

    def kernel_attn(qi):
        return paged_prefill_attention_pallas(
            qi, kf, vf, kp, vp, pt, start, lens,
            interpret=pallas_mod.default_interpret())

    for name, fn in (("attn_xla_gather", gather_attn),
                     ("attn_pallas_kernel", kernel_attn)):
        def build(n, fn=fn):
            @jax.jit
            def run():
                def body(qi, _):
                    return fn(qi).astype(qi.dtype), ()
                q_fin, _ = jax.lax.scan(body, q, None, length=n)
                return q_fin[0, 0, 0]
            return run
        try:
            out[name + "_layer_ms"] = round(
                _scan_slope(build, args.n_lo, args.n_hi), 3)
        except Exception as exc:  # noqa: BLE001
            out[name + "_layer_ms"] = \
                f"error: {type(exc).__name__}: {exc}"
        _mark("prefill." + name + "_layer_ms", out[name + "_layer_ms"])

    # Post-scan all-layer scatter of the fresh ys.
    k_new = jnp.asarray(rng.normal(size=(L, B, T, Hkv, D)), dt)
    v_new = jnp.asarray(rng.normal(size=(L, B, T, Hkv, D)), dt)

    def scat_build(n):
        @jax.jit
        def run():
            def body(kv, _):
                return att.write_prefill_kv_all_layers_xla(
                    kv[0], kv[1], k_new, v_new, pt, start, lens), ()
            kv_fin, _ = jax.lax.scan(body, kv0, None, length=n)
            return kv_fin[0][0, 1, 0, 0, 0]
        return run

    out["kv_scatter_ms"] = round(
        _scan_slope(scat_build, args.n_lo, args.n_hi), 3)
    _mark("prefill.kv_scatter_ms", out["kv_scatter_ms"])

    # MXU reference: the layer's matmul tower (qkv + o + mlp) x L, no
    # attention math — what the step would cost if matmul-bound.
    H = cfg.hidden_size
    x0 = jnp.asarray(rng.normal(size=(B, T, H)), dt)
    wq = jnp.asarray(rng.normal(size=(H, Hq * D)), dt)
    wkv = jnp.asarray(rng.normal(size=(H, 2 * Hkv * D)), dt)
    wo = jnp.asarray(rng.normal(size=(Hq * D, H)), dt)
    w1 = jnp.asarray(rng.normal(size=(H, 2 * cfg.intermediate_size)), dt)
    w2 = jnp.asarray(rng.normal(size=(cfg.intermediate_size, H)), dt)

    def tower_build(n):
        @jax.jit
        def run():
            def body(x, _):
                def layer(xc, _):
                    a = xc @ wq
                    kvp = xc @ wkv
                    # kvp consumed cheaply so the kv projections aren't
                    # dead-code-eliminated out of the tower.
                    xc = xc + a @ wo \
                        + (kvp.sum(-1, keepdims=True) * 1e-9).astype(
                            xc.dtype)
                    u = xc @ w1
                    g = jax.nn.silu(u[..., :cfg.intermediate_size]) \
                        * u[..., cfg.intermediate_size:]
                    return (xc + g @ w2).astype(x.dtype), ()
                x2, _ = jax.lax.scan(layer, x, None, length=L)
                return x2, ()
            x_fin, _ = jax.lax.scan(body, x0, None, length=n)
            return x_fin[0, 0, 0]
        return run

    out["matmul_tower_ms"] = round(
        _scan_slope(tower_build, args.n_lo, args.n_hi), 3)
    _mark("prefill.matmul_tower_ms", out["matmul_tower_ms"])
    return out


def main() -> None:
    enable_compile_cache()
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--batch", type=int, default=64)
    ap.add_argument("--ctx", type=int, default=384,
                    help="live context per sequence (tokens)")
    ap.add_argument("--n-lo", type=int, default=4)
    ap.add_argument("--n-hi", type=int, default=16)
    ap.add_argument("--small", action="store_true",
                    help="tiny shapes for harness tests off-hardware")
    ap.add_argument("--prefill", action="store_true",
                    help="also decompose the prefill step (round-3: "
                         "prefill MFU measured ~0.007 on the chip — "
                         "find out where the seconds go)")
    ap.add_argument("--no-decode", action="store_true",
                    help="skip the decode components (prefill-only run)")
    ap.add_argument("--essential", action="store_true",
                    help="only the owner-question components (XLA gather "
                         "+ the default (B,pages) kernel + scatter + "
                         "lm_head), skipping the ragged one-dispatch "
                         "A/B — fewer compiles")
    args = ap.parse_args()

    from xllm_service_tpu.ops import attention as att
    from xllm_service_tpu.ops.pallas.paged_attention import (
        _paged_decode_attention_impl)
    from xllm_service_tpu.ops.pallas.ragged_attention import (
        ragged_paged_attention_pallas)
    from xllm_service_tpu.ops import pallas as pallas_mod

    if args.small:
        B, Hq, Hkv, D, ps, L, V = 4, 4, 2, 16, 8, 2, 256
        P = 64
    else:
        # llama3-1b geometry (config.py llama3_1b) + the bench pool.
        B, Hq, Hkv, D, ps, L, V = args.batch, 32, 8, 64, 64, 16, 128256
        P = 1024
    ctx_tokens = args.ctx if not args.small else 24
    interpret = pallas_mod.default_interpret()

    rng = np.random.default_rng(0)
    dt = jnp.bfloat16
    k_pages = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), dt)
    v_pages = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), dt)
    pt, MP = _page_table(B, ctx_tokens, ps, P)
    ctx = jnp.full((B,), ctx_tokens, jnp.int32)
    q0 = jnp.asarray(rng.normal(size=(B, Hq, D)), dt)
    kc = jnp.asarray(rng.normal(size=(B, Hkv, D)), dt)
    vc = jnp.asarray(rng.normal(size=(B, Hkv, D)), dt)

    def attn_builder(kernel_fn):
        def build(n):
            @jax.jit
            def run():
                def body(q, _):
                    out = kernel_fn(q, k_pages, v_pages, pt, ctx, kc, vc)
                    # Data dependency: next q IS the output (same cost
                    # profile, scan can't collapse or hoist).
                    return out.astype(q.dtype), ()
                q_fin, _ = jax.lax.scan(body, q0, None, length=n)
                return q_fin[0, 0]
            return run
        return build

    # The serving default is the LAYERED kernel (full 5D pools + traced
    # layer index — no per-layer slice materialization); the sliced
    # forms remain as A/B references.
    L_pool = 4   # enough layers to expose slice-vs-layered cost
    kp5 = jnp.asarray(rng.normal(size=(L_pool, P, ps, Hkv, D)), dt)
    vp5 = jnp.asarray(rng.normal(size=(L_pool, P, ps, Hkv, D)), dt)

    def layered_attn(q, k, v, t, c, kcur, vcur):
        return _paged_decode_attention_impl(
            q, kp5, vp5, t, c, kcur, vcur, interpret=interpret,
            layer=jnp.int32(1))

    variants = {
        "attn_pallas_layered": layered_attn,
        "attn_xla_gather": lambda q, k, v, t, c, kcur, vcur:
            att.paged_decode_attention_current(q, k, v, t, c, kcur, vcur),
        "attn_pallas_grid": functools.partial(
            _paged_decode_attention_impl, interpret=interpret),
    }

    if args.essential:
        keep = ("attn_pallas_layered", "attn_xla_gather",
                "attn_pallas_grid")
        variants = {k: v for k, v in variants.items() if k in keep}
    detail = {"shape": {"B": B, "Hq": Hq, "Hkv": Hkv, "D": D,
                        "page_size": ps, "table_width": MP,
                        "ctx_tokens": ctx_tokens, "layers": L},
              "platform": jax.devices()[0].platform,
              "note": "ms per single layer-call (multiply by layers for "
                      "per-step attention cost); scan-slope timing"}
    if args.no_decode:
        variants = {}
    for name, fn in variants.items():
        try:
            detail[name + "_ms"] = round(
                _scan_slope(attn_builder(fn), args.n_lo, args.n_hi), 4)
        except Exception as exc:  # noqa: BLE001 — a kernel that fails to
            # lower must not hide the others' numbers
            detail[name + "_ms"] = f"error: {type(exc).__name__}: {exc}"
        _mark(name + "_ms", detail[name + "_ms"])

    # Ragged one-dispatch A/B (XLLM_RAGGED_ATTN): a mixed batch of decode rows +
    # prefill windows served by ONE ragged program vs the SAME rows as
    # two dispatches (decode bucket, then prefill bucket, both through
    # the same kernel) — isolating dispatch fusion from kernel quality.
    if not args.no_decode and not args.essential:
        T_pf = 8 if args.small else 128
        nd = max(1, B // 2)
        npf = max(1, B // 8)
        pt_r, _ = _page_table(nd + npf, ctx_tokens, ps, P)
        q_rag = jnp.asarray(
            rng.normal(size=(nd + npf, T_pf, Hq, D)), dt)
        qs_r = jnp.concatenate([
            jnp.full((nd,), ctx_tokens - 1, jnp.int32),
            jnp.zeros((npf,), jnp.int32)])
        ln_r = jnp.concatenate([
            jnp.ones((nd,), jnp.int32),
            jnp.full((npf,), min(T_pf, ctx_tokens), jnp.int32)])

        def ragged_mixed_build(n):
            @jax.jit
            def run():
                def body(q, _):
                    out = ragged_paged_attention_pallas(
                        q, k_pages, v_pages, pt_r, qs_r, ln_r,
                        interpret=interpret)
                    return out.astype(q.dtype), ()
                q_fin, _ = jax.lax.scan(body, q_rag, None, length=n)
                return q_fin[0, 0, 0]
            return run

        def ragged_split_build(n):
            @jax.jit
            def run():
                def body(q, _):
                    o_dec = ragged_paged_attention_pallas(
                        q[:nd, :1], k_pages, v_pages, pt_r[:nd],
                        qs_r[:nd], ln_r[:nd], interpret=interpret)
                    o_pf = ragged_paged_attention_pallas(
                        q[nd:], k_pages, v_pages, pt_r[nd:],
                        qs_r[nd:], ln_r[nd:], interpret=interpret)
                    q2 = q.at[:nd, :1].set(o_dec.astype(q.dtype))
                    q2 = q2.at[nd:].set(o_pf.astype(q.dtype))
                    return q2, ()
                q_fin, _ = jax.lax.scan(body, q_rag, None, length=n)
                return q_fin[0, 0, 0]
            return run

        for name, build in (("attn_ragged_mixed_ms", ragged_mixed_build),
                            ("attn_ragged_split_ms", ragged_split_build)):
            try:
                detail[name] = round(
                    _scan_slope(build, args.n_lo, args.n_hi), 4)
            except Exception as exc:  # noqa: BLE001 — one failed lower
                # must not hide the other's number
                detail[name] = f"error: {type(exc).__name__}: {exc}"
            _mark(name, detail[name])

    # All-layer KV scatter, as the engine issues it once per decode step.
    k_all = jnp.asarray(rng.normal(size=(L, B, Hkv, D)), dt)
    v_all = jnp.asarray(rng.normal(size=(L, B, Hkv, D)), dt)
    kp_l = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), dt)
    vp_l = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), dt)
    # The last mapped position: page ctx//ps would be NULL (unmapped) and
    # every row would collide on one flat slot — a degenerate scatter,
    # not the engine's per-row distinct-page write.
    positions = jnp.full((B,), ctx_tokens - 1, jnp.int32)
    active = jnp.ones((B,), bool)

    def scatter_build(n):
        @jax.jit
        def run():
            def body(carry, _):
                kp, vp = carry
                kp2, vp2 = att.write_decode_kv_all_layers_xla(
                    kp, vp, k_all, v_all, pt, positions, active)
                return (kp2, vp2), ()
            (kp2, _), _ = jax.lax.scan(body, (kp_l, vp_l), None, length=n)
            return kp2[0, 1, 0, 0, 0]
        return run

    if not args.no_decode:
        detail["kv_scatter_all_layers_ms"] = round(
            _scan_slope(scatter_build, args.n_lo, args.n_hi), 4)
        _mark("kv_scatter_all_layers_ms",
              detail["kv_scatter_all_layers_ms"])

        # The in-place Pallas KV write (serving default on TPU) vs the
        # XLA scatter above — the round-5 fix for the per-step full-pool
        # copies.
        from xllm_service_tpu.ops.pallas.kv_update import paged_kv_update

        def kvk_build(n):
            @jax.jit
            def run():
                def body(carry, _):
                    kp, vp = carry
                    kp2, vp2 = paged_kv_update(
                        kp, vp, k_all, v_all, pt, positions, active,
                        interpret=interpret)
                    return (kp2, vp2), ()
                (kp2, _), _ = jax.lax.scan(body, (kp_l, vp_l), None,
                                           length=n)
                return kp2[0, 1, 0, 0, 0]
            return run

        try:
            detail["kv_update_kernel_ms"] = round(
                _scan_slope(kvk_build, args.n_lo, args.n_hi), 4)
        except Exception as exc:  # noqa: BLE001
            detail["kv_update_kernel_ms"] = \
                f"error: {type(exc).__name__}: {exc}"
        _mark("kv_update_kernel_ms", detail["kv_update_kernel_ms"])

    # lm_head + greedy argmax tail.
    h0 = jnp.asarray(rng.normal(size=(B, D * Hq)), dt)
    head = jnp.asarray(rng.normal(size=(D * Hq, V)), dt)

    def head_build(n):
        @jax.jit
        def run():
            def body(h, _):
                logits = (h @ head).astype(jnp.float32)
                tok = jnp.argmax(logits, axis=-1)
                h2 = h + tok[:, None].astype(h.dtype) * 1e-6
                return h2, ()
            h_fin, _ = jax.lax.scan(body, h0, None, length=n)
            return h_fin[0, 0]
        return run

    if not args.no_decode:
        detail["lm_head_greedy_ms"] = round(
            _scan_slope(head_build, args.n_lo, args.n_hi), 4)
        _mark("lm_head_greedy_ms", detail["lm_head_greedy_ms"])

    if args.prefill:
        detail["prefill"] = _prefill_budget(args, rng)
        if "decode_full_step_ms" in detail["prefill"]:
            detail["decode_full_step_ms"] = \
                detail["prefill"].pop("decode_full_step_ms")

    # Weight-read floor for context: params bytes / HBM bandwidth.
    params_b = 1.24e9 * 2 if not args.small else 0
    detail["weight_read_floor_ms"] = round(params_b / 819e9 * 1e3, 3) \
        if params_b else None

    # "value" must stay numeric for aggregating harnesses even when a
    # kernel failed to lower (its detail entry is an "error: ..." string).
    value = detail.get("attn_pallas_grid_ms", 0)
    if not isinstance(value, (int, float)):
        value = 0
    print(json.dumps({"metric": "decode_budget", "value": value,
                      "unit": "ms/layer-call", "detail": detail}))


if __name__ == "__main__":
    main()
