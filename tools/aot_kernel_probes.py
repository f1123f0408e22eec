"""Offline Mosaic verdicts for EVERY Pallas kernel form (v5e, no chip).

Compiles each kernel form at the bench geometry through the described
topology (tools/aot_tpu.py), so "does Mosaic lower it" is answered
without a chip. The forms on the serving path are also kept among the
tests (tests/test_chip_compile.py); whether a kernel's RESULT is right
when not interpreted is what ``chip_smoke.py``'s parity phase checks.

Prints one verdict line per form (COMPILE OK / FAIL) plus a JSON
summary.
"""

from __future__ import annotations

import functools
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import jax.numpy as jnp

from tools.aot_tpu import aot_compile, sds


_VERDICTS: dict = {}


def _probe(name, fn, args, key=None, **kw):
    """``key`` names the distinct program; forms that trace to the SAME
    program (the prefill window rides as a traced scalar, so "plain" and
    "window" are byte-identical) share one compile and one verdict."""
    key = key or name
    if key not in _VERDICTS:
        try:
            aot_compile(functools.partial(fn, **kw) if kw else fn, args)
            _VERDICTS[key] = (True, "")
        except Exception as e:  # noqa: BLE001 — verdicts, not crashes
            msg = str(e).replace("\n", " ")   # one LINE per verdict
            i = msg.find("Mosaic")
            _VERDICTS[key] = (False, msg[i if i >= 0 else 0:][:300])
    ok, msg = _VERDICTS[key]
    print(f"{name}: COMPILE OK" if ok else f"{name}: FAIL: {msg}")
    return ok


def main() -> int:
    from xllm_service_tpu.ops.pallas.paged_attention import (
        _paged_decode_attention_impl)
    from xllm_service_tpu.ops.pallas.prefill_attention import _impl
    from xllm_service_tpu.ops.pallas.ragged_attention import (
        ragged_paged_attention_pallas)

    results = {}

    # ---- prefill kernel, all model-delta forms (probe geometry) ----
    B, T, Hq, Hkv, D = 2, 256, 32, 8, 64
    P, PS, MP = 64, 64, 8
    q = sds((B, T, Hq, D), jnp.bfloat16)
    kf = sds((B, T, Hkv, D), jnp.bfloat16)
    kp = sds((P, PS, Hkv, D), jnp.bfloat16)
    pt = sds((B, MP), jnp.int32)
    qs = sds((B,), jnp.int32)
    ln = sds((B,), jnp.int32)
    win = sds((1,), jnp.int32)
    sinks = sds((Hq,), jnp.float32)
    scale = 1.0 / (D ** 0.5)
    # The window is a TRACED scalar operand, so "plain"/"window" (and
    # "sinks"/"gptoss window+sinks") trace to identical programs — the
    # key dedupes their compiles while still printing all five verdict
    # lines.
    for name, key, sk, kw in (
            ("plain", "pf-base", None, {}),
            ("window", "pf-base", None, {}),
            ("softcap+scale", "pf-cap", None,
             dict(logits_soft_cap=50.0, scale=0.0625)),
            ("sinks", "pf-sinks", sinks, {}),
            ("gptoss window+sinks", "pf-sinks", sinks, {}),
    ):
        results[f"prefill/{name}"] = _probe(
            f"PREFILL KERNEL [{name}]", _impl,
            (q, kf, kf, kp, kp, pt, qs, ln, win, sk), key=key,
            q_block=64, logits_soft_cap=kw.get("logits_soft_cap", 0.0),
            scale=kw.get("scale", scale), interpret=False)

    # ---- decode kernels, bench geometry ----
    Bd = 64
    qd = sds((Bd, Hq, D), jnp.bfloat16)
    kd = sds((1024, PS, Hkv, D), jnp.bfloat16)
    ptd = sds((Bd, 8), jnp.int32)
    ctx = sds((Bd,), jnp.int32)
    kc = sds((Bd, Hkv, D), jnp.bfloat16)
    winW = sds((1,), jnp.int32)
    q_mla = sds((Bd, 16, 576), jnp.bfloat16)
    k_mla = sds((1024, PS, 1, 576), jnp.bfloat16)
    kc_mla = sds((Bd, 1, 576), jnp.bfloat16)
    for name, fn, args, kw in (
            ("V1 base", _paged_decode_attention_impl,
             (qd, kd, kd, ptd, ctx, kc, kc), dict(interpret=False)),
            ("V1 window", _paged_decode_attention_impl,
             (qd, kd, kd, ptd, ctx, kc, kc, winW, None),
             dict(interpret=False)),
            ("V1 window+sinks", _paged_decode_attention_impl,
             (qd, kd, kd, ptd, ctx, kc, kc, winW, sinks),
             dict(interpret=False)),
            ("V1 MLA shape (Hkv=1 D=576)", _paged_decode_attention_impl,
             (q_mla, k_mla, k_mla, ptd, ctx, kc_mla, kc_mla),
             dict(interpret=False, scale=0.1)),
            ("V1 layered full-pool (L=16)",
             lambda q, kp, vp, pt, c, k1, v1, l:
             _paged_decode_attention_impl(
                 q, kp, vp, pt, c, k1, v1, interpret=False, layer=l),
             (qd, sds((16, 1024, PS, Hkv, D), jnp.bfloat16),
              sds((16, 1024, PS, Hkv, D), jnp.bfloat16), ptd, ctx, kc, kc,
              sds((), jnp.int32)),
             {}),
    ):
        results[f"decode/{name}"] = _probe(name, fn, args, **kw)

    # ---- unified ragged mixed-batch kernel (XLLM_RAGGED_ATTN) ----
    qr = sds((8, 256, Hq, D), jnp.bfloat16)
    ptr = sds((8, MP), jnp.int32)
    qsr = sds((8,), jnp.int32)
    lnr = sds((8,), jnp.int32)
    results["ragged/RAGGED mixed-batch"] = _probe(
        "RAGGED mixed-batch",
        lambda q2, k2, v2, p2, s2, l2: ragged_paged_attention_pallas(
            q2, k2, v2, p2, s2, l2, interpret=False),
        (qr, kd, kd, ptr, qsr, lnr))
    results["ragged/RAGGED window+sinks"] = _probe(
        "RAGGED window+sinks",
        lambda q2, k2, v2, p2, s2, l2, w2, sk2:
        ragged_paged_attention_pallas(
            q2, k2, v2, p2, s2, l2, sliding_window=w2[0], sinks=sk2,
            interpret=False),
        (qr, kd, kd, ptr, qsr, lnr, win, sinks))
    results["ragged/RAGGED softcap+scale"] = _probe(
        "RAGGED softcap+scale",
        lambda q2, k2, v2, p2, s2, l2: ragged_paged_attention_pallas(
            q2, k2, v2, p2, s2, l2, logits_soft_cap=50.0, scale=0.0625,
            interpret=False),
        (qr, kd, kd, ptr, qsr, lnr))
    results["ragged/layered full-pool (L=16)"] = _probe(
        "RAGGED layered full-pool (L=16)",
        lambda q2, k2, v2, p2, s2, l2, ll: ragged_paged_attention_pallas(
            q2, k2, v2, p2, s2, l2, interpret=False, layer=ll),
        (qr, sds((16, 1024, PS, Hkv, D), jnp.bfloat16),
         sds((16, 1024, PS, Hkv, D), jnp.bfloat16), ptr, qsr, lnr,
         sds((), jnp.int32)))

    # ---- layered prefill (full 5D pools + traced layer index) ----
    results["prefill/layered full-pool (L=16)"] = _probe(
        "PREFILL KERNEL [layered full-pool]",
        lambda qq, kff, vff, kpp, vpp, ptt, qss, lnn, ww, ll: _impl(
            qq, kff, vff, kpp, vpp, ptt, qss, lnn, ww, None, ll,
            q_block=64, logits_soft_cap=0.0, scale=scale,
            interpret=False),
        (q, kf, kf, sds((16, P, PS, Hkv, D), jnp.bfloat16),
         sds((16, P, PS, Hkv, D), jnp.bfloat16), pt, qs, ln, win,
         sds((), jnp.int32)))

    # ---- the in-place decode KV write (the scatter replacement) ----
    from xllm_service_tpu.ops.pallas.kv_update import paged_kv_update
    results["decode/kv_update"] = _probe(
        "KV UPDATE (in-place write)",
        lambda kp, vp, knn, vnn, pt, pos, act: paged_kv_update(
            kp, vp, knn, vnn, pt, pos, act, interpret=False),
        (sds((16, 1024, PS, Hkv, D), jnp.bfloat16),
         sds((16, 1024, PS, Hkv, D), jnp.bfloat16),
         sds((16, Bd, Hkv, D), jnp.bfloat16),
         sds((16, Bd, Hkv, D), jnp.bfloat16),
         ptd, ctx, sds((Bd,), jnp.bool_)))

    results["decode/kv_update MLA latent (Hkv=1 D=576)"] = _probe(
        "KV UPDATE @ MLA latent",
        lambda kp, vp, knn, vnn, pt, pos, act: paged_kv_update(
            kp, vp, knn, vnn, pt, pos, act, interpret=False),
        (sds((16, 1024, PS, 1, 576), jnp.bfloat16),
         sds((16, 1024, PS, 1, 576), jnp.bfloat16),
         sds((16, Bd, 1, 576), jnp.bfloat16),
         sds((16, Bd, 1, 576), jnp.bfloat16),
         ptd, ctx, sds((Bd,), jnp.bool_)))

    from xllm_service_tpu.ops.pallas.kv_update import (
        paged_prefill_kv_update)
    for tag, HkvW, DW in (("", Hkv, D), (" MLA latent (Hkv=1 D=576)",
                                         1, 576)):
        results[f"prefill/kv_update{tag}"] = _probe(
            f"PREFILL KV UPDATE{tag.upper() if not tag else ' @ MLA latent'}",
            lambda kp, vp, knn, vnn, pt2, st, lnn: paged_prefill_kv_update(
                kp, vp, knn, vnn, pt2, st, lnn, interpret=False),
            (sds((16, 1024, PS, HkvW, DW), jnp.bfloat16),
             sds((16, 1024, PS, HkvW, DW), jnp.bfloat16),
             sds((16, 32, 128, HkvW, DW), jnp.bfloat16),
             sds((16, 32, 128, HkvW, DW), jnp.bfloat16),
             sds((32, MP), jnp.int32), sds((32,), jnp.int32),
             sds((32,), jnp.int32)))

    # ---- write-then-attend forms: the single-layer (traced layer
    # index) aliased writers and the pool-only prefill attention ----
    from xllm_service_tpu.ops.pallas.kv_update import (
        paged_kv_update_layer, paged_prefill_kv_update_layer)
    lyr = sds((), jnp.int32)
    for tag, HkvW, DW in (("", Hkv, D), (" MLA latent (Hkv=1 D=576)",
                                         1, 576)):
        results[f"decode/kv_update_layer{tag}"] = _probe(
            f"KV UPDATE LAYER (write-then-attend){tag}",
            lambda kp, vp, knn, vnn, pt2, pos, act, ll:
            paged_kv_update_layer(kp, vp, knn, vnn, pt2, pos, act, ll,
                                  interpret=False),
            (sds((16, 1024, PS, HkvW, DW), jnp.bfloat16),
             sds((16, 1024, PS, HkvW, DW), jnp.bfloat16),
             sds((Bd, HkvW, DW), jnp.bfloat16),
             sds((Bd, HkvW, DW), jnp.bfloat16),
             ptd, ctx, sds((Bd,), jnp.bool_), lyr))
        results[f"prefill/kv_update_layer{tag}"] = _probe(
            f"PREFILL KV UPDATE LAYER (write-then-attend){tag}",
            lambda kp, vp, knn, vnn, pt2, st, lnn, ll:
            paged_prefill_kv_update_layer(kp, vp, knn, vnn, pt2, st,
                                          lnn, ll, interpret=False),
            (sds((16, 1024, PS, HkvW, DW), jnp.bfloat16),
             sds((16, 1024, PS, HkvW, DW), jnp.bfloat16),
             sds((32, 128, HkvW, DW), jnp.bfloat16),
             sds((32, 128, HkvW, DW), jnp.bfloat16),
             sds((32, MP), jnp.int32), sds((32,), jnp.int32),
             sds((32,), jnp.int32), lyr))

    results["prefill/pool-only (write-then-attend)"] = _probe(
        "PREFILL KERNEL [pool-only]",
        lambda qq, kpp, vpp, ptt, qss, lnn, ww: _impl(
            qq, None, None, kpp, vpp, ptt, qss, lnn, ww, None,
            q_block=64, logits_soft_cap=0.0, scale=scale,
            interpret=False, from_pool=True),
        (q, kp, kp, pt, qs, ln, win))
    results["prefill/pool-only layered (write-then-attend)"] = _probe(
        "PREFILL KERNEL [pool-only layered]",
        lambda qq, kpp, vpp, ptt, qss, lnn, ww, ll: _impl(
            qq, None, None, kpp, vpp, ptt, qss, lnn, ww, None, ll,
            q_block=64, logits_soft_cap=0.0, scale=scale,
            interpret=False, from_pool=True),
        (q, sds((16, P, PS, Hkv, D), jnp.bfloat16),
         sds((16, P, PS, Hkv, D), jnp.bfloat16), pt, qs, ln, win, lyr))

    print(json.dumps({"aot_target": "v5e (local libtpu topology)",
                      "pass": sum(results.values()),
                      "total": len(results),
                      "results": results}))
    return 0


if __name__ == "__main__":
    raise SystemExit(main())
