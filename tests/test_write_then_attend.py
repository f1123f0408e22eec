"""Write-then-attend KV plumbing (EngineConfig.write_then_attend /
XLLM_WRITE_THEN_ATTEND): the pool rides the layer scan as a carry, each
layer writes its fresh K/V in place BEFORE attending, and attention
reads everything — including the current window/token — from the pool.

Covers: the single-layer aliased writers against the XLA scatter
references (including every drop case), the pool-only prefill kernel
form against the dual-source reference, and engine-level greedy-token
identity with the flag on vs off — the acceptance gate of the
re-plumb."""

import contextlib

import numpy as np
import jax
import jax.numpy as jnp
import pytest

from xllm_service_tpu.ops import attention as att
from xllm_service_tpu.ops.pallas.kv_update import (
    paged_kv_update_layer, paged_prefill_kv_update_layer)


class TestLayerWriters:
    """The traced-layer single-layer writers must match the all-layers
    XLA scatters layer by layer, drops included."""

    def test_decode_layer_writer_matches_scatter(self):
        rng = np.random.default_rng(11)
        L, P, ps, Hkv, D, B, MP = 3, 32, 8, 2, 64, 5, 4
        kp = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
        kn = jnp.asarray(rng.normal(size=(L, B, Hkv, D)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(L, B, Hkv, D)), jnp.float32)
        pt = jnp.asarray(np.arange(1, B * MP + 1).reshape(B, MP),
                         jnp.int32)
        pt = pt.at[1, :].set(0)                    # NULL row → dropped
        pos = jnp.asarray([0, 5, 7, 13, 100], jnp.int32)  # 100 off-table
        act = jnp.asarray([1, 1, 0, 1, 1], bool)          # row 2 inactive
        ref_k, ref_v = att.write_decode_kv_all_layers_xla(
            kp, vp, kn, vn, pt, pos, act)
        got_k, got_v = kp, vp
        for li in range(L):
            got_k, got_v = paged_kv_update_layer(
                got_k, got_v, kn[li], vn[li], pt, pos, act,
                jnp.int32(li), interpret=True)
        assert jnp.array_equal(ref_k, got_k)
        assert jnp.array_equal(ref_v, got_v)
        # The XLA fallback writer agrees too (the wta path's
        # kernel-ineligible branch).
        got_k, got_v = kp, vp
        for li in range(L):
            got_k, got_v = att.write_decode_kv_layer_xla(
                got_k, got_v, kn[li], vn[li], pt, pos, act, jnp.int32(li))
        assert jnp.array_equal(ref_k, got_k)
        assert jnp.array_equal(ref_v, got_v)

    def test_prefill_layer_writer_matches_scatter(self):
        rng = np.random.default_rng(12)
        L, P, ps, Hkv, D, B, T, MP = 3, 32, 8, 2, 16, 4, 16, 6
        kp = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
        kn = jnp.asarray(rng.normal(size=(L, B, T, Hkv, D)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(L, B, T, Hkv, D)), jnp.float32)
        pt = jnp.asarray(np.arange(1, B * MP + 1).reshape(B, MP),
                         jnp.int32)
        pt = pt.at[2, :].set(0)                        # NULL row
        start = jnp.asarray([0, 8, 0, 16], jnp.int32)  # page-aligned
        lens = jnp.asarray([16, 11, 16, 5], jnp.int32)  # ragged tails
        ref_k, ref_v = att.write_prefill_kv_all_layers_xla(
            kp, vp, kn, vn, pt, start, lens)
        got_k, got_v = kp, vp
        for li in range(L):
            got_k, got_v = paged_prefill_kv_update_layer(
                got_k, got_v, kn[li], vn[li], pt, start, lens,
                jnp.int32(li), interpret=True)
        assert jnp.array_equal(ref_k, got_k)
        assert jnp.array_equal(ref_v, got_v)
        got_k, got_v = kp, vp
        for li in range(L):
            got_k, got_v = att.write_prefill_kv_layer_xla(
                got_k, got_v, kn[li], vn[li], pt, start, lens,
                jnp.int32(li))
        assert jnp.array_equal(ref_k, got_k)
        assert jnp.array_equal(ref_v, got_v)

    def test_prefill_layer_writer_unaligned_start_falls_back(self,
                                                             monkeypatch):
        """A mid-page window start must NOT reach the page-granular
        kernel (it would misplace whole pages); a plan whose
        page_aligned is False pins the XLA scatter, which handles any
        alignment — with the writers on, and whatever the environment
        says."""
        from xllm_service_tpu.ops.plan import KernelPlan
        monkeypatch.setenv("XLLM_PALLAS_KV", "1")
        plan = KernelPlan(kv_writers=True, page_aligned=False,
                          interpret=True)
        rng = np.random.default_rng(13)
        L, P, ps, Hkv, D, B, T, MP = 2, 32, 8, 1, 16, 2, 16, 6
        kp = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)), jnp.float32)
        kn = jnp.asarray(rng.normal(size=(L, B, T, Hkv, D)), jnp.float32)
        vn = jnp.asarray(rng.normal(size=(L, B, T, Hkv, D)), jnp.float32)
        pt = jnp.asarray(np.arange(1, B * MP + 1).reshape(B, MP),
                         jnp.int32)
        start = jnp.asarray([4, 20], jnp.int32)        # UNALIGNED
        lens = jnp.asarray([16, 9], jnp.int32)
        ref = att.write_prefill_kv_all_layers_xla(kp, vp, kn, vn, pt,
                                                  start, lens)
        for li in range(L):
            kp, vp = att.write_prefill_kv_layer(
                kp, vp, kn[li], vn[li], pt, start, lens, jnp.int32(li),
                plan)
        assert jnp.array_equal(ref[0], kp)
        assert jnp.array_equal(ref[1], vp)


class TestPoolOnlyPrefillKernel:
    """The from_pool (write-then-attend) form of the prefill attention
    kernel: window K/V pre-written into the pool, no fresh operands,
    ragged tail read through the page table."""

    def _case(self, seed, B, T, Hq, Hkv, D, P, ps, MP, q_starts, lengths,
              q_block=16, **extras):
        from xllm_service_tpu.ops.attention import (
            gather_pages, mha_prefill, write_prefill_kv_all_layers_xla)
        from xllm_service_tpu.ops.pallas.prefill_attention import (
            paged_prefill_attention_pallas)
        rng = np.random.default_rng(seed)
        q = jnp.asarray(rng.normal(size=(B, T, Hq, D)), jnp.float32)
        kf = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
        vf = jnp.asarray(rng.normal(size=(B, T, Hkv, D)), jnp.float32)
        kp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
        vp = jnp.asarray(rng.normal(size=(P, ps, Hkv, D)), jnp.float32)
        # Disjoint tables so each row's window pages are its own.
        pt = jnp.asarray(1 + np.arange(B * MP).reshape(B, MP), jnp.int32)
        q_start = jnp.asarray(q_starts, jnp.int32)
        lens = jnp.asarray(lengths, jnp.int32)
        # Reference: dual-source (pool prefix + fresh overlay).
        k_all = att.overlay_fresh_kv(gather_pages(kp, pt), kf, q_start)
        v_all = att.overlay_fresh_kv(gather_pages(vp, pt), vf, q_start)
        ref = mha_prefill(q, k_all, v_all, q_start + lens, q_start,
                          extras.get("logits_soft_cap", 0.0),
                          extras.get("sliding_window", 0),
                          extras.get("scale"), extras.get("sinks"))
        # Write the window into the pool first, then attend pool-only.
        kp2, vp2 = write_prefill_kv_all_layers_xla(
            kp[None], vp[None], kf[None], vf[None], pt, q_start, lens)
        out = paged_prefill_attention_pallas(
            q, None, None, kp2[0], vp2[0], pt, q_start, lens,
            q_block=q_block, interpret=True, from_pool=True, **extras)
        for b in range(B):
            n = int(lens[b])
            got, want = out[b, :n], ref[b, :n]
            assert jnp.allclose(got, want, atol=2e-5), (
                b, float(jnp.max(jnp.abs(got - want))))

    def test_plain_and_ragged(self):
        self._case(20, B=3, T=32, Hq=8, Hkv=2, D=32, P=32, ps=16, MP=4,
                   q_starts=[0, 16, 0], lengths=[32, 16, 7])

    def test_cached_prefix_and_window(self):
        self._case(21, B=2, T=32, Hq=8, Hkv=2, D=32, P=32, ps=16, MP=6,
                   q_starts=[32, 16], lengths=[32, 20], sliding_window=9)

    def test_softcap_scale_sinks(self):
        rng = np.random.default_rng(22)
        self._case(22, B=2, T=32, Hq=8, Hkv=2, D=32, P=32, ps=16, MP=4,
                   q_starts=[16, 0], lengths=[32, 11],
                   logits_soft_cap=25.0, scale=0.21,
                   sinks=jnp.asarray(rng.normal(size=(8,)), jnp.float32))

    def test_layered_pool_only_matches_sliced(self):
        from xllm_service_tpu.ops.pallas.prefill_attention import (
            paged_prefill_attention_pallas)
        rng = np.random.default_rng(23)
        L, P, ps, Hkv, D, B, T, MP, Hq = 3, 8, 8, 2, 16, 2, 16, 4, 4
        kp5 = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)),
                          jnp.float32)
        vp5 = jnp.asarray(rng.normal(size=(L, P, ps, Hkv, D)),
                          jnp.float32)
        q = jnp.asarray(rng.normal(size=(B, T, Hq, D)), jnp.float32)
        pt = jnp.asarray(1 + rng.integers(0, P - 1, size=(B, MP)),
                         jnp.int32)
        start = jnp.asarray([8, 16], jnp.int32)
        lens = jnp.full((B,), T, jnp.int32)
        for li in range(L):
            ref = paged_prefill_attention_pallas(
                q, None, None, kp5[li], vp5[li], pt, start, lens,
                interpret=True, from_pool=True)
            got = paged_prefill_attention_pallas(
                q, None, None, kp5, vp5, pt, start, lens,
                interpret=True, from_pool=True, layer=jnp.int32(li))
            assert jnp.allclose(ref, got, atol=1e-6), f"layer {li}"


def _run_engine(monkeypatch, env: dict, cfg=None, prompts=None,
                max_tokens=8, ecfg_kw=None):
    from xllm_service_tpu.config import EngineConfig, ModelConfig
    from xllm_service_tpu.runtime.engine import Engine, EngineRequest
    from xllm_service_tpu.utils.types import SamplingParams

    for k, v in env.items():
        monkeypatch.setenv(k, v)
    cfg = cfg or ModelConfig.tiny(vocab_size=256)
    kw = dict(page_size=16, num_pages=64, max_model_len=256,
              max_batch_size=4, max_prefill_tokens=128,
              prefill_buckets=(16, 32, 64))
    kw.update(ecfg_kw or {})
    ecfg = EngineConfig(**kw)
    prompts = prompts or [list(range(1, 33)), [7, 9, 11] * 8]
    sp = SamplingParams(max_tokens=max_tokens, temperature=0.0,
                        ignore_eos=True)
    eng = Engine(cfg, ecfg, seed=0)
    outs = {}
    # Second wave repeats prompt 0 → prefix-cache hit → q_start > 0.
    for wave in (prompts, [prompts[0]]):
        for i, p in enumerate(wave):
            rid = f"r{len(outs)}-{i}"
            eng.add_request(EngineRequest(
                request_id=rid, token_ids=list(p), sampling=sp))
        while eng.has_work():
            for o in eng.step():
                outs.setdefault(o.request_id, []).extend(o.new_token_ids)
    return outs


class TestEngineWriteThenAttend:
    """Greedy generations must be token-identical with the flag on vs
    off — through decode steps, chunked prefill windows, and a
    prefix-cache readmission — on both the Pallas (interpreter) and
    pure-XLA serving paths. The acceptance gate of the re-plumb."""

    @pytest.mark.parametrize("heads", ["by_heads", "flat"])
    def test_identical_generations_pallas_path(self, monkeypatch, caplog,
                                               heads):
        """``flat`` (PR 51): two key-value heads of 128, whose page the
        paged decode kernel reads as one matrix (ops/plan.py
        ``paged_flat_positions``): written in place as [ps, 2, 128] by
        the writer, read as [ps * 2, 128] by the kernel in the same step
        program, with the token in registers (off) and in the pool (on).
        The engine's plan line and the decode launch's span say so."""
        import dataclasses
        import logging

        from xllm_service_tpu.config import ModelConfig
        from xllm_service_tpu.obs import steptrace
        cfg, flat = None, 1
        if heads == "flat":
            # float32: in bfloat16 the two orders' roundings part on a
            # near-tie
            cfg = dataclasses.replace(
                ModelConfig.tiny(vocab_size=256), name="tiny-flat-page",
                hidden_size=512, head_dim=128, dtype="float32")
            flat = 4
        spans = []

        def span(*parts, **args):
            spans.append(("".join(parts), args))
            return contextlib.nullcontext()
        monkeypatch.setattr(steptrace, "span", span)
        base = {"XLLM_PALLAS": "1", "XLLM_PALLAS_PREFILL": "1"}
        with caplog.at_level(logging.INFO,
                             logger="xllm_service_tpu.runtime.engine"):
            off = _run_engine(monkeypatch,
                              dict(base, XLLM_WRITE_THEN_ATTEND="0"),
                              cfg=cfg)
            on = _run_engine(monkeypatch,
                             dict(base, XLLM_WRITE_THEN_ATTEND="1"),
                             cfg=cfg)
        assert set(off) == set(on)
        for rid in off:
            assert off[rid] == on[rid], rid
        lines = [m for m in caplog.messages if m.startswith("engine plan:")]
        assert len(lines) == 2 and all(
            m.endswith(", a page flat, 4 positions a tile") == (flat > 1)
            and "; paged fold " in m for m in lines)
        launches = [a for n, a in spans if n.endswith("dispatch")
                    and a.get("program") == "decode"]
        assert launches and all(a["flat"] == flat for a in launches)

    def test_identical_generations_xla_path(self, monkeypatch):
        base = {"XLLM_PALLAS": "0", "XLLM_PALLAS_PREFILL": "0"}
        off = _run_engine(monkeypatch,
                          dict(base, XLLM_WRITE_THEN_ATTEND="0"))
        on = _run_engine(monkeypatch,
                         dict(base, XLLM_WRITE_THEN_ATTEND="1"))
        assert set(off) == set(on)
        for rid in off:
            assert off[rid] == on[rid], rid

    def test_identical_generations_swa(self, monkeypatch):
        """Sliding-window model (windowed masks + O(W) page trimming)
        through the wta path."""
        import dataclasses

        from xllm_service_tpu.config import ModelConfig
        cfg = dataclasses.replace(ModelConfig.tiny(vocab_size=256),
                                  name="tiny-swa-wta", sliding_window=24)
        base = {"XLLM_PALLAS": "1", "XLLM_PALLAS_PREFILL": "1"}
        off = _run_engine(monkeypatch,
                          dict(base, XLLM_WRITE_THEN_ATTEND="0"),
                          cfg=cfg, max_tokens=16)
        on = _run_engine(monkeypatch,
                         dict(base, XLLM_WRITE_THEN_ATTEND="1"),
                         cfg=cfg, max_tokens=16)
        assert set(off) == set(on)
        for rid in off:
            assert off[rid] == on[rid], rid

    def test_swa_window_walk_matches_reference_plan(self, monkeypatch,
                                                    caplog):
        """A uniform-window engine past W + two pages, pages trimmed, its
        table wider than the window's span: the kernel plan (interpreted)
        walks the span's columns and not the table's, and generates the
        reference plan's tokens."""
        import contextlib
        import dataclasses
        import logging

        from xllm_service_tpu.config import EngineConfig, ModelConfig
        from xllm_service_tpu.obs import steptrace
        from xllm_service_tpu.ops.plan import paged_fold_pages
        from xllm_service_tpu.runtime.engine import Engine, EngineRequest
        from xllm_service_tpu.utils.types import SamplingParams

        W, ps = 16, 8
        # float32: in bfloat16 the two plans' roundings part on a near-tie
        cfg = dataclasses.replace(ModelConfig.tiny(vocab_size=256),
                                  name="tiny-swa-walk", sliding_window=W,
                                  dtype="float32")
        ecfg = EngineConfig(page_size=ps, num_pages=64, max_model_len=128,
                            max_batch_size=2, max_prefill_tokens=64,
                            prefill_buckets=(16, 32))
        dispatched = []

        def span(*parts, **args):
            if "".join(parts) in ("xllm.step.decode.dispatch",
                                  "xllm.step.decode.tail_dispatch"):
                dispatched.append(args)
            return contextlib.nullcontext()
        monkeypatch.setattr(steptrace, "span", span)

        def generate(pallas):
            monkeypatch.setenv("XLLM_PALLAS", pallas)
            eng = Engine(cfg, ecfg, seed=0)
            assert eng.plan.decode_attn == (pallas == "1")
            sp = SamplingParams(max_tokens=W + 3 * ps, temperature=0.0,
                                ignore_eos=True)
            for i, p in enumerate([list(range(1, 21)), [7, 9, 11] * 4]):
                eng.add_request(EngineRequest(
                    request_id=f"r{i}", token_ids=p, sampling=sp))
            outs, trimmed = {}, 0
            del dispatched[:]
            while eng.has_work():
                for o in eng.step():
                    outs.setdefault(o.request_id, []).extend(
                        o.new_token_ids)
                trimmed = max([trimmed] + [s.num_trimmed
                                           for s in eng.running])
            assert trimmed >= 3                  # _swa_trim was active
            return outs, list(dispatched)

        with caplog.at_level(logging.INFO,
                             logger="xllm_service_tpu.runtime.engine"):
            ref, ref_args = generate("0")
            got, got_args = generate("1")
        assert ref == got
        span_cols = W // ps + 1

        def fold(walk):
            """Pages a grid step of the kernel folds at the tiny model's
            pools (float32, ``num_kv_heads`` heads of ``head_dim``)."""
            return paged_fold_pages(ps, cfg.num_kv_heads, cfg.head_dim, 4,
                                    walk)
        # the plan's log line states the walk once per engine, and the
        # block of pages a grid step where the kernel serves (PR 46)
        assert [m.split("; decode walk ", 1)[1] for m in caplog.messages
                if m.startswith("engine plan:")] == [
            "16 of 16 columns",
            f"{span_cols} of 16 columns; paged fold {fold(span_cols)} "
            f"pages a grid step, {-(-span_cols // fold(span_cols))} steps "
            f"of {span_cols} columns"]
        assert 1 < fold(span_cols) <= span_cols
        wide = [a for a in got_args if a["MP"] > span_cols]
        assert wide and all(a["walk"] == span_cols for a in wide)
        assert all(a["walk"] == min(a["MP"], span_cols) for a in got_args)
        assert all(a["fold"] == fold(a["walk"]) for a in got_args)
        # the XLA reference gathers the whole table: no kernel folds
        assert all(a["walk"] == a["MP"] and a["fold"] == 1
                   for a in ref_args)

    @pytest.mark.parametrize("pallas", ["0", "1"], ids=["xla", "kernels"])
    def test_a_prefill_table_is_clamped_to_the_sequences_pages(
            self, monkeypatch, pallas):
        """Under write-then-attend a prefill's table is no wider than
        ``max_pages_per_seq`` (6 pages here, where the power of two over
        them is 8). A cached late start whose bucket overshoots the
        table (start 80, bucket 32: positions to 112 of 96) writes and
        attends what the overlay's full-width table does."""
        from xllm_service_tpu.runtime.engine import Engine
        widths = []
        real = Engine._prefill_table_width

        def spy(self, pages):
            widths.append(real(self, pages))
            return widths[-1]
        monkeypatch.setattr(Engine, "_prefill_table_width", spy)
        kw = dict(max_model_len=96, max_prefill_tokens=96,
                  prefill_buckets=(32, 64, 96))
        doc = [(7 * i + 3) % 251 for i in range(80)]
        prompts = [doc + [5, 6, 7, 8, 9, 10, 11, 12, 13, 14], doc + [99]]
        env = {"XLLM_PALLAS": pallas, "XLLM_PALLAS_INTERPRET": "1"}
        on = _run_engine(monkeypatch, dict(env, XLLM_WRITE_THEN_ATTEND="1"),
                         prompts=prompts, max_tokens=4, ecfg_kw=kw)
        clamped, widths[:] = sorted(set(widths)), []
        off = _run_engine(monkeypatch, dict(env, XLLM_WRITE_THEN_ATTEND="0"),
                          prompts=prompts, max_tokens=4, ecfg_kw=kw)
        assert on == off
        assert max(clamped) == 6 and max(widths) == 8

    def test_env_flag_reaches_the_engines_plan(self, monkeypatch):
        """The variable lands in the plan of an engine built under it
        (tests/test_kernel_plan.py holds the resolver's whole table),
        not in the configuration."""
        from xllm_service_tpu.config import EngineConfig, ModelConfig
        from xllm_service_tpu.runtime.engine import Engine
        ecfg = EngineConfig(page_size=16, num_pages=32, max_model_len=64)
        monkeypatch.setenv("XLLM_PALLAS", "0")
        monkeypatch.setenv("XLLM_WRITE_THEN_ATTEND", "1")
        on = Engine(ModelConfig.tiny(), ecfg)
        assert on.plan.write_then_attend and ecfg.write_then_attend is None
        monkeypatch.setenv("XLLM_WRITE_THEN_ATTEND", "0")
        assert not Engine(ModelConfig.tiny(), ecfg,
                          params=on.params).plan.write_then_attend
        assert on.plan.write_then_attend


class TestMlaWriteThenAttend:
    """MLA (latent-pool) forward parity with the flag on vs off, plus
    the page_aligned_prefill regression (advisor bugfix): an MLA config
    with non-page-multiple prefill buckets produces UNALIGNED window
    starts mid-prompt, which must keep the kernel-free scatter instead
    of corrupting the pool via page-granular writes."""

    def _mla_cfg(self):
        from xllm_service_tpu.config import ModelConfig
        return ModelConfig(
            name="tiny-mla", vocab_size=128, hidden_size=32,
            intermediate_size=64, num_layers=2, num_heads=4,
            num_kv_heads=4, kv_lora_rank=16, qk_rope_head_dim=8,
            qk_nope_head_dim=16, v_head_dim=16, dtype="float32")

    def _forward(self, wta, start, T, aligned, kernels=True):
        from xllm_service_tpu.models import transformer
        from xllm_service_tpu.ops.plan import KernelPlan
        plan = KernelPlan(decode_attn=kernels, kv_writers=kernels,
                          write_then_attend=wta, page_aligned=aligned,
                          interpret=True)
        cfg = self._mla_cfg()
        params = transformer.init_params(cfg, jax.random.PRNGKey(1))
        kv = transformer.init_kv_cache(cfg, 16, 8, jnp.float32)
        rng = np.random.default_rng(7)
        B = 2
        toks = jnp.asarray(rng.integers(1, 127, size=(B, T)), jnp.int32)
        starts = jnp.asarray([0, start], jnp.int32)
        lens = jnp.asarray([T, T - 3], jnp.int32)
        pt = jnp.asarray(np.arange(1, B * 6 + 1).reshape(B, 6), jnp.int32)
        last, _, kv2 = transformer.forward_prefill(
            params, cfg, toks, starts, lens, kv, pt, plan=plan)
        assert len(kv2) == 1        # the one latent pool
        return (np.asarray(last), np.asarray(kv2[0]))

    def test_mla_wta_matches_baseline(self):
        base = self._forward(wta=False, start=8, T=16,
                             aligned=True, kernels=False)
        got = self._forward(wta=True, start=8, T=16,
                            aligned=True)
        for a, b in zip(base, got):
            assert np.max(np.abs(a - b)) < 2e-4

    def test_mla_misaligned_bucket_uses_scatter(self):
        """start_pos=20 on 8-token pages (a 20-token bucket's second
        window): before page_aligned_prefill was threaded through
        _mla_forward_prefill, the kernel path engaged with the
        unaligned start and silently corrupted the pool."""
        base = self._forward(wta=False, start=20, T=16,
                             aligned=False, kernels=False)
        got = self._forward(wta=False, start=20, T=16,
                            aligned=False)
        for a, b in zip(base, got):
            assert np.max(np.abs(a - b)) < 2e-4
        got_wta = self._forward(wta=True, start=20, T=16,
                                aligned=False)
        for a, b in zip(base, got_wta):
            assert np.max(np.abs(a - b)) < 2e-4
