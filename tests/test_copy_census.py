"""The HLO copy-census probe (tools/aot_copy_census.py) as a tier-1
check: future PRs cannot silently reintroduce KV-pool copies around the
attention/writer custom calls or the jit-call boundary.

Two tiers inside one file:
- pure text-parsing units (always run, no compiler);
- real v5e AOT assertions through the local-libtpu topology
  (tools/aot_tpu.py; runtime stays the pinned CPU) — skipped cleanly
  when the image has no usable libtpu/topology, so the suite stays
  green on CPU-only environments while asserting for real wherever the
  AOT path exists.
"""

import os

import jax.numpy as jnp

from tools.aot_copy_census import census_pool_copies

POOL = (2, 32, 64, 8, 64)


class TestCensusParser:
    def test_counts_pool_sized_copies_only(self):
        hlo = """
ENTRY %main (p0: bf16[2,32,64,8,64]) -> bf16[2,32,64,8,64] {
  %copy.1 = bf16[2,32,64,8,64]{4,3,2,1,0:T(8,128)(2,1)} copy(%p0)
  %copy.2 = bf16[2,32,64,8,64]{2,4,3,1,0:T(8,128)(2,1)} copy(%copy.1)
  %copy.3 = f32[64,8,64]{2,1,0} copy(%other)
  %add.1 = bf16[2,32,64,8,64]{4,3,2,1,0} add(%copy.1, %copy.2)
}
"""
        hits = census_pool_copies(hlo, POOL)
        assert len(hits) == 2          # the small copy and the add don't count

    def test_async_copy_counts_start_only(self):
        hlo = """
  %cs = (bf16[2,32,64,8,64]{4,3,2,1,0}, u32[]) copy-start(%p0)
  %cd = bf16[2,32,64,8,64]{4,3,2,1,0} copy-done(%cs)
"""
        # copy-done would double-count the same physical copy.
        assert len(census_pool_copies(hlo, POOL)) == 1

    def test_zero_on_clean_text(self):
        assert census_pool_copies("%fusion.1 = bf16[8,8]{1,0} fusion()",
                                  POOL) == []

    def test_alternate_memory_prefetch_excluded(self):
        # An S(1) (alternate-memory-space) copy is XLA prefetching a
        # toy-sized pool into faster memory — an optimization, not the
        # defensive HBM copy class under test.
        hlo = ("%cs = (bf16[2,32,64,8,64]{4,3,2,1,0:T(8,128)(2,1)S(1)}, "
               "bf16[2,32,64,8,64]{4,3,2,1,0:T(8,128)(2,1)}, u32[]{:S(2)})"
               " copy-start(bf16[2,32,64,8,64]{4,3,2,1,0} %p)")
        assert census_pool_copies(hlo, POOL) == []


def test_census_plan_is_what_an_engine_resolves(monkeypatch):
    """The kernel mix the census compiles (``cc.census_plan``: aliased
    Pallas writers + XLA attention, REAL Mosaic lowering) is a plan an
    engine can be given: what XLLM_PALLAS=0 XLLM_PALLAS_KV=1
    XLLM_PALLAS_INTERPRET=0 resolve to."""
    import tools.aot_copy_census as cc
    from xllm_service_tpu.config import EngineConfig, ModelConfig
    from xllm_service_tpu.ops.plan import KernelPlan
    monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "0")
    monkeypatch.setenv("XLLM_PALLAS", "0")
    monkeypatch.setenv("XLLM_PALLAS_KV", "1")
    for wta in (True, False):
        assert cc.census_plan(wta) == KernelPlan.from_env(
            ModelConfig.tiny(),
            EngineConfig(page_size=64, num_pages=32, max_model_len=128,
                         prefill_buckets=(64,), write_then_attend=wta))


class TestCensusAot:
    def test_positive_control_undonated_writer_copies(self, aot):
        """An UN-donated aliased write forces XLA to copy both pools —
        the census must see them, or a zero result proves nothing."""
        aot_compile, sds = aot
        from xllm_service_tpu.ops.pallas.kv_update import paged_kv_update
        L, P, PS, Hkv, D, B, MP = POOL[0], POOL[1], POOL[2], POOL[3], \
            POOL[4], 4, 2
        args = (sds(POOL, jnp.bfloat16), sds(POOL, jnp.bfloat16),
                sds((L, B, Hkv, D), jnp.bfloat16),
                sds((L, B, Hkv, D), jnp.bfloat16),
                sds((B, MP), jnp.int32), sds((B,), jnp.int32),
                sds((B,), jnp.bool_))

        def write(kp, vp, kn, vn, pt, pos, act):
            return paged_kv_update(kp, vp, kn, vn, pt, pos, act,
                                   interpret=False)

        undonated = aot_compile(write, args)
        assert len(census_pool_copies(undonated.as_text(), POOL)) >= 2
        donated = aot_compile(write, args, donate_argnums=(0, 1))
        assert census_pool_copies(donated.as_text(), POOL) == []

    def test_decode_step_zero_pool_copies_wta(self, aot):
        """The real (tiny-shaped, structurally identical) decode step
        with write_then_attend on: ZERO pool-sized copies anywhere in
        the optimized HLO — loop bodies and the call boundary."""
        aot_compile, _ = aot
        import tools.aot_copy_census as cc
        progs = cc.build_programs(tiny=True)
        fn, args, donate, pool_shape = progs["decode_single"]
        kw = cc._kv_layout_kwargs(args, donate, cc._N_OUT["decode_single"])
        compiled = aot_compile(fn, args, donate_argnums=donate, **kw)
        hits = census_pool_copies(compiled.as_text(), pool_shape)
        assert hits == [], hits

    def test_prefill_zero_pool_copies_wta(self, aot):
        aot_compile, _ = aot
        import tools.aot_copy_census as cc
        progs = cc.build_programs(tiny=True)
        fn, args, donate, pool_shape = progs["prefill"]
        kw = cc._kv_layout_kwargs(args, donate, cc._N_OUT["prefill"])
        compiled = aot_compile(fn, args, donate_argnums=donate, **kw)
        hits = census_pool_copies(compiled.as_text(), pool_shape)
        assert hits == [], hits

    def test_ragged_zero_pool_copies(self, aot):
        """The ragged mixed-batch program (XLLM_RAGGED_ATTN): ONE
        dispatch serving decode rows + prefill windows must keep the
        prefill program's guarantees — pools donated straight through,
        ZERO pool-sized copies in the optimized HLO."""
        aot_compile, _ = aot
        import tools.aot_copy_census as cc
        progs = cc.build_programs(tiny=True)
        fn, args, donate, pool_shape = progs["ragged"]
        kw = cc._kv_layout_kwargs(args, donate, cc._N_OUT["ragged"])
        compiled = aot_compile(fn, args, donate_argnums=donate, **kw)
        hits = census_pool_copies(compiled.as_text(), pool_shape)
        assert hits == [], hits

    def test_restore_scatter_zero_pool_copies(self, aot):
        """The spill-tier restore / cross-worker block-adopt scatter
        (engine ``_jit_kv_scatter``, shared with PD import): donated and
        pinned like the step programs — the aliased in-place write must
        compile with ZERO pool-sized copies, or every prefix restore
        pays a pool-sized bill that dwarfs what it saved. (Unpinned, the
        chip hands a [.., 128, 8, 64] pool back in its own default
        layout: pools the pinned step programs then refuse.)"""
        aot_compile, sds = aot
        from xllm_service_tpu.runtime.engine import (
            _kv_scatter as restore, row_major_format)
        L, P, ps, Hkv, D = POOL
        n = 2       # restored blocks per call; structurally identical
        #             at any count (the engine caches per distinct n)

        blk = sds((L, n, ps, Hkv, D), jnp.bfloat16)
        args = ((sds(POOL, jnp.bfloat16), sds(POOL, jnp.bfloat16)),
                sds((n,), jnp.int32), (blk, blk))
        pin = tuple(row_major_format(5, a.sharding) for a in args[0])
        compiled = aot_compile(restore, args, donate_argnums=(0,),
                               in_shardings=(pin, None, None),
                               out_shardings=pin)
        hits = census_pool_copies(compiled.as_text(), POOL)
        assert hits == [], hits
