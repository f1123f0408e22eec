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

import math
import os

import jax.numpy as jnp

import pytest

from tools.aot_copy_census import (census_layer_results,
                                   census_pool_copies,
                                   census_weight_relayouts)

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


# A stack of 4 projection weights of 256 x 512 and one layer's matrix.
WEIGHTS = [(4, 256, 512), (256, 512)]


class TestWeightCensusParser:
    def test_a_slice_into_a_temporary_and_its_transposed_copy_count(self):
        """The parent's form (PERF.md, PR 44): a layer's matrix sliced out
        of the stack into S(1), then copied into another order. The
        alternate memory space excuses neither."""
        hlo = """
%fused_computation.82 (p0: bf16[4,256,512], p1: s32[]) -> bf16[1,256,512] {
  %p0 = bf16[4,256,512]{2,1,0:T(8,128)(2,1)} parameter(0)
  ROOT %ds = bf16[1,256,512]{2,1,0:T(8,128)(2,1)S(1)} dynamic-slice(%p0, %p1, %c, %c), dynamic_slice_sizes={1,256,512}
}

%body (arg: (s32[], bf16[4,256,512])) -> (s32[], bf16[4,256,512]) {
  %constant_dynamic-slice_fusion.6 = bf16[1,256,512]{2,1,0:T(8,128)(2,1)S(1)} fusion(%w, %i), kind=kLoop, calls=%fused_computation.82
  %copy.44 = bf16[1,256,512]{1,2,0:T(8,128)(2,1)S(1)} copy(%constant_dynamic-slice_fusion.6)
}
"""
        assert census_weight_relayouts(hlo, WEIGHTS) == [
            "fusion 1,256,512 {2,1,0}", "copy 1,256,512 {1,2,0}"]

    def test_a_whole_stack_copied_outside_the_loop_counts(self):
        hlo = ("ENTRY %main (w: bf16[4,256,512]) -> bf16[8,512] {\n"
               "  %copy.17 = bf16[4,256,512]{1,2,0:T(8,128)(2,1)} "
               "copy(%w)\n"
               "  %transpose.3 = bf16[4,512,256]{2,1,0} transpose(%w), "
               "dimensions={0,2,1}\n}\n")
        assert len(census_weight_relayouts(hlo, WEIGHTS)) == 2

    def test_a_slice_fused_into_its_product_is_clean(self):
        """The feed-forward's form, and the cured projections': the
        dynamic-slice (and a fusion nested around it) lives in the
        product's own fusion, whose result is an activation. Nothing in
        a fusion's body is materialized, so nothing there counts."""
        hlo = """
%fused_computation.5 (p0: bf16[4,256,512], p1: s32[]) -> bf16[256,512] {
  %ds = bf16[1,256,512]{2,1,0} dynamic-slice(%p0, %p1, %c, %c), dynamic_slice_sizes={1,256,512}
  ROOT %b = bf16[256,512]{1,0} bitcast(%ds)
}

%fused_computation.12 (p0: bf16[8,256], p1: bf16[4,256,512], p2: s32[]) -> bf16[8,512] {
  %fusion.109 = bf16[256,512]{1,0:T(8,128)(2,1)} fusion(%p1, %p2), kind=kLoop, calls=%fused_computation.5
  %copy.9 = bf16[256,512]{0,1} copy(%fusion.109)
  ROOT %convolution.31 = bf16[8,512]{1,0} convolution(%p0, %copy.9), dim_labels=bf_io->bf
}

%body (arg: (s32[], bf16[4,256,512])) -> (s32[], bf16[4,256,512]) {
  %fusion.134 = bf16[8,512]{1,0:T(8,128)(2,1)S(1)} fusion(%x, %w, %i), kind=kOutput, calls=%fused_computation.12
}
"""
        assert census_weight_relayouts(hlo, WEIGHTS) == []

    def test_other_sizes_and_other_fusions_do_not_count(self):
        hlo = ("%body (a: s32[]) -> s32[] {\n"
               # weight-sized, but no dynamic-slice in what it calls
               "  %fusion.7 = bf16[256,512]{1,0} fusion(%a), kind=kLoop, "
               "calls=%fused_computation.1\n"
               # a copy of an activation
               "  %copy.2 = bf16[8,512]{0,1} copy(%x)\n}\n")
        assert census_weight_relayouts(hlo, WEIGHTS) == []

    def test_the_prefetch_of_an_unstacked_weight_is_excused(self):
        """A copy-start into an alternate space that keeps the order of
        the dimensions is that weight's one read; one that changes the
        order, or lands in default memory, is a relayout."""
        tail = ("bf16[1,256,512]{2,1,0:T(8,128)(2,1)}, u32[]{:S(2)}) "
                "copy-start(%p)\n}\n")
        head = "ENTRY %main (p: bf16[1,256,512]) -> bf16[8,512] {\n  %cs = ("
        prefetch = (head + "bf16[1,256,512]{2,1,0:T(8,128)(2,1)S(1)}, "
                    + tail)
        reordered = (head + "bf16[1,256,512]{1,2,0:T(8,128)(2,1)S(1)}, "
                     + tail)
        in_hbm = head + "bf16[1,256,512]{2,1,0:T(8,128)(2,1)}, " + tail
        assert census_weight_relayouts(prefetch, WEIGHTS) == []
        assert len(census_weight_relayouts(reordered, WEIGHTS)) == 1
        assert len(census_weight_relayouts(in_hbm, WEIGHTS)) == 1


class TestLayerCensusParser:
    """``census_layer_results``: what in a program is as large as ONE
    LAYER of a pool (PR 47). The text below is the Mistral cell's
    parent program in small: a layer sliced out of each pool in front of
    the gather."""

    POOLS = [(4, 768, 128, 8, 128), (4, 768, 128, 8, 128)]
    PARENT = """
%fused_computation.61 (p0: bf16[4,768,128,8,128], p1: s32[]) -> bf16[768,128,8,128] {
  %p0 = bf16[4,768,128,8,128]{4,3,2,1,0:T(8,128)(2,1)} parameter(0)
  %ds = bf16[1,768,128,8,128]{4,3,2,1,0:T(8,128)(2,1)} dynamic-slice(%p0, %p1, %c, %c, %c, %c), dynamic_slice_sizes={1,768,128,8,128}
  ROOT %b = bf16[768,128,8,128]{3,2,1,0:T(8,128)(2,1)} bitcast(%ds)
}

%body (arg: (s32[], bf16[4,768,128,8,128], bf16[4,768,128,8,128])) -> (s32[], bf16[4,768,128,8,128], bf16[4,768,128,8,128]) {
  %k = bf16[4,768,128,8,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %dynamic-slice_bitcast_fusion.4 = bf16[768,128,8,128]{3,2,1,0:T(8,128)(2,1)} fusion(%k, %i), kind=kLoop, calls=%fused_computation.61
  %gather.47 = bf16[64,128,8,128]{3,2,1,0:T(8,128)(2,1)} gather(%dynamic-slice_bitcast_fusion.4, %idx), offset_dims={1,2,3}
}
"""

    def test_the_slice_in_front_of_the_gather_counts_once(self):
        """The fusion's RESULT is materialized and counts; the
        dynamic-slice and the bitcast in its body are its arithmetic."""
        assert census_layer_results(self.PARENT, self.POOLS) == [
            "fusion 768,128,8,128"]

    def test_a_gather_straight_off_the_pool_is_clean(self):
        hlo = """
%body (arg: (s32[], bf16[4,768,128,8,128])) -> (s32[], bf16[4,768,128,8,128]) {
  %k = bf16[4,768,128,8,128]{4,3,2,1,0:T(8,128)(2,1)} get-tuple-element(%arg), index=1
  %gather.47 = bf16[64,128,8,128]{3,2,1,0:T(8,128)(2,1)} gather(%k, %idx), offset_dims={1,2,3}, collapsed_slice_dims={0,1}
}
"""
        assert census_layer_results(hlo, self.POOLS) == []

    @pytest.mark.parametrize("line, counted", [
        # whatever made it counts, in any memory space, in any order of
        # its dimensions, under any split of them
        ("%copy.9 = bf16[768,128,8,128]{2,3,1,0:T(8,128)(2,1)} copy(%x)",
         ["copy 768,128,8,128"]),
        ("%f = bf16[1,768,128,1024]{3,2,1,0:T(8,128)(2,1)S(1)} fusion(%x), "
         "kind=kLoop, calls=%fc.1", ["fusion 1,768,128,1024"]),
        ("%cs = (bf16[768,128,8,128]{3,2,1,0}, bf16[768,128,8,128]{3,2,1,0}"
         ", u32[]{:S(2)}) copy-start(%x)", ["copy-start 768,128,8,128"]),
        # names for bytes that are there already
        ("%p = bf16[768,128,8,128]{3,2,1,0} parameter(0)", []),
        ("%b = bf16[98304,8,128]{2,1,0} bitcast(%x)", []),
        ("%g = bf16[768,128,8,128]{3,2,1,0} get-tuple-element(%t), index=2",
         []),
        # the whole pool, a window's rows, the gathered pages
        ("%c = bf16[4,768,128,8,128]{4,3,2,1,0} copy(%x)", []),
        ("%c = bf16[64,128,8,128]{3,2,1,0} copy(%x)", []),
    ])
    def test_what_counts(self, line, counted):
        hlo = "%body (a: s32[]) -> s32[] {\n  " + line + "\n}\n"
        assert census_layer_results(hlo, self.POOLS) == counted


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
    # ... and the weight census's (``cc.chip_plan``) is what the
    # benchmark's dense cells resolve on the chip: every kernel on, page
    # 128 under the default bucket ladder.
    monkeypatch.setenv("XLLM_PALLAS", "1")
    monkeypatch.delenv("XLLM_PALLAS_KV")
    for cfg in (ModelConfig.tiny(), _cell_config("lfm2-24b-a2b"),
                _cell_config("joyai-llm-flash", 0),
                _cell_config("falcon-h1-34b", 0),
                _cell_config("solar-open2-250b", 0)):
        assert cc.chip_plan(cfg) == KernelPlan.from_env(
            cfg, EngineConfig(page_size=128, num_pages=64,
                              max_model_len=8192, max_batch_size=8))


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


# The benchmark's cells whose layers run ``transformer._qkv``, at their
# published widths and few layers: layers (0: the benchmark's own cut),
# pool pages, page-table width, decode rows.
CELLS = {
    "mistral-7b-v01": (4, 64, 64, 8),
    # 4 passes over the same 4 layers: the pass scan around the layer
    # scan is what lets the compiler hoist a copy of the WHOLE stack.
    "ouro-2.6b": (4, 40, 8, 8),
    # Two attention layers among convolutions, heads of 64 packed two to
    # a row of pools tiled (4, 128); sparse experts.
    "lfm2-24b-a2b": (0, 256, 96, 64),
}
DENSE_CELLS = ["mistral-7b-v01", "ouro-2.6b"]


def _cell_config(name, layers=None):
    """The benchmark's own config.json, cut in depth alone."""
    import json

    from xllm_service_tpu.config import ModelConfig
    if layers is None:
        layers = CELLS[name][0]
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "chipbench", "configs", name,
        "config.json")
    with open(path) as f:
        hf = json.load(f)
    if layers:
        hf["num_hidden_layers"] = layers
        if "layer_types" in hf:
            hf["layer_types"] = hf["layer_types"][:layers]
    return ModelConfig.from_hf_config(hf, name)


def _census(aot, name, program):
    """(weight relayouts, pool copies) of one program of a cell
    (tools/aot_copy_census.py ``build_cell_programs``)."""
    import tools.aot_copy_census as cc
    aot_compile, _ = aot
    _, pages, table_width, batch = CELLS[name]
    programs, weights, pools = cc.build_cell_programs(
        _cell_config(name), pages, table_width, batch)
    fn, args, jit_kw = programs[program]
    text = aot_compile(fn, args, **jit_kw).as_text()
    return (census_weight_relayouts(text, weights),
            [hit for pool in set(pools)
             for hit in census_pool_copies(text, pool)])


@pytest.mark.parametrize("program", ["decode", "prefill"])
@pytest.mark.parametrize("cell", DENSE_CELLS)
class TestWeightCensusAot:
    """No compiled dense step program slices a q/k/v weight into a
    temporary or copies one into another layout (PR 44; the cure is
    ``transformer._pin``, flat): Mistral's widths, where the compiler
    did it a layer at a time, and the looped model's, where it copied
    each stack whole once a step."""

    def test_projections_read_their_weights_where_they_lie(
            self, aot, cell, program):
        weights, pools = _census(aot, cell, program)
        assert weights == [] and pools == [], (weights, pools)

    def test_positive_control_products_then_reshape(
            self, aot, cell, program, monkeypatch):
        """The parent's form, three products and then the reshape to
        heads, patched in: the census must find q's, k's and v's
        relayouts, or the zero above proves nothing."""
        from xllm_service_tpu.models import transformer
        monkeypatch.setattr(transformer, "_pin", lambda values: values)
        hits, _ = _census(aot, cell, program)
        assert len(hits) >= 3, hits
        assert any(h.startswith("copy") for h in hits), hits


class TestHybridCellAot:
    """The same cure in the hybrid cell's two attention layers moves
    neither a weight nor a POOL (PR 44; ``transformer._pin``, in
    heads)."""

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_no_weight_and_no_pool_moves(self, aot, program):
        weights, pools = _census(aot, "lfm2-24b-a2b", program)
        # 256 x 4 x 8 x 128 values of the prefill's attention have a
        # weight's element count (2048 x 512) and hold a dynamic-slice.
        weights = [h for h in weights if not h.startswith("fusion 1,256,4,")]
        assert weights == [] and pools == [], (weights, pools)

    def test_positive_control_pinned_flat_alone(self, aot, monkeypatch):
        """Pinned flat and not in heads (this PR's first form, which cost
        the cell a fifth of its ``out_tok_s`` on the chip): the prefill
        program carries a pool through its layer loop in another layout
        and copies it whole, in, out and around each attention layer."""
        import jax

        from xllm_service_tpu.models import transformer

        def flat_alone(values):
            leaves = jax.tree_util.tree_leaves(values)
            return (jax.lax.optimization_barrier(values)
                    if leaves[0].ndim == 3 else values)
        monkeypatch.setattr(transformer, "_pin", flat_alone)
        # ... under the read of that day, a layer sliced out of the pool
        # in front of the gather: with the pages gathered out of the
        # whole pool (PR 47) the order the attention wants no longer
        # reaches the carried pool, and this form moves none either.
        monkeypatch.setattr(transformer, "gather_layer_pages",
                            _slice_then_gather)
        weights, pools = _census(aot, "lfm2-24b-a2b", "prefill")
        assert len(pools) >= 2, pools
        monkeypatch.undo()
        monkeypatch.setattr(transformer, "_pin", flat_alone)
        assert _census(aot, "lfm2-24b-a2b", "prefill")[1] == []


class TestStatePoolAot:
    """The family with a mixer beside attention (PR 45): no step program
    holds a copy of the pool of matrix states by slot ([6, 97, 32, 128,
    256] float32, 2.44 GB), nor of the pages' pools, and no projection
    of either branch is relaid. The state update is the Pallas kernel of
    ops/pallas/ssm_update.py, which maps a row's block by its slot and
    aliases the pool; XLA's own gather-and-scatter form compiles beside
    it as the reading it was chosen over."""

    PAGES, WIDTH, BATCH, SLOTS = 256, 16, 32, 97

    def _programs(self, plan=None):
        import json

        import tools.aot_copy_census as cc
        from xllm_service_tpu.config import ModelConfig
        path = os.path.join(os.path.dirname(os.path.dirname(
            os.path.abspath(__file__))), "chipbench", "configs",
            "falcon-h1-34b", "config.json")
        with open(path) as f:
            cfg = ModelConfig.from_hf_config(json.load(f), "falcon-h1-34b")
        return cfg, cc.build_cell_programs(
            cfg, self.PAGES, self.WIDTH, self.BATCH,
            state_slots=self.SLOTS, plan=plan)

    @pytest.mark.parametrize("program", ["decode", "prefill"])
    def test_no_pool_moves_and_no_weight_is_relaid(self, aot, program):
        aot_compile, _ = aot
        _, (programs, _, pools) = self._programs()
        assert pools[3] == (6, 97, 32, 256, 128)
        fn, args, jit_kw = programs[program]
        compiled = aot_compile(fn, args, **jit_kw)
        text = compiled.as_text()
        assert [hit for pool in set(pools)
                for hit in census_pool_copies(text, pool)] == []
        if program == "decode":
            assert "ssm_decode_update" in text
            assert "paged_decode_attention" in text
        # the projections' own shapes, by dimension (an activation of
        # 32 rows has the element count of the 32-wide dt projection,
        # which the census by count would take for the weight)
        import re
        relaid = [ln for ln in text.splitlines()
                  if re.search(r"\s(copy|copy-start|transpose)\(", ln)
                  and re.search(r"\[(6,)?(5120,(2560|512|4096|5120|32)"
                                r"|2560,5120|4096,5120)\]", ln)]
        assert relaid == []
        # weights 10.5 GB + pools 2.9 GB, and the program's own
        # temporaries, fit the chip's 16.9 GB... (a v5e reports 15.75 GiB)
        ma = compiled.memory_analysis()
        assert ma.argument_size_in_bytes + ma.temp_size_in_bytes < 15.2e9
        assert ma.alias_size_in_bytes >= 2.9e9      # every pool in place


# The two cells whose state layers keep a filter ring a page: pool pages,
# table width, decode rows, slots of the pool of states.
RING_CELLS = {"solar-open2-250b": (1888, 96, 64, 193),
              "falcon-h1-34b": (256, 16, 32, 97)}


def _ring_sliced_by_layer(real):
    """The form NOT taken: the layer's rings sliced out of the pool in
    front of the writer and put back behind it."""
    def write(cfg, tails, c, *rest):
        import jax
        layer = jax.lax.dynamic_index_in_dim(tails, c, axis=0, keepdims=True)
        return jax.lax.dynamic_update_index_in_dim(
            tails, real(cfg, layer, 0, *rest)[0], c, 0)
    return write


@pytest.mark.parametrize("cell", RING_CELLS)
class TestRingWrittenInPlace:
    """A state layer's filter ring in the DECODE program of its cell
    (PR 50): written by the in-place writer of ops/pallas/ring_update.py
    into the whole pool of tails ([3, 1888, 4, 24576], 1.11 GB; the
    mixer's [6, 256, 4, 5120]), the layer an index. No copy of the pool,
    no result that is one layer of it, no ``dynamic-update-slice`` into
    it (the parent's scatter went one row after another: 2.6 ms of the
    delta-rule cell's 17.7 ms step on the chip), and the pool not staged
    through VMEM around the call (the mixer's 31 MB were, until the
    kernel typed its result as HBM's)."""

    def _text(self, aot, cell):
        import tools.aot_copy_census as cc
        aot_compile, _ = aot
        pages, width, batch, slots = RING_CELLS[cell]
        programs, _, pools = cc.build_cell_programs(
            _cell_config(cell, 0), pages, width, batch, state_slots=slots)
        fn, args, jit_kw = programs["decode"]
        return aot_compile(fn, args, **jit_kw).as_text(), pools

    def test_the_decode_program_leaves_the_pool_of_tails_where_it_lies(
            self, aot, cell):
        import re
        text, pools = self._text(aot, cell)
        tails = pools[2]
        assert len(tails) == 4 and tails[2] == 4
        assert "ring_write" in text
        assert census_pool_copies(text, tails) == []
        assert census_layer_results(text, [tails]) == []
        dims = ",".join(str(d) for d in tails)
        assert not re.search(
            rf"= bf16\[{dims}\][^ ]* dynamic-update-slice\(", text)
        # a copy of it INTO the alternate memory space too, which the
        # census by design lets a toy-sized pool have
        assert not re.search(rf"= \(bf16\[{dims}\][^ ]* copy-start\(", text)

    def test_positive_control_sliced_by_layer(self, aot, cell, monkeypatch):
        from xllm_service_tpu.models import transformer
        monkeypatch.setattr(transformer, "_ring_write",
                            _ring_sliced_by_layer(transformer._ring_write))
        text, pools = self._text(aot, cell)
        assert (census_layer_results(text, [pools[2]])
                or census_pool_copies(text, pools[2]))


# Every cell of the benchmark, its config at its published widths and its
# pools at the CELL'S OWN page count (what the compiler copies depends
# on the pool's size: the hybrid family's whole-pool copies under the
# merged leading axes show at 3,776 pages and not at 256): layers (0:
# the benchmark's own cut), pool pages, decode rows, slots of a pool of
# states, and three of the prefill shapes its mix warms up, (rows,
# bucket, table width): a follow-up alone, a batch of them, a set-up
# window. What the compiler copies depends on the SHAPE too: the latent
# pool was copied whole from two rows on and in every window past 256
# tokens, and at one row of 256 not (PR 47); ``python
# tools/aot_copy_census.py --cells`` walks every shape of every mix.
PREFILL_CELLS = {
    "mistral-7b-v01": (4, 768, 8, 0,
                       [(1, 256, 64), (8, 256, 64), (1, 2048, 64)]),
    "ouro-2.6b": (4, 40, 8, 0,                  # x 4 passes: 16 slots
                  [(1, 256, 8), (8, 256, 8), (1, 1024, 8)]),
    "lfm2-24b-a2b": (0, 3776, 64, 0,
                     [(1, 256, 96), (16, 256, 96), (1, 2048, 96)]),
    "joyai-llm-flash": (0, 1888, 32, 0,         # ONE pool, pinned latent
                        [(1, 256, 96), (2, 256, 96), (1, 2048, 96)]),
    # from two rows on a layer of the pool of matrix states was sliced
    # out too (406 MB): transformer._state_rows reads a slice a row
    "falcon-h1-34b": (0, 256, 32, 97,
                      [(1, 256, 16), (2, 256, 16), (1, 1024, 8)]),
    # from two rows on the gather of a row's filter ring (196 KB over
    # q | k | v) was split over thirds of the whole pool of tails, copied
    # first (1.1 GB a layer) while the pool kept flat rows; a ring is
    # one contiguous piece of [n, P, K, C] and one gather reads it (two
    # rows: ``test_a_wide_ring_is_gathered_where_it_lies``)
    "solar-open2-250b": (0, 1888, 64, 193, [(1, 2048, 96)]),
    # NO pool of keys, values or tails (shapes of no layers, which the
    # census leaves out); the pool of states, 6.7 GB, is read and
    # written a row at a time where it lies (transformer._ret_window)
    "brumby-14b": (0, 1024, 16, 49, [(1, 2048, 168), (2, 256, 168)]),
    # TWO pairs of pools (the 2 full layers' of the 8 taken, and the 6
    # window layers' own 562 pages: ``_prefill_census`` sizes it as an
    # engine does) and two tables side by side; a window layer gathers
    # the 18 or 33 columns its window reaches, a full layer all 264
    "trinity-mini": (8, 1088, 16, 0,
                     [(1, 256, 264), (8, 256, 264), (1, 2048, 264)]),
}
CELL_SHAPES = [(cell, shape) for cell, spec in PREFILL_CELLS.items()
               for shape in spec[4]]
# What is LEFT, on purpose: the dense path reads its keys in place and
# its VALUES still through a slice of their pool, because with both read
# in place the Mistral cell outruns its mix (the docqa clients finish
# their 31 rounds before the run's end, which the generator counts as
# failed requests: PERF.md, PR 47). One slice a layer; [] once a
# benchmark PR has raised chipbench/traffic/docqa.json max_rounds_per_s
# and forward_prefill gathers vp_c as it gathers kp_c (clean offline at
# every warm-up shape of both dense cells: my compiles, PR 47).
LEFT = {"mistral-7b-v01": ["fusion 768,128,8,128"],
        "ouro-2.6b": ["fusion 40,128,16,128"],
        # ONE layer attends, so the in-place prefill writer's aliased
        # result, the pool itself, has the shape of "one layer of it"
        "solar-open2-250b": ["custom-call 1,1888,128,8,128"]}


def _slice_then_gather(pool, layer, page_table):
    """The parent's read (PR 46 and before): a layer out of the pool,
    then the gather from the slice."""
    import jax

    from xllm_service_tpu.ops.attention import gather_pages
    return gather_pages(jax.lax.dynamic_index_in_dim(
        pool, layer, axis=0, keepdims=False), page_table)


def _merged_leading_axes(pool, layer, page_table):
    """The form NOT taken: layers and pages merged into one axis, one
    index a page."""
    from xllm_service_tpu.ops.attention import gather_pages
    layers, pages = pool.shape[:2]
    return gather_pages(pool.reshape((layers * pages,) + pool.shape[2:]),
                        layer * pages + page_table)


def _window_pages(name, layers, batch):
    """Pages of the cell's pool of window layers, as its engine sizes it
    (0: the model has none)."""
    from xllm_service_tpu.runtime.engine import window_pool_pages
    cfg = _cell_config(name, layers)
    return window_pool_pages(cfg.sliding_window, 128, batch, batch,
                             2048)[1] if cfg.num_swa_layers else 0


def _prefill_census(aot, name, shape):
    """(results that are one layer of a pool, pool-sized copies) of the
    cell's compiled prefill program of ``shape`` = (rows, bucket, table
    width)."""
    import tools.aot_copy_census as cc
    aot_compile, _ = aot
    layers, pages, batch, slots, _ = PREFILL_CELLS[name]
    rows, window, table_width = shape
    programs, _, pools = cc.build_cell_programs(
        _cell_config(name, layers), pages, table_width, batch,
        window=window, state_slots=slots, prefill_rows=rows,
        window_pages=_window_pages(name, layers, batch))
    fn, args, jit_kw = programs["prefill"]
    return cc.census_pools(aot_compile(fn, args, **jit_kw).as_text(), pools,
                           window=(rows, window))


class TestPrefillReadsPagesOffThePool:
    """No cell's prefill program holds a result that is ONE LAYER of a
    pool, nor a copy of a whole pool (PR 47): the attention read gathers
    a row's pages out of the pool where it lies, the layer a second
    index (ops/attention.py ``gather_layer_pages``; a latent pool's
    ``gather_latent_layer_pages``)."""

    @pytest.mark.parametrize("cell, shape", CELL_SHAPES)
    def test_no_layer_of_a_pool_is_materialized(self, aot, cell, shape):
        layer_sized, copies = _prefill_census(aot, cell, shape)
        assert layer_sized == LEFT.get(cell, []) and copies == [], (
            layer_sized, copies)

    def test_a_wide_ring_is_gathered_where_it_lies(self, aot):
        """Two follow-ups in one window of the delta-rule cell: the
        gather of their filter rings (196 KB each) out of a pool of
        FLAT rows was split over thirds of the whole pool, each copied
        first: 1.1 GB of temporaries that no pool-shaped result shows
        (PR 49, which read a slice a row instead). Out of [n, P, K, C]
        one gather takes them where they lie (PR 50)."""
        import tools.aot_copy_census as cc
        aot_compile, _ = aot
        name = "solar-open2-250b"
        _, pages, batch, slots, _ = PREFILL_CELLS[name]
        programs, _, pools = cc.build_cell_programs(
            _cell_config(name, 0), pages, 96, batch, window=256,
            state_slots=slots, prefill_rows=2)
        fn, args, jit_kw = programs["prefill"]
        compiled = aot_compile(fn, args, **jit_kw)
        assert cc.census_pools(compiled.as_text(), pools) == (LEFT[name], [])
        tails = 3 * 1888 * 4 * 24576 * 2
        assert compiled.memory_analysis().temp_size_in_bytes < tails // 4

    def test_sixteen_retention_windows_hold_one_rows_temporaries(self, aot):
        """A retention layer's entry of one row is 34 MB a layer: sixteen
        follow-ups gathered, scanned and scattered side by side would
        hold over 2 GB of entries (a source, a final state and a
        snapshot each) beside a pool of 6.7 GB and weights of 5.75 on a
        chip of 16. Walked one row after another the program's
        temporaries stay under a gigabyte (0.75 GB, compiled for a
        described v5e, PR 53; 0.51 at one row), and the pool is never
        copied."""
        import tools.aot_copy_census as cc
        aot_compile, _ = aot
        name = "brumby-14b"
        _, pages, batch, slots, _ = PREFILL_CELLS[name]
        programs, _, pools = cc.build_cell_programs(
            _cell_config(name, 0), pages, 168, batch, window=256,
            state_slots=slots, prefill_rows=16)
        assert [math.prod(p) for p in pools[:3]] == [0, 0, 0]
        fn, args, jit_kw = programs["prefill"]
        compiled = aot_compile(fn, args, **jit_kw)
        assert cc.census_pools(compiled.as_text(), pools) == ([], [])
        assert compiled.memory_analysis().temp_size_in_bytes < 1.0e9

    def test_two_pools_decode_under_two_names_and_copy_neither(self, aot):
        """The decode program of the cell with window and full layers,
        at its pools' real shapes (8 of its 32 layers): the paged kernel
        is called under the two names a trace tells its pools by (6
        window layers and 2 full ones: one call each in the scanned
        period, one a layer outside it), neither pool is copied, and the
        program's temporaries are a few MB."""
        import re

        import tools.aot_copy_census as cc
        aot_compile, _ = aot
        name = "trinity-mini"
        layers, pages, batch, _, _ = PREFILL_CELLS[name]
        programs, _, pools = cc.build_cell_programs(
            _cell_config(name, layers), pages, 264, batch,
            window_pages=_window_pages(name, layers, batch))
        assert pools[0] == (2, 1088, 128, 4, 128) \
            and pools[3] == (6, 562, 128, 4, 128)
        fn, args, jit_kw = programs["decode"]
        compiled = aot_compile(fn, args, **jit_kw)
        text = compiled.as_text()
        assert set(re.findall(r"%(paged_decode_attention_\w+?)[.\d]* = ",
                              text)) == {"paged_decode_attention_swa",
                                         "paged_decode_attention_full"}
        assert cc.census_pools(text, pools) == ([], [])
        assert compiled.memory_analysis().temp_size_in_bytes < 64e6

    # (not the delta-rule cell: ONE of its layers attends, and a layer
    # sliced out of a pool of one layer is the pool; not the retention
    # cell: no layer attends)
    @pytest.mark.parametrize("cell", [c for c in PREFILL_CELLS
                                      if c not in ("solar-open2-250b",
                                                   "brumby-14b")])
    def test_positive_control_slice_then_gather(self, aot, cell,
                                                monkeypatch):
        """The parent's form patched in: the census must find the slice
        of each pool the attention reads (the ledger's
        ``dynamic-slice_bitcast_fusion.4`` / ``.5``), or the zero above
        proves nothing."""
        from xllm_service_tpu.models import transformer
        for name in ("gather_layer_pages", "gather_latent_layer_pages"):
            monkeypatch.setattr(transformer, name, _slice_then_gather)
        layer_sized, _ = _prefill_census(aot, cell,
                                         PREFILL_CELLS[cell][4][0])
        fusions = [h for h in layer_sized if h.startswith("fusion")]
        assert len(fusions) >= (1 if cell == "joyai-llm-flash" else 2), \
            layer_sized

    def test_positive_control_a_layer_of_states_sliced_out(
            self, aot, monkeypatch):
        """The fifth family's parent form for its rows' matrix states, a
        layer out of the pool and then the rows' gather: from two rows a
        window on the compiler materializes the layer, every slot of it
        (406 MB of the cell's pool)."""
        import jax

        from xllm_service_tpu.models import transformer
        monkeypatch.setattr(
            transformer, "_state_rows",
            lambda state, c, slots: jax.lax.dynamic_index_in_dim(
                state, c, axis=0, keepdims=False)[slots])
        layer_sized, _ = _prefill_census(aot, "falcon-h1-34b",
                                         (2, 256, 16))
        assert layer_sized == ["fusion 97,32,256,128"], layer_sized

    def test_positive_control_merged_leading_axes(self, aot, monkeypatch):
        """``pool.reshape(L * P, ...)[layer * P + page_table]`` in the
        loop over layer kinds: under pools tiled (4, 128) the merge of
        the two leading axes is no bitcast, and the compiler copies both
        attention pools WHOLE (1.98 GB a prefill program in the hybrid
        cell). The census must see them."""
        from xllm_service_tpu.models import transformer
        monkeypatch.setattr(transformer, "gather_layer_pages",
                            _merged_leading_axes)
        _, copies = _prefill_census(aot, "lfm2-24b-a2b", (1, 256, 96))
        assert len(copies) >= 2, copies

    def test_positive_control_a_latent_pool_under_the_plain_gather(
            self, aot, monkeypatch):
        """Why a latent pool has a helper of its own: under the plain
        two-index gather the order the attention product wants (the
        positions minor) is settled on the gather's OPERAND, and from
        two rows a window on the compiler copies the whole pool, 1.5 GB,
        once a layer (at one row of 256 tokens it does not)."""
        from xllm_service_tpu.models import transformer
        from xllm_service_tpu.ops.attention import gather_layer_pages
        monkeypatch.setattr(transformer, "gather_latent_layer_pages",
                            gather_layer_pages)
        _, copies = _prefill_census(aot, "joyai-llm-flash", (2, 256, 96))
        assert len(copies) >= 2, copies
