"""Compile what the chip serves, for the chip, without the chip.

The TPU's compiler is installed here and compiles for a v5e that is
described and not attached (tools/aot_tpu.py, the topology
tests/test_copy_census.py builds too). One parametrised test lowers the
kernels of the serving path at llama3-1b widths through real Mosaic —
what interpret mode cannot show: a slice not aligned to the tiling, too
much fast memory, a shape that cannot be partitioned — plus the
engine's own decode step program at full width, two layers deep, with
the pool pinned as a single-device engine pins it.

A compile that passes is not a chip run: results and times come from
``chip_smoke.py``. Skipped where the topology cannot be described.
"""

import dataclasses
import functools

import jax
import jax.numpy as jnp
import pytest

# llama3-1b widths (config.py ModelConfig.llama3_1b) at the worker CLI's
# page size, and the smoke's pool.
HQ, HKV, D, PS, L, P = 32, 8, 64, 128, 16, 1024
B_DEC, MP = 8, 16          # decode batch and page-table width (len 2048)
B_PF, T = 2, 256           # a prefill window of two pages
MLA_HKV, MLA_D = 1, 576    # absorbed-MLA latent pool (kv_lora 512 + rope 64)


def _pools(sds, hkv=HKV, d=D, layers=L, pages=P):
    pool = sds((layers, pages, PS, hkv, d), jnp.bfloat16)
    return pool, pool


def _decode_attention(sds, b=B_DEC, hq=HQ, hkv=HKV, d=D, mp=MP, window=0,
                      pool=(L, P)):
    from xllm_service_tpu.ops.pallas import paged_decode_attention_pallas
    fn = functools.partial(paged_decode_attention_pallas, interpret=False,
                           sliding_window=window)
    return (lambda q, kp, vp, pt, ctx, lyr: fn(q, kp, vp, pt, ctx,
                                               layer=lyr),
            (sds((b, hq, d), jnp.bfloat16), *_pools(sds, hkv, d, *pool),
             sds((b, mp), jnp.int32), sds((b,), jnp.int32),
             sds((), jnp.int32)), {})


# The benchmark's five cells that decode through the paged kernel: rows,
# query / key-value heads of 128 as the kernel sees them, table width,
# static window, the pool's layers and pages, the pages a grid step
# folds there (ops/plan.py ``paged_fold_pages`` at 128-token pages of
# bfloat16: PERF.md, PR 46) and the positions a tile of the page read
# flat (``paged_flat_positions``, PR 51; 1: the page is read by heads).
CELLS = {
    # Mistral-7B-v0.1: the grid walks the window's 33 columns from each
    # row's first live page, which the folded table holds
    "window": (dict(b=8, hq=32, hkv=8, d=128, mp=64, window=4096,
                    pool=(16, 768)), 4, 1),
    # Ouro-2.6B: 16 key-value heads of group size 1, a table of 8
    "looped": (dict(b=8, hq=16, hkv=16, d=128, mp=8, pool=(192, 40)), 2, 1),
    # LFM2-24B-A2B: 8 heads of 64 packed two to a 128-wide row
    "packed": (dict(b=64, hq=32, hkv=4, d=128, mp=96, pool=(2, 3776)), 8,
               4),
    # Falcon-H1-34B: a group of 5
    "group5": (dict(b=32, hq=20, hkv=4, d=128, mp=16, pool=(6, 256)), 8, 4),
    # Solar-Open2-250B: ONE layer of 4 attends, at a group of 8
    "group8": (dict(b=64, hq=64, hkv=8, d=128, mp=96, pool=(1, 1888)), 4,
               1),
}
# No cell runs one: a chip's slice of an 8-head model under tensor
# parallelism, 2 heads or 1 (the Mistral cell's widths otherwise); the
# page is read flat like the hybrid cell's.
TP_SLICES = {
    "tp-slice-2": (dict(b=8, hq=8, hkv=2, d=128, mp=64, window=4096,
                        pool=(16, 768)), 8, 8),
    "tp-slice-1": (dict(b=8, hq=4, hkv=1, d=128, mp=64, window=4096,
                        pool=(16, 768)), 8, 16),
}
DECODE_SHAPES = {**CELLS, **TP_SLICES}


def _decode_writer(sds, hkv=HKV, d=D):
    from xllm_service_tpu.ops.pallas.kv_update import paged_kv_update_layer
    new = sds((B_DEC, hkv, d), jnp.bfloat16)
    return (functools.partial(paged_kv_update_layer, interpret=False),
            (*_pools(sds, hkv, d), new, new, sds((B_DEC, MP), jnp.int32),
             sds((B_DEC,), jnp.int32), sds((B_DEC,), jnp.bool_),
             sds((), jnp.int32)), {"donate_argnums": (0, 1)})


def _latent_pool(sds):
    """The latent pool as its kernels take it: [L, P, ps, D]."""
    return sds((L, P, PS, MLA_D), jnp.bfloat16)


def _latent_attention(sds, b=B_DEC, hq=16, mp=MP, pool=None):
    from xllm_service_tpu.ops.pallas.latent import latent_decode_attention
    return (functools.partial(latent_decode_attention, scale=0.07,
                              interpret=False),
            (sds((b, hq, MLA_D), jnp.bfloat16),
             _latent_pool(sds) if pool is None else sds(pool, jnp.bfloat16),
             sds((b, mp), jnp.int32), sds((b,), jnp.int32),
             sds((), jnp.int32)), {})


def _latent_writer(sds):
    from xllm_service_tpu.ops.pallas.latent import latent_kv_update_layer
    return (functools.partial(latent_kv_update_layer, interpret=False),
            (_latent_pool(sds), sds((B_DEC, MLA_D), jnp.bfloat16),
             sds((B_DEC, MP), jnp.int32), sds((B_DEC,), jnp.int32),
             sds((B_DEC,), jnp.bool_), sds((), jnp.int32)),
            {"donate_argnums": (0,)})


def _prefill_writer(sds, hkv=HKV, d=D):
    from xllm_service_tpu.ops.pallas.kv_update import (
        paged_prefill_kv_update_layer)
    new = sds((B_PF, T, hkv, d), jnp.bfloat16)
    return (functools.partial(paged_prefill_kv_update_layer,
                              interpret=False),
            (*_pools(sds, hkv, d), new, new, sds((B_PF, MP), jnp.int32),
             sds((B_PF,), jnp.int32), sds((B_PF,), jnp.int32),
             sds((), jnp.int32)), {"donate_argnums": (0, 1)})


def _prefill_attention(sds):
    """The opt-in prefill kernel (XLLM_PALLAS_PREFILL) in the form
    write-then-attend calls it: window and prefix both from the pool."""
    from xllm_service_tpu.ops.pallas import paged_prefill_attention_pallas
    return (lambda q, kp, vp, pt, st, ln, lyr:
            paged_prefill_attention_pallas(
                q, None, None, kp, vp, pt, st, ln, layer=lyr,
                from_pool=True, interpret=False),
            (sds((B_PF, T, HQ, D), jnp.bfloat16), *_pools(sds),
             sds((B_PF, MP), jnp.int32), sds((B_PF,), jnp.int32),
             sds((B_PF,), jnp.int32), sds((), jnp.int32)), {})


def _ragged_attention(sds):
    """The opt-in ragged kernel (XLLM_RAGGED_ATTN): decode rows and
    prefill windows in one batch."""
    from xllm_service_tpu.ops.pallas import ragged_paged_attention_pallas
    return (lambda q, kp, vp, pt, st, ln, lyr:
            ragged_paged_attention_pallas(q, kp, vp, pt, st, ln, layer=lyr,
                                          interpret=False),
            (sds((B_DEC, T, HQ, D), jnp.bfloat16), *_pools(sds),
             sds((B_DEC, MP), jnp.int32), sds((B_DEC,), jnp.int32),
             sds((B_DEC,), jnp.int32), sds((), jnp.int32)), {})


def _kda_update(sds):
    from xllm_service_tpu.ops.pallas.kda_update import kda_decode_update
    b, h, d = 64, 64, 128
    row = sds((b, h, d), jnp.float32)
    return (functools.partial(kda_decode_update, interpret=False),
            (sds((3, 193, h, d, d), jnp.float32), sds((), jnp.int32),
             sds((b,), jnp.int32), sds((b,), jnp.int32), row, row, row, row,
             sds((b, h), jnp.float32)), {"donate_argnums": (0,)})


def _retention_update(sds):
    from xllm_service_tpu.ops.pallas.retention_update import (
        retention_decode_update)
    b, h, g, d = 16, 8, 5, 128
    row = sds((b, h, d), jnp.float32)
    return (functools.partial(retention_decode_update, interpret=False),
            (sds((4, 49, h, 8392, d), jnp.float32), sds((), jnp.int32),
             sds((b,), jnp.int32), sds((b,), jnp.int32),
             sds((b, h * g, d), jnp.float32), row, row,
             sds((b, h), jnp.float32)), {"donate_argnums": (0,)})


def _ring_writer(sds, rows, pool):
    """The pool pinned row-major on both sides, as an engine pins it,
    and a second result beside it, as every step program has: the kernel
    types its result as HBM's, and a program whose ONLY result is that
    fails the compiler's check of the donated argument's alias (seen
    here, PR 50; a step program's tuple does not)."""
    from xllm_service_tpu.ops.pallas.ring_update import ring_write
    from xllm_service_tpu.runtime.engine import row_major_format
    tails = sds(pool, jnp.bfloat16)
    pin = row_major_format(len(pool), tails.sharding)
    return (lambda tails, layer, pid, rings: (
                ring_write(tails, layer, pid, rings, interpret=False), pid),
            (tails, sds((), jnp.int32), sds((rows,), jnp.int32),
             sds((rows,) + pool[2:], jnp.bfloat16)),
            {"donate_argnums": (0,), "out_shardings": (pin, None),
             "in_shardings": (pin, None, None, None)})


KERNELS = {
    # The default path: what a served worker runs on the chip.
    "decode-attention": _decode_attention,
    # ... and at the benchmark's cells, each with the block of pages the
    # plan picks for it: a block that does not fit VMEM fails here.
    **{f"decode-attention[{name}]": functools.partial(_decode_attention,
                                                      **shape)
       for name, (shape, _, _) in DECODE_SHAPES.items()},
    "decode-kv-writer": _decode_writer,
    "prefill-kv-writer": _prefill_writer,
    # Opt-in kernels: compile-checked here, their A/B is later work.
    "prefill-attention[opt-in]": _prefill_attention,
    "ragged-attention[opt-in]": _ragged_attention,
    # The MLA latent pool, one KV "head" of width 576 (not a multiple of
    # the 128-lane tile): the first benchmark cells will be MLA.
    "mla-decode-attention": functools.partial(
        _decode_attention, hq=16, hkv=MLA_HKV, d=MLA_D),
    "mla-decode-kv-writer": functools.partial(
        _decode_writer, hkv=MLA_HKV, d=MLA_D),
    "mla-prefill-kv-writer": functools.partial(
        _prefill_writer, hkv=MLA_HKV, d=MLA_D),
    # What a write-then-attend decode step of a latent model runs since
    # PR 36 (ops/pallas/latent.py): the pool as [L, P, ps, 576].
    "latent-decode-attention": _latent_attention,
    # ... and at the benchmark cell's shapes (joyai-flash-docqa32: 32
    # rows, 32 heads, a table of 96, the pool of 1,888 pages of 5
    # layers), where a grid step folds a block of 8 pages (ops/plan.py
    # ``latent_fold_pages``): a block that does not fit VMEM fails here.
    "latent-decode-attention[cell]": functools.partial(
        _latent_attention, b=32, hq=32, mp=96,
        pool=(5, 1888, PS, MLA_D)),
    "latent-decode-kv-writer": _latent_writer,
    # A delta-rule layer's one-token state update (ops/pallas/
    # kda_update.py) at the benchmark cell's shapes: 64 rows, 64 heads of
    # 128 x 128 float32 in a pool of 3 layers x 193 slots, blocks of 8
    # heads mapped by slot, four [128, 1] columns a head sliced out of a
    # 32-lane block.
    "kda-decode-update[cell]": _kda_update,
    # A retention layer's one-token state update (ops/pallas/
    # retention_update.py) at the benchmark cell's shapes: 16 rows, 8
    # key-value heads of 8,392 x 128 float32 (4.3 MB a block, in and out
    # double-buffered 17.2 MB: the kernel asks for its VMEM) in a pool
    # of 4 layers x 49 slots, five query heads a block; the strided lane
    # rotation that expands a key and the two [128, 128] transposes.
    "retention-decode-update[cell]": _retention_update,
    # A state layer's filter ring written in place (ops/pallas/
    # ring_update.py) at both cells' shapes: a copy a row from [B, K, C]
    # into a page of [n, P, K, C] (a page's ring has to be
    # one aligned piece: a pool of flat rows is refused here).
    "ring-write[solar-open2-250b]": functools.partial(
        _ring_writer, rows=64, pool=(3, 1888, 4, 24576)),
    "ring-write[falcon-h1-34b]": functools.partial(
        _ring_writer, rows=32, pool=(6, 256, 4, 5120)),
}


def _engine_decode_step(sds, monkeypatch):
    """The engine's real jitted decode step (runtime/engine.py
    ``_decode_step`` as ``_build_step_programs`` jits it: donated,
    pinned, sampling fused in), llama3-1b at full width and two layers,
    kernels on and not interpreted, the pool at the smoke's size."""
    from xllm_service_tpu.config import EngineConfig, ModelConfig
    from xllm_service_tpu.models import transformer
    from xllm_service_tpu.runtime import engine as E

    # The engine resolves its plan when it is built; the runtime backend
    # is the CPU, so the test steers it as the chip would resolve it.
    monkeypatch.setenv("XLLM_PALLAS", "1")
    monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "0")
    cfg = dataclasses.replace(ModelConfig.llama3_1b(), num_layers=2)
    ecfg = EngineConfig(page_size=PS, num_pages=P, max_model_len=2048,
                        max_batch_size=B_DEC)
    there = sds((1,), jnp.int32).sharding

    def described(tree):
        return jax.tree_util.tree_map(
            lambda a: jax.ShapeDtypeStruct(a.shape, a.dtype,
                                           sharding=there), tree)
    params = described(jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0))))
    kv = described(jax.eval_shape(
        lambda: transformer.init_kv_cache(cfg, P, PS)))
    # A live engine on the CPU with a token pool, its step programs then
    # rebuilt for pools of the smoke's size placed on the described chip.
    eng = E.Engine(cfg, dataclasses.replace(ecfg, num_pages=8),
                   params=jax.tree_util.tree_map(
                       lambda a: jnp.zeros(a.shape, a.dtype), params))
    eng._build_step_programs(kv)
    small = described((*eng._sampling_tensors([], B_DEC),
                       *eng._batch_bias([], B_DEC, cfg.vocab_size)))
    st_f32, st_i32, b_ids, b_vals = small
    compiled = eng._jit_decode.lower(
        params, sds((B_DEC, E._PACK_COLS + MP), jnp.int32), kv, st_f32,
        st_i32, described(jax.eval_shape(lambda: jax.random.PRNGKey(0))),
        None, b_ids, b_vals).compile()
    return compiled, sum(
        2 * int(jnp.prod(jnp.array(x.shape))) for x in kv)


def _tp4_forward_decode(monkeypatch):
    """One decode forward of llama3-1b (two layers) partitioned over the
    four chips of a described v5e 2x2 with the repo's own sharding rules
    — what a ``--tp 4`` worker's engine traces: the reference plan
    (ops/plan.py ``KernelPlan.from_env`` on a mesh), because the Mosaic
    kernels cannot be partitioned automatically and are not wrapped in
    shard_map yet."""
    import numpy as np
    from jax.experimental import topologies
    from jax.sharding import Mesh, NamedSharding, PartitionSpec

    from xllm_service_tpu.config import EngineConfig, ModelConfig
    from xllm_service_tpu.models import transformer
    from xllm_service_tpu.ops.plan import KernelPlan
    from xllm_service_tpu.parallel.mesh import MESH_AXES
    from xllm_service_tpu.parallel.sharding import (
        kv_cache_sharding, param_shardings)

    monkeypatch.setenv("XLLM_PALLAS", "1")
    monkeypatch.setenv("XLLM_PALLAS_INTERPRET", "0")
    cfg = dataclasses.replace(ModelConfig.llama3_1b(), num_layers=2)
    topo = topologies.get_topology_desc(platform="tpu",
                                        topology_name="v5e:2x2")
    mesh = Mesh(np.array(topo.devices).reshape(1, 1, 1, 4), MESH_AXES)
    shapes = jax.eval_shape(
        lambda: transformer.init_params(cfg, jax.random.PRNGKey(0)))
    params = jax.tree_util.tree_map(
        lambda a, s: jax.ShapeDtypeStruct(a.shape, a.dtype, sharding=s),
        shapes, param_shardings(shapes, mesh, cfg))
    kv = tuple(
        jax.ShapeDtypeStruct(a.shape, a.dtype,
                             sharding=kv_cache_sharding(mesh, cfg))
        for a in jax.eval_shape(
            lambda: transformer.init_kv_cache(cfg, P, PS)))
    everywhere = NamedSharding(mesh, PartitionSpec())

    def whole(shape, dtype):
        return jax.ShapeDtypeStruct(shape, dtype, sharding=everywhere)
    args = (params, whole((B_DEC,), jnp.int32), whole((B_DEC,), jnp.int32),
            whole((B_DEC,), jnp.bool_), kv, whole((B_DEC, MP), jnp.int32))

    ecfg = EngineConfig(page_size=PS, num_pages=P, max_model_len=2048,
                        max_batch_size=B_DEC)
    one_chip_plan = KernelPlan.from_env(cfg, ecfg)
    mesh_plan = KernelPlan.from_env(cfg, ecfg, mesh)
    assert one_chip_plan.decode_attn and one_chip_plan.write_then_attend \
        and not one_chip_plan.interpret
    assert mesh_plan == KernelPlan(page_aligned=False)

    def with_kernels(p, t, pos, act, kv, pt):
        return transformer.forward_decode(p, cfg, t, pos, act, kv, pt,
                                          plan=one_chip_plan)

    def reference(p, t, pos, act, kv, pt):
        return transformer.forward_decode(p, cfg, t, pos, act, kv, pt,
                                          plan=mesh_plan)
    with pytest.raises(NotImplementedError,
                       match="cannot be automatically partitioned"):
        jax.jit(with_kernels, donate_argnums=(4,)).lower(*args).compile()
    return jax.jit(reference, donate_argnums=(4,)).lower(*args).compile()


@pytest.mark.parametrize("cell", CELLS)
def test_paged_fold_pages_at_the_cells_shapes(cell):
    """K from shapes: one VMEM budget for every caller, no more pages
    than the walk has columns, and a page a grid step (today's schedule
    from the same body) where two page pairs do not fit the budget."""
    from xllm_service_tpu.ops.plan import (
        decode_walk_columns, paged_fold_pages)
    shape, want, _ = CELLS[cell]
    walk = decode_walk_columns(shape["mp"], PS, shape.get("window", 0))
    assert paged_fold_pages(PS, shape["hkv"], shape["d"], 2, walk) == want
    # a narrower table (the engine's tables are powers of two) caps K
    assert paged_fold_pages(PS, shape["hkv"], shape["d"], 2, 2) == 2
    assert paged_fold_pages(PS, shape["hkv"], shape["d"], 2, 1) == 1
    # float32 pools: half the pages; a page too large for two: one
    assert paged_fold_pages(PS, shape["hkv"], shape["d"], 4,
                            walk) == max(want // 2, 1)
    assert paged_fold_pages(PS, 8 * shape["hkv"], shape["d"], 4, walk) == 1


@pytest.mark.parametrize("cell", DECODE_SHAPES)
def test_paged_flat_positions_at_the_cells_shapes(cell):
    """Which pages the kernel reads flat, from shapes: those whose head
    axis is under 8 rows, over whole 128-lane rows; as many positions a
    tile as fill it (16 rows of bfloat16, 8 of float32)."""
    from xllm_service_tpu.ops.plan import paged_flat_positions
    shape, _, want = DECODE_SHAPES[cell]
    hkv, d = shape["hkv"], shape["d"]
    assert paged_flat_positions(hkv, d, 2) == want
    # float32: half as many rows a tile, so 8 heads and more fill it too
    assert paged_flat_positions(hkv, d, 4) == max(want // 2, 1)
    # unpacked heads of 64 (or the rehearsal widths' 16) are no whole
    # row of lanes: the flat view would not name the pool's bytes in
    # their order, and the page is read by heads
    assert paged_flat_positions(hkv, 64, 2) == 1
    assert paged_flat_positions(hkv, 16, 4) == 1
    # a head count that does not divide a tile is read by heads
    assert paged_flat_positions(3, d, 2) == paged_flat_positions(6, d, 2) == 1


@pytest.mark.parametrize("case", [*KERNELS, "engine-decode-step",
                                  "tp4-forward-decode"])
def test_compiles_for_v5e(aot, monkeypatch, case):
    aot_compile, sds = aot
    if case == "tp4-forward-decode":
        text = _tp4_forward_decode(monkeypatch).as_text()
        assert "tpu_custom_call" not in text and "all-reduce" in text
        return
    if case != "engine-decode-step":
        fn, args, jit_kw = KERNELS[case](sds)
        text = aot_compile(fn, args, **jit_kw).as_text()
        assert "tpu_custom_call" in text
        cell = case.removeprefix("decode-attention[").rstrip("]")
        if cell in DECODE_SHAPES:
            # the page as the compiled kernel's operands see it: flat,
            # a bitcast of the pool (no copy of it), or by heads
            shape, _, flat = DECODE_SHAPES[cell]
            page = (f"{PS * shape['hkv']},{shape['d']}" if flat > 1
                    else f"{PS},{shape['hkv']},{shape['d']}")
            pool = "bf16[" + ",".join(map(str, shape["pool"]))
            assert f"{pool},{page}]" in text
            assert not [ln for ln in text.splitlines()
                        if " copy(" in ln and pool in ln]
        return
    compiled, pool_nominal_bytes = _engine_decode_step(sds, monkeypatch)
    assert "tpu_custom_call" in compiled.as_text()
    mem = compiled.memory_analysis()
    # The pool is aliased through the step (donated in, returned), and
    # no temporary is anywhere near a pool: unpinned, this program holds
    # two more whole pools in temporaries. The pinned row-major pool
    # itself is twice its nominal bytes at head_dim 64 (the minor
    # dimension pads to the 128-lane tile) — recorded, not asserted away.
    assert mem.alias_size_in_bytes >= pool_nominal_bytes
    assert mem.temp_size_in_bytes < pool_nominal_bytes // 8
    assert mem.alias_size_in_bytes == 2 * pool_nominal_bytes
