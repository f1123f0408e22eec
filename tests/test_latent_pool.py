"""A model under latent attention keeps ONE pool (PR 36): its cached row
is key and value both, so ``init_kv_cache`` gives a 1-tuple, every KV
writer runs over that pool alone, and the engine's (k, v) boundaries
(PD handoff, the spill tier, block export) send the one block for
both and write the one pool back."""

import dataclasses as dc
import json
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.config import EngineConfig, ModelConfig
from xllm_service_tpu.models import transformer
from xllm_service_tpu.ops import attention as A
from xllm_service_tpu.ops.plan import KernelPlan
from xllm_service_tpu.runtime.engine import Engine, EngineRequest
from xllm_service_tpu.utils.types import SamplingParams

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
CONFIG = os.path.join(ROOT, "chipbench", "configs", "joyai-llm-flash")


def tiny_model() -> ModelConfig:
    with open(os.path.join(CONFIG, "config.json")) as f:
        cfg = json.load(f)
    with open(os.path.join(CONFIG, "meta.json")) as f:
        cfg.update(json.load(f)["rehearsal_widths"])
    return dc.replace(ModelConfig.from_hf_config(cfg, "joyai-tiny"),
                      dtype="float32")


def tiny_engine(**kw) -> Engine:
    return Engine(tiny_model(), EngineConfig(
        page_size=16, num_pages=kw.pop("num_pages", 32), max_model_len=256,
        max_batch_size=2, max_prefill_tokens=256,
        prefill_buckets=(32, 64, 128), **kw), seed=0)


def run(eng: Engine, prompt, rid: str, n: int = 8, **kw):
    eng.add_request(EngineRequest(
        request_id=rid, token_ids=list(prompt),
        sampling=SamplingParams(max_tokens=n, temperature=0.0,
                                ignore_eos=True), **kw))
    out = []
    while eng.has_work():
        for o in eng.step():
            if o.request_id == rid:
                out.extend(o.new_token_ids)
    return out


def test_a_latent_model_keeps_one_pool_and_a_dense_one_two():
    mc = tiny_model()
    kv = transformer.init_kv_cache(mc, 8, 16)
    width = mc.kv_lora_rank + mc.qk_rope_head_dim
    assert len(kv) == 1 and kv[0].shape == (mc.num_layers, 8, 16, 1, width)
    assert len(transformer.init_kv_cache(ModelConfig.tiny(), 8, 16)) == 2
    eng = tiny_engine()
    assert len(eng.kv) == 1
    # one block = the latent rows of one page over every layer, once
    assert eng.kv_block_bytes() == mc.num_layers * 16 * width * 4


WRITERS = {
    "decode_all_layers": (A.write_decode_kv_all_layers_xla, "lbhd", False),
    "decode_layer": (A.write_decode_kv_layer_xla, "bhd", True),
    "prefill_all_layers": (A.write_prefill_kv_all_layers_xla, "lbthd",
                           False),
    "prefill_layer": (A.write_prefill_kv_layer_xla, "bthd", True),
}


@pytest.mark.parametrize("name", sorted(WRITERS))
def test_a_writer_over_one_pool_writes_what_it_writes_into_the_first_of_two(
        name):
    """The four XLA scatters a latent forward calls (the paged kernel
    writers never see a latent pool)."""
    fn, dims, layered = WRITERS[name]
    L, P, ps, H, D, B, T = 3, 9, 8, 1, 24, 2, 16
    rng = np.random.default_rng(5)
    pool = jnp.asarray(rng.normal(size=(L, P, ps, H, D)), jnp.float32)
    shape = tuple({"l": L, "b": B, "t": T, "h": H, "d": D}[c] for c in dims)
    new = jnp.asarray(rng.normal(size=shape), jnp.float32)
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 6, 7]], jnp.int32)
    if "t" in dims:
        where = (jnp.asarray([0, 8], jnp.int32), jnp.asarray([T, T - 5],
                                                            jnp.int32))
    else:
        where = (jnp.asarray([5, 17], jnp.int32), jnp.asarray([True, True]))
    tail = (jnp.asarray(1, jnp.int32),) if layered else ()
    one = fn(pool, None, new, None, table, *where, *tail)
    two = fn(pool, pool, new, new, table, *where, *tail)
    assert len(one) == 1 and len(two) == 2
    assert np.array_equal(np.asarray(one[0]), np.asarray(two[0]))
    assert not np.array_equal(np.asarray(one[0]), np.asarray(pool))


def test_a_latent_sequence_is_handed_from_one_engine_to_another():
    prompt = list(range(3, 40))
    mono = run(tiny_engine(), prompt, "m")
    a, b = tiny_engine(), tiny_engine()
    first = run(a, prompt, "r", n=1, hold_after_finish=True)
    assert first == mono[:1]
    tokens, k, v = a.export_held("r")
    # the wire speaks (k, v): the one latent block stands for both
    assert k.shape == v.shape == (a.cfg.num_layers, 3, 16, 1,
                                  a.kv[0].shape[-1])
    assert np.array_equal(k, v)
    assert b.import_sequence(
        EngineRequest(request_id="r", token_ids=list(prompt),
                      sampling=SamplingParams(max_tokens=8, temperature=0.0,
                                              ignore_eos=True)),
        tokens, k, v)
    cont = []
    while b.has_work():
        for o in b.step():
            cont.extend(o.new_token_ids)
    assert first + cont == mono
    assert len(b.kv) == 1


def test_a_latent_prefix_is_spilled_restored_and_exported():
    eng = tiny_engine(num_pages=16, kv_spill_mb=64.0)
    p1 = [7] * 5 + list(range(40))
    out1 = run(eng, p1, "a")
    run(eng, list(range(100, 330))[:230], "b")      # reclaims p1's pages
    assert eng.prefix_cache_stats()["spilled_pages"] > 0
    exported = eng.export_blocks(eng.prefix_cache.block_hashes(p1)[:2])
    assert exported is not None and exported[0] == 2
    assert run(eng, p1, "c") == out1
    assert eng.prefix_cache_stats()["restored_pages"] > 0
    # a peer adopts what the holder exported into its own single pool
    peer = tiny_engine()
    assert peer.adopt_blocks(p1, 0, exported[1], exported[2]) == 2
    assert run(peer, p1, "d") == out1
    assert peer.prefix_hit_tokens >= 32


def _latent_case(seed=3, L=3, P=9, ps=16, D=40, B=3, Hq=4, MP=4):
    rng = np.random.default_rng(seed)
    pool = jnp.asarray(rng.normal(size=(L, P, ps, D)), jnp.float32)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)), jnp.float32)
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 2]],
                        jnp.int32)
    return pool, q, table


def test_the_latent_decode_kernel_attends_what_the_gather_attends():
    """``ops/pallas/latent.py`` ``latent_decode_attention`` over the
    pool as [L, P, ps, D] against softmax(q . rows) . rows of the rows
    each table names, one layer at a traced index; a row without
    context gives zeros."""
    from xllm_service_tpu.ops.pallas.latent import latent_decode_attention
    pool, q, table = _latent_case()
    ctx = jnp.asarray([37, 0, 64], jnp.int32)
    out = jax.jit(lambda li: latent_decode_attention(
        q, pool, table, ctx, li, scale=0.3, interpret=True))(
        jnp.asarray(1, jnp.int32))
    for b, n in enumerate(np.asarray(ctx)):
        rows = np.asarray(pool)[1][np.asarray(table)[b]].reshape(-1, 40)[:n]
        if n == 0:
            assert not np.asarray(out[b]).any()
            continue
        lg = np.asarray(q[b]) @ rows.T * 0.3
        w = np.exp(lg - lg.max(-1, keepdims=True))
        want = (w / w.sum(-1, keepdims=True)) @ rows
        np.testing.assert_allclose(np.asarray(out[b]), want, rtol=2e-5,
                                   atol=2e-5)


# (page size, row width, table columns) -> pages a grid step in float32,
# in bfloat16 (ops/plan.py ``latent_fold_pages``): one column is one page
# a step; a table of four folds whole; a table of six folds four and then
# two, the block's last two columns past the table; a float32 page of
# 1.3 MB folds alone under a table of three, and in bfloat16 two by two.
FOLD_SHAPES = {
    "K1-one-column": (16, 40, 1, 1, 1),
    "K4-divides-4": (16, 40, 4, 4, 4),
    "K4-of-6-columns": (16, 40, 6, 4, 4),
    "K1-or-2-large-page": (512, 576, 3, 1, 2),
}


@pytest.mark.parametrize("dtype", ["float32", "bfloat16"])
@pytest.mark.parametrize("shape", sorted(FOLD_SHAPES))
def test_the_latent_decode_kernel_folds_a_block_of_pages_a_grid_step(
        shape, dtype):
    """``latent_decode_attention`` against the hand reference (softmax(q
    . rows) . rows of the rows each table names, in float64) where a grid
    step folds K pages: contexts of nothing, one row, one short of a
    page, exactly a block, one past a block and the whole table; dead
    columns name page 0, whose rows are large and must weigh nothing; the
    layer index traced."""
    from xllm_service_tpu.ops.pallas.latent import latent_decode_attention
    from xllm_service_tpu.ops.plan import latent_fold_pages
    ps, D, MP, *pages = FOLD_SHAPES[shape]
    K = pages[dtype == "bfloat16"]
    dt = jnp.dtype(dtype)
    assert latent_fold_pages(ps, D, dt.itemsize, MP) == K
    whole = MP * ps
    ctx = np.asarray([0, 1, ps - 1, min(K * ps, whole),
                      min(K * ps + 1, whole), whole], np.int32)
    B, Hq, L = len(ctx), 4, 2
    rng = np.random.default_rng(MP)
    P = 1 + B * MP
    pool = rng.normal(size=(L, P, ps, D)) * 0.5
    pool[:, 0] = 1e3
    table = np.zeros((B, MP), np.int32)
    for b, n in enumerate(ctx):
        live = -(-int(n) // ps)
        table[b, :live] = 1 + b * MP + rng.permutation(MP)[:live]
    pool = jnp.asarray(pool, dt)
    q = jnp.asarray(rng.normal(size=(B, Hq, D)) * 0.3, dt)
    out = jax.jit(lambda li: latent_decode_attention(
        q, pool, jnp.asarray(table), jnp.asarray(ctx), li, scale=0.3,
        interpret=True))(jnp.asarray(1, jnp.int32))
    assert out.shape == (B, Hq, D) and out.dtype == dt
    out = np.asarray(out, np.float64)
    stored, qs = np.asarray(pool, np.float64), np.asarray(q, np.float64)
    tol = 2e-5 if dtype == "float32" else 2e-2
    for b, n in enumerate(ctx):
        if n == 0:
            assert not out[b].any()
            continue
        rows = stored[1][table[b]].reshape(-1, D)[:n]
        lg = qs[b] @ rows.T * 0.3
        w = np.exp(lg - lg.max(-1, keepdims=True))
        want = (w / w.sum(-1, keepdims=True)) @ rows
        np.testing.assert_allclose(out[b], want, rtol=tol, atol=tol)


def test_the_fold_is_what_the_shapes_say_and_the_plan_line_names_it(
        monkeypatch, caplog):
    """K comes from shapes in ONE function (ops/plan.py
    ``latent_fold_pages``): the kernel reads it there and so does the
    engine's plan line. At the benchmark cell's shapes (pages of 128 rows
    of 576 bfloat16 values in 640 lanes, a table of 96) the
    double-buffered block of 8 pages is 2.6 MB; no block is wider than
    its table; a model without latent attention names the paged
    kernel's fold in its place (PR 46: the same rule over a page's keys
    and values, ``paged_fold_pages``)."""
    import logging
    from xllm_service_tpu.ops.plan import latent_fold_pages
    assert latent_fold_pages(128, 576, 2, 96) == 8
    assert [latent_fold_pages(128, 576, 2, mp)
            for mp in (1, 2, 3, 4, 8, 64)] == [1, 2, 2, 4, 8, 8]
    assert latent_fold_pages(16, 40, 4, 4) == 4
    assert latent_fold_pages(128, 576, 4, 96) == 4     # float32 rows
    monkeypatch.setenv("XLLM_PALLAS", "1")
    with caplog.at_level(logging.INFO,
                         logger="xllm_service_tpu.runtime.engine"):
        eng = tiny_engine()
        dense = Engine(ModelConfig.tiny(), EngineConfig(
            page_size=16, num_pages=32, max_model_len=256,
            max_batch_size=2), seed=0)
    assert eng.plan.latent_decode and not dense.plan.latent_decode
    mp = eng.ecfg.max_pages_per_seq
    pages = latent_fold_pages(16, eng.kv[0].shape[-1], 4, mp)
    assert mp == 16 and pages == 16
    lines = [m for m in caplog.messages if m.startswith("engine plan:")]
    assert [m.rsplit("; ", 1)[1] for m in lines] == [
        "latent fold 16 pages a grid step, 1 steps of 16 columns",
        "paged fold 16 pages a grid step, 1 steps of 16 columns"]
    assert "decode walk 16 of 16 columns; latent fold" in lines[0]
    assert "decode walk 16 of 16 columns; paged fold" in lines[1]


def test_the_latent_writer_writes_a_steps_rows_and_nothing_else():
    from xllm_service_tpu.ops.pallas.latent import latent_kv_update_layer
    pool, _, table = _latent_case()
    new = jnp.asarray(np.random.default_rng(4).normal(size=(3, 40)),
                      jnp.float32)
    positions = jnp.asarray([36, 5, 70], jnp.int32)   # row 2: off the table
    active = jnp.asarray([True, False, True])
    out = np.asarray(jax.jit(lambda li: latent_kv_update_layer(
        pool, new, table, positions, active, li, interpret=True))(
        jnp.asarray(2, jnp.int32)))
    want = np.asarray(pool).copy()
    want[2, 3, 4] = np.asarray(new[0])      # position 36: page 3, slot 4
    assert np.array_equal(out, want)
    # ... as the scatter the other plans take writes it
    ref, = A.write_decode_kv_layer_xla(
        pool[:, :, :, None], None, new[:, None], None, table, positions,
        active, 2)
    assert np.array_equal(out, np.asarray(ref)[:, :, :, 0])


@pytest.mark.parametrize("seed", [0, 1])
def test_a_decode_step_under_the_latent_kernels_is_the_reference_step(seed):
    """``forward_decode`` under write-then-attend with the latent
    kernels (interpreted) against the XLA plan: logits, the pool and
    what the sparse layers counted."""
    mc = tiny_model()
    params = transformer.init_params(mc, jax.random.PRNGKey(seed))
    B, ps = 3, 16
    kv = tuple(jnp.asarray(np.random.default_rng(seed).normal(size=x.shape),
                           x.dtype) * 0.1
               for x in transformer.init_kv_cache(mc, 12, ps))
    table = jnp.asarray([[1, 2, 3, 0], [4, 5, 0, 0], [6, 7, 8, 9]],
                        jnp.int32)
    args = (jnp.asarray([5, 9, 11], jnp.int32),
            jnp.asarray([37, 20, 63], jnp.int32),
            jnp.asarray([True, False, True]), kv, table)
    outs = [transformer.forward_decode(
        params, mc, *args, return_stats=True,
        plan=KernelPlan(write_then_attend=True, interpret=True, **kw))
        for kw in ({}, dict(decode_attn=True, kv_writers=True,
                            latent_decode=True))]
    (lg0, kv0, st0), (lg1, kv1, st1) = outs
    assert kv1[0].shape == kv[0].shape and len(kv1) == 1
    live = np.asarray([0, 2])
    np.testing.assert_allclose(np.asarray(lg1)[live], np.asarray(lg0)[live],
                               rtol=2e-4, atol=2e-4)
    # layer 0 writes the same rows; later layers' follow the attention
    # before them, to rounding
    assert np.array_equal(np.asarray(kv1[0][0]), np.asarray(kv0[0][0]))
    np.testing.assert_allclose(np.asarray(kv1[0]), np.asarray(kv0[0]),
                               rtol=2e-4, atol=2e-4)
    assert not np.array_equal(np.asarray(kv1[0]), np.asarray(kv[0]))
    assert np.array_equal(np.asarray(st1["moe"]), np.asarray(st0["moe"]))


@pytest.mark.parametrize("program", ["decode", "prefill"])
def test_a_latent_step_for_the_chip_keeps_its_pool_in_place_and_unpadded(
        aot, program):
    """Compiled for a described v5e at the configuration's published
    widths (tools/aot_tpu.py), the pool pinned as the engine pins a
    latent pool on a TPU (``latent_pool_format``: the size-1 head axis
    outermost): the pool is aliased through the step, costs 640 / 576
    of its rows' bytes (row-major it costs 2.2 times them), and no
    temporary comes near it: neither a decode step's kernels, which
    take it as [L, P, ps, D], nor a prefill step's XLA attention copies
    it into another layout."""
    from xllm_service_tpu.runtime.engine import latent_pool_format
    aot_compile, sds = aot
    with open(os.path.join(CONFIG, "config.json")) as f:
        mc = ModelConfig.from_hf_config(json.load(f), "joyai")
    plan = KernelPlan(decode_attn=True, kv_writers=True, latent_decode=True,
                      expert_gmm=True, write_then_attend=True,
                      page_aligned=False, interpret=False)
    params = jax.tree_util.tree_map(
        lambda a: sds(a.shape, a.dtype), jax.eval_shape(
            lambda: transformer.init_params(mc, jax.random.PRNGKey(0))))
    P, ps, MP = 256, 128, 32
    pool = sds((mc.num_layers, P, ps, 1, mc.kv_cache_dim), jnp.bfloat16)
    pin = (latent_pool_format(pool.sharding),)
    if program == "decode":
        B = 8

        def step(params, tok, pos, act, kv, pt):
            return transformer.forward_decode(params, mc, tok, pos, act, kv,
                                              pt, plan=plan)
        args = (params, sds((B,), jnp.int32), sds((B,), jnp.int32),
                sds((B,), jnp.bool_), (pool,), sds((B, MP), jnp.int32))
        outs = (None, pin)
    else:
        def step(params, toks, start, lens, kv, pt):
            return transformer.forward_prefill(params, mc, toks, start, lens,
                                               kv, pt, plan=plan)
        args = (params, sds((2, 256), jnp.int32), sds((2,), jnp.int32),
                sds((2,), jnp.int32), (pool,), sds((2, MP), jnp.int32))
        outs = (None, None, pin)
    compiled = aot_compile(step, args, donate_argnums=(4,),
                           in_shardings=(None, None, None, None, pin, None),
                           out_shardings=outs)
    rows = mc.num_layers * P * ps * mc.kv_cache_dim * 2
    mem = compiled.memory_analysis()
    assert rows <= mem.alias_size_in_bytes <= 1.12 * rows
    assert mem.temp_size_in_bytes < rows // 2
    assert f"bf16[{mc.num_layers},{P},{ps},1,{mc.kv_cache_dim}]" \
        "{4,3,2,1,0" not in compiled.as_text()
