"""Sharding/collective tests on the virtual 8-device CPU mesh."""

import dataclasses

import numpy as np
import pytest

import jax
import jax.numpy as jnp
from jax.sharding import NamedSharding, PartitionSpec as P

from xllm_service_tpu.config import ModelConfig
from xllm_service_tpu.models import (
    init_params, init_kv_cache, forward_prefill, forward_decode)
from xllm_service_tpu.ops import mha_prefill
from xllm_service_tpu.parallel import (
    MeshSpec, make_mesh, shard_params, shard_kv_cache)
from xllm_service_tpu.parallel.ring import ring_attention_sharded


def _tiny(**kw):
    kw.setdefault("dtype", "float32")
    return dataclasses.replace(ModelConfig.tiny(), **kw)


def test_mesh_axes(cpu_devices):
    mesh = make_mesh(MeshSpec(dp=2, tp=4))
    assert mesh.axis_names == ("dp", "ep", "sp", "tp")
    assert mesh.devices.shape == (2, 1, 1, 4)
    with pytest.raises(ValueError):
        make_mesh(MeshSpec(dp=4, tp=4))


def test_tp_sharded_forward_matches_single_device(cpu_devices):
    """TP=4 prefill+decode must be numerically identical (up to fp
    reassociation) to the unsharded run — GSPMD inserts the collectives."""
    cfg = _tiny()
    params = init_params(cfg, jax.random.PRNGKey(0))
    kv = init_kv_cache(cfg, 8, 4, jnp.float32)
    pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    toks = jnp.asarray([[3, 1, 4, 1], [5, 9, 2, 0]], jnp.int32)
    lens = jnp.asarray([4, 3], jnp.int32)
    zero = jnp.zeros(2, jnp.int32)

    ref_last, _, ref_kv = forward_prefill(params, cfg, toks, zero, lens,
                                          kv, pt)

    mesh = make_mesh(MeshSpec(tp=4))
    sp_params = shard_params(params, mesh, cfg)
    sp_kv = shard_kv_cache(jax.tree_util.tree_map(jnp.copy, kv), mesh, cfg)
    with jax.set_mesh(mesh):
        got_last, _, got_kv = jax.jit(
            forward_prefill, static_argnums=(1,))(
                sp_params, cfg, toks, zero, lens, sp_kv, pt)
    np.testing.assert_allclose(np.asarray(got_last), np.asarray(ref_last),
                               rtol=2e-4, atol=2e-4)

    # Decode one step on both paths.
    nxt = jnp.asarray([7, 8], jnp.int32)
    pos = jnp.asarray([4, 3], jnp.int32)
    act = jnp.asarray([True, True])
    ref_logits, _ = forward_decode(params, cfg, nxt, pos, act, ref_kv, pt)
    with jax.set_mesh(mesh):
        got_logits, _ = jax.jit(forward_decode, static_argnums=(1,))(
            sp_params, cfg, nxt, pos, act, got_kv, pt)
    np.testing.assert_allclose(np.asarray(got_logits), np.asarray(ref_logits),
                               rtol=2e-4, atol=2e-4)


def test_ep_moe_sharded_forward(cpu_devices):
    cfg = _tiny(num_experts=4, num_experts_per_tok=2)
    params = init_params(cfg, jax.random.PRNGKey(1))
    kv = init_kv_cache(cfg, 8, 4, jnp.float32)
    pt = jnp.asarray([[1, 2]], jnp.int32)
    toks = jnp.asarray([[3, 1, 4, 1]], jnp.int32)
    lens = jnp.asarray([4], jnp.int32)
    zero = jnp.zeros(1, jnp.int32)
    ref_last, _, _ = forward_prefill(params, cfg, toks, zero, lens, kv, pt)

    mesh = make_mesh(MeshSpec(ep=4, tp=2))
    sp_params = shard_params(params, mesh, cfg)
    sp_kv = shard_kv_cache(kv, mesh, cfg)
    with jax.set_mesh(mesh):
        got_last, _, _ = jax.jit(forward_prefill, static_argnums=(1,))(
            sp_params, cfg, toks, zero, lens, sp_kv, pt)
    np.testing.assert_allclose(np.asarray(got_last), np.asarray(ref_last),
                               rtol=2e-4, atol=2e-4)


def test_sparse_moe_matches_dense_oracle(cpu_devices):
    """Top-k capacity dispatch (parallel/expert.py) must reproduce the
    dense every-expert oracle exactly when capacity admits every token
    (cf = E/k ⇒ C = N ⇒ no drops)."""
    sparse = _tiny(num_experts=4, num_experts_per_tok=2,
                   moe_capacity_factor=2.0)         # E/k = 2 → no drops
    dense = _tiny(num_experts=4, num_experts_per_tok=2,
                  moe_capacity_factor=0.0)
    params = init_params(sparse, jax.random.PRNGKey(3))
    toks = jnp.asarray([[3, 1, 4, 1, 5, 9, 2, 6]], jnp.int32)
    lens = jnp.asarray([8], jnp.int32)
    zero = jnp.zeros(1, jnp.int32)
    pt = jnp.asarray([[1, 2, 3]], jnp.int32)
    kv1 = init_kv_cache(sparse, 8, 4, jnp.float32)
    kv2 = init_kv_cache(dense, 8, 4, jnp.float32)
    ls, _, _ = forward_prefill(params, sparse, toks, zero, lens, kv1, pt)
    ld, _, _ = forward_prefill(params, dense, toks, zero, lens, kv2, pt)
    np.testing.assert_allclose(np.asarray(ls), np.asarray(ld),
                               rtol=2e-4, atol=2e-4)


def test_topk_dispatch_capacity_drop_renormalizes(cpu_devices):
    """Tokens routed past a full expert lose that expert but renormalize
    over survivors; dispatch slots never exceed capacity."""
    from xllm_service_tpu.parallel.expert import topk_dispatch

    # 4 tokens all prefer expert 0 (then expert 1); capacity 8-aligned
    # min is 8, so force a tiny cap directly.
    gates = jnp.asarray(np.tile([[0.7, 0.3, 0.0, 0.0]], (4, 1)),
                        jnp.float32)
    dispatch, combine = topk_dispatch(gates, k=2, cap=2)
    d = np.asarray(dispatch)
    # Each expert holds exactly its capacity (the first two tokens).
    assert d[:, 0].sum() == 2 and d[:, 1].sum() == 2
    c = np.asarray(combine).sum(axis=(1, 2))
    # Surviving tokens renormalize to 1; fully-dropped tokens contribute
    # nothing (the residual stream carries them).
    np.testing.assert_allclose(c, [1.0, 1.0, 0.0, 0.0], rtol=1e-5)


def test_topk_dispatch_valid_mask_excludes_padding(cpu_devices):
    """Invalid (padding / inactive-lane) tokens must not take capacity
    slots from real tokens (review finding: output depended on batch
    composition)."""
    from xllm_service_tpu.parallel.expert import topk_dispatch

    gates = jnp.asarray(np.tile([[0.9, 0.1]], (4, 1)), jnp.float32)
    valid = jnp.asarray([True, False, True, False])
    d, c = topk_dispatch(gates, k=1, cap=2, valid=valid)
    d = np.asarray(d)
    # Both real tokens (0 and 2) hold expert-0 slots; padding holds none.
    assert d[0, 0].sum() == 1 and d[2, 0].sum() == 1
    assert d[1].sum() == 0 and d[3].sum() == 0
    # Without the mask, padding token 1 steals the second slot and real
    # token 2 is dropped — the bug the mask exists to prevent.
    d_unmasked = np.asarray(topk_dispatch(gates, k=1, cap=2)[0])
    assert d_unmasked[2].sum() == 0


def test_ring_attention_matches_full(cpu_devices):
    rng = np.random.default_rng(7)
    B, T, Hq, Hkv, D, SP = 2, 32, 4, 2, 8, 8
    q = rng.standard_normal((B, T, Hq, D)).astype(np.float32)
    k = rng.standard_normal((B, T, Hkv, D)).astype(np.float32)
    v = rng.standard_normal((B, T, Hkv, D)).astype(np.float32)
    kv_len = np.array([32, 27], np.int32)

    ref = np.asarray(mha_prefill(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v),
        jnp.asarray(kv_len), jnp.zeros(B, jnp.int32)))

    mesh = make_mesh(MeshSpec(sp=SP))
    ring = ring_attention_sharded(mesh, "sp")
    got = np.asarray(jax.jit(ring)(
        jnp.asarray(q), jnp.asarray(k), jnp.asarray(v), jnp.asarray(kv_len)))
    # Padded-position outputs (global pos >= kv_len) are garbage in both
    # paths; compare valid positions only.
    for b in range(B):
        np.testing.assert_allclose(got[b, :kv_len[b]], ref[b, :kv_len[b]],
                                   rtol=2e-4, atol=2e-4)


def test_moe_grouped_dispatch_matches_dense_oracle(cpu_devices):
    """Group-chunked dispatch (G < N, with a ragged tail that exercises
    the padding path) must still reproduce the dense oracle when
    per-group capacity admits every token (cf ≥ E/k ⇒ C_g ≥ G)."""
    from xllm_service_tpu.parallel.expert import moe_mlp

    rng = np.random.default_rng(11)
    E, k, D, F = 4, 2, 16, 32
    B, T = 2, 37                       # N = 74: 9 groups of 8 + padding
    x = jnp.asarray(rng.standard_normal((B, T, D)), jnp.float32)
    router = jnp.asarray(rng.standard_normal((D, E)) * 0.5, jnp.float32)
    gate = jnp.asarray(rng.standard_normal((E, D, F)) * 0.1, jnp.float32)
    up = jnp.asarray(rng.standard_normal((E, D, F)) * 0.1, jnp.float32)
    down = jnp.asarray(rng.standard_normal((E, F, D)) * 0.1, jnp.float32)
    valid = jnp.asarray(rng.random((B, T)) > 0.2)

    out, dropped = moe_mlp(x, router, gate, up, down, k,
                           capacity_factor=float(E) / k, valid=valid,
                           group_size=8)
    assert int(dropped) == 0

    # Dense oracle on the same weights.
    gates = jax.nn.softmax((x @ router).astype(jnp.float32), axis=-1)
    topv, topi = jax.lax.top_k(gates, k)
    topv = topv / jnp.sum(topv, axis=-1, keepdims=True)
    w = np.zeros((B, T, E), np.float32)
    for b in range(B):
        for t in range(T):
            for j in range(k):
                w[b, t, int(topi[b, t, j])] += float(topv[b, t, j])
    h = jax.nn.silu(jnp.einsum("btd,edf->btef", x, gate)) \
        * jnp.einsum("btd,edf->btef", x, up)
    ref = jnp.einsum("btef,efd->bted", h, down)
    ref = np.asarray(jnp.einsum("bted,bte->btd", ref, jnp.asarray(w)))
    got = np.asarray(out)
    v = np.asarray(valid)
    np.testing.assert_allclose(got[v], ref[v], rtol=2e-4, atol=2e-4)


def test_moe_grouped_dispatch_memory_linear(cpu_devices):
    """The dispatch/combine masks must be [groups, G, E, C_g] — linear in
    window length — not the round-2 [N, E, k·cf·N/E] quadratic blowup
    (VERDICT r2 weak #4: ~2 GB per layer call at an 8k window)."""
    from xllm_service_tpu.parallel.expert import moe_mlp

    E, k, D, F, G = 8, 2, 8, 8, 512
    N = 8192
    x = jnp.zeros((1, N, D), jnp.float32)
    router = jnp.zeros((D, E), jnp.float32)
    gate = jnp.zeros((E, D, F), jnp.float32)
    up = jnp.zeros((E, D, F), jnp.float32)
    down = jnp.zeros((E, F, D), jnp.float32)
    jaxpr = jax.make_jaxpr(
        lambda *a: moe_mlp(*a, k, capacity_factor=2.0, group_size=G))(
        x, router, gate, up, down)

    def max_intermediate_bytes(jpr):
        worst = 0
        for eqn in jpr.eqns:
            for var in eqn.outvars:
                aval = getattr(var, "aval", None)
                if aval is not None and hasattr(aval, "shape"):
                    n = int(np.prod(aval.shape)) * aval.dtype.itemsize
                    worst = max(worst, n)
            for sub in eqn.params.values():
                if hasattr(sub, "jaxpr"):
                    inner = sub.jaxpr if hasattr(sub.jaxpr, "eqns") \
                        else sub
                    worst = max(worst, max_intermediate_bytes(inner))
        return worst

    worst = max_intermediate_bytes(jaxpr.jaxpr)
    # Grouped masks: C_g = align8(int(512·2·2/8)+1) = 264, so each of
    # dispatch/combine is 16 groups × 512 × 8 × 264 × 4 B ≈ 33 MiB; the
    # largest observed intermediate is the fused pair (~66 MiB). The old
    # global mask alone would be 8192 × 8 × 4096 × 4 B = 1 GiB. Bound at
    # ~2x the fused pair — far below any quadratic resurfacing.
    assert worst <= 128 * 1024 * 1024, \
        f"quadratic intermediate resurfaced: {worst / 2**20:.0f} MiB"


def test_moe_drop_accounting_surfaces_in_engine(cpu_devices):
    """Force drops with a sub-guarantee capacity factor and assert the
    engine counts them into load_metrics (heartbeat visibility)."""
    import dataclasses as _dc
    from xllm_service_tpu.config import EngineConfig
    from xllm_service_tpu.runtime.engine import Engine, EngineRequest
    from xllm_service_tpu.utils.types import SamplingParams

    # G=32, cf=0.25 → cap = align8(int(32·2·0.25/4)+1) = 8 slots/expert,
    # vs an expected per-expert load of 16 — drops are guaranteed.
    cfg = _dc.replace(_tiny(num_experts=4, num_experts_per_tok=2),
                      moe_capacity_factor=0.25, moe_group_size=32)
    eng = Engine(cfg, EngineConfig(page_size=4, num_pages=32,
                                   max_model_len=64, max_batch_size=2,
                                   max_prefill_tokens=64,
                                   prefill_buckets=(16, 32, 64)), seed=0)
    eng.add_request(EngineRequest(
        request_id="drop", token_ids=list(range(1, 33)),
        sampling=SamplingParams(max_tokens=4, temperature=0.0)))
    for _ in range(100):
        if not eng.has_work():
            break
        eng.step()
    lm = eng.load_metrics()
    assert lm["moe_dropped_tokens"] > 0
