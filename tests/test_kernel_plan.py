"""The one decision about attention paths (ops/plan.py ``KernelPlan`` and
its resolver ``KernelPlan.from_env``): its truth table, and the seam it
makes — an engine decides once, carries the plan as a jit static, and
never reads a kernel gate again.

The table is the specification: it was written from the gate functions
this module replaced (``enabled``, ``prefill_kernel_enabled``,
``mla_kernel_enabled``, ``_kv_update_kernel_enabled``,
``ragged_attn_enabled``, ``default_interpret``, the thread-local
``reference_path`` and the env parsing of ``EngineConfig``), case by
case, before any of them moved.
"""

import dataclasses
import inspect
import os

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from xllm_service_tpu.config import EngineConfig, ModelConfig
from xllm_service_tpu.models import transformer
from xllm_service_tpu.ops import pallas
from xllm_service_tpu.ops import plan as plan_mod
from xllm_service_tpu.ops.plan import KernelPlan
from xllm_service_tpu.runtime import engine as E

GATES = ("XLLM_PALLAS", "XLLM_PALLAS_PREFILL", "XLLM_PALLAS_KV",
         "XLLM_PALLAS_INTERPRET", "XLLM_RAGGED_ATTN",
         "XLLM_WRITE_THEN_ATTEND")

# What "the kernels are on" resolves to, before the opt-ins.
ON = dict(decode_attn=True, kv_writers=True, write_then_attend=True)

# (id, on a TPU, on a mesh, environment, model is MLA, EngineConfig
#  fields, the plan's fields that differ from KernelPlan()'s defaults
#  — ``interpret`` apart, which is "anywhere but a TPU" unless stated).
TABLE = [
    ("cpu-nothing-set", False, False, {}, False, {}, {}),
    ("tpu-nothing-set", True, False, {}, False, {}, ON),
    ("cpu-pallas-1", False, False, {"XLLM_PALLAS": "1"}, False, {}, ON),
    ("cpu-pallas-yes", False, False, {"XLLM_PALLAS": " yes "}, False, {},
     ON),
    ("tpu-pallas-0", True, False, {"XLLM_PALLAS": "0"}, False, {}, {}),
    ("tpu-pallas-no", True, False, {"XLLM_PALLAS": "no"}, False, {}, {}),
    ("tpu-pallas-unreadable", True, False, {"XLLM_PALLAS": "maybe"},
     False, {}, ON),
    # The two opt-ins need the base gate, and "1" exactly.
    ("prefill-opt-in", False, False,
     {"XLLM_PALLAS": "1", "XLLM_PALLAS_PREFILL": "1"}, False, {},
     dict(ON, prefill_attn=True)),
    ("prefill-needs-base", False, False, {"XLLM_PALLAS_PREFILL": "1"},
     False, {}, {}),
    ("prefill-off-on-tpu-by-default", True, False,
     {"XLLM_PALLAS_PREFILL": "true"}, False, {}, ON),
    # A latent pool's decode attention takes the kernel wherever the
    # kernels are on (decided on the chip, PR 36), and only there.
    ("mla-kernel-on-tpu", True, False, {}, True, {},
     dict(ON, latent_decode=True)),
    ("mla-needs-base", True, False, {"XLLM_PALLAS": "0"}, True, {}, {}),
    # ... and only as a write-then-attend pair (ops/pallas/latent.py).
    ("mla-kernels-need-write-then-attend", True, False,
     {"XLLM_WRITE_THEN_ATTEND": "0"}, True, {},
     dict(ON, write_then_attend=False)),
    ("mla-kernel-interpreted-on-cpu", False, False, {"XLLM_PALLAS": "1"},
     True, {}, dict(ON, latent_decode=True)),
    # The writers follow the base gate, can be switched off alone, and
    # can be FORCED on with the attention kernels off.
    ("kv-forced-on-kernels-off", False, False,
     {"XLLM_PALLAS": "0", "XLLM_PALLAS_KV": "1"}, False, {},
     dict(kv_writers=True)),
    ("kv-forced-on-cpu-default", False, False, {"XLLM_PALLAS_KV": "1"},
     False, {}, dict(kv_writers=True)),
    ("kv-off-kernels-on", True, False, {"XLLM_PALLAS_KV": "0"}, False,
     {}, dict(ON, kv_writers=False)),
    # A mesh: the XLA reference everywhere, whatever the gates say.
    ("mesh-every-gate-set", True, True,
     {"XLLM_PALLAS": "1", "XLLM_PALLAS_PREFILL": "1",
      "XLLM_PALLAS_KV": "1"}, True, {}, {}),
    ("mesh-tpu-default", True, True, {}, False, {}, {}),
    ("mesh-wta-asked-for", False, True, {"XLLM_WRITE_THEN_ATTEND": "1"},
     False, {}, dict(write_then_attend=True)),
    ("mesh-ragged-asked-for", False, True, {"XLLM_RAGGED_ATTN": "1"},
     False, {}, dict(mixed_step=True)),
    # Write-then-attend: auto follows the kernels; the field states it;
    # the environment wins over the field.
    ("wta-env-off-kernels-on", True, False,
     {"XLLM_WRITE_THEN_ATTEND": "0"}, False, {},
     dict(ON, write_then_attend=False)),
    ("wta-env-on-kernels-off", False, False,
     {"XLLM_WRITE_THEN_ATTEND": "1"}, False, {},
     dict(write_then_attend=True)),
    ("wta-field-on", False, False, {}, False,
     dict(write_then_attend=True), dict(write_then_attend=True)),
    ("wta-field-off-kernels-on", True, False, {}, False,
     dict(write_then_attend=False), dict(ON, write_then_attend=False)),
    ("wta-env-beats-field-on", False, False,
     {"XLLM_WRITE_THEN_ATTEND": "0"}, False,
     dict(write_then_attend=True), {}),
    ("wta-env-beats-field-off", False, False,
     {"XLLM_WRITE_THEN_ATTEND": "true"}, False,
     dict(write_then_attend=False), dict(write_then_attend=True)),
    # The ragged mixed program: off unless asked for, never for MLA.
    ("ragged-env-on", True, False, {"XLLM_RAGGED_ATTN": "1"}, False, {},
     dict(ON, mixed_step=True)),
    ("ragged-field-on", False, False, {}, False, dict(ragged_attn=True),
     dict(mixed_step=True)),
    ("ragged-env-beats-field", False, False, {"XLLM_RAGGED_ATTN": "0"},
     False, dict(ragged_attn=True), {}),
    ("ragged-never-for-mla", True, False, {"XLLM_RAGGED_ATTN": "1"}, True,
     dict(ragged_attn=True), dict(ON, latent_decode=True)),
    # Prefill windows start on pages iff every bucket is a page multiple.
    ("unaligned-buckets", True, False, {}, False,
     dict(prefill_buckets=(12, 32)), dict(ON, page_aligned=False)),
    ("aligned-buckets", False, False, {}, False,
     dict(prefill_buckets=(8, 16, 64)), {}),
    # Interpreter or Mosaic: the platform, unless stated.
    ("mosaic-forced-off-tpu", False, False,
     {"XLLM_PALLAS": "1", "XLLM_PALLAS_INTERPRET": "0"}, False, {},
     dict(ON, interpret=False)),
    ("interpreter-forced-on-tpu", True, False,
     {"XLLM_PALLAS_INTERPRET": "1"}, False, {}, dict(ON, interpret=True)),
]


def _ecfg(**kw):
    kw.setdefault("prefill_buckets", (16, 32))
    return EngineConfig(page_size=8, num_pages=16, max_model_len=64,
                        max_batch_size=2, **kw)


def _mla_cfg():
    return dataclasses.replace(
        ModelConfig.tiny(), kv_lora_rank=32, qk_nope_head_dim=16,
        qk_rope_head_dim=8, v_head_dim=16)


@pytest.mark.parametrize("on_tpu,mesh,env,want", [
    (True, False, {}, True), (False, False, {}, False),
    (False, False, {"XLLM_PALLAS": "1"}, True),
    (True, False, {"XLLM_PALLAS": "0"}, False), (True, True, {}, False)])
def test_the_expert_layers_grouped_matmul_follows_the_base_gate(
        monkeypatch, no_gates, on_tpu, mesh, env, want):
    """``expert_gmm``: the Pallas grouped matmul wherever the kernels are
    on, for the one family whose sparse layers call it (latent attention
    with experts); XLA's ``ragged_dot`` elsewhere, and on a mesh."""
    monkeypatch.setattr(plan_mod, "_on_tpu", lambda: on_tpu)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    sparse = dataclasses.replace(_mla_cfg(), num_experts=8,
                                 num_experts_per_tok=2)
    got = KernelPlan.from_env(sparse, _ecfg(), object() if mesh else None)
    assert got.expert_gmm is want
    assert got.uses_kernels or not want
    for other in (_mla_cfg(), ModelConfig.tiny(num_experts=4)):
        assert not KernelPlan.from_env(other, _ecfg(), None).expert_gmm


@pytest.fixture
def no_gates(monkeypatch):
    for name in GATES:
        monkeypatch.delenv(name, raising=False)


@pytest.mark.parametrize(
    "on_tpu,mesh,env,mla,fields,differs",
    [pytest.param(*row[1:], id=row[0]) for row in TABLE])
def test_resolver_truth_table(monkeypatch, no_gates, on_tpu, mesh, env,
                              mla, fields, differs):
    monkeypatch.setattr(plan_mod, "_on_tpu", lambda: on_tpu)
    for k, v in env.items():
        monkeypatch.setenv(k, v)
    model = _mla_cfg() if mla else ModelConfig.tiny()
    assert model.mla == mla
    want = dataclasses.replace(
        KernelPlan(), **{"interpret": not on_tpu, **differs})
    got = KernelPlan.from_env(model, _ecfg(**fields),
                              object() if mesh else None)
    assert got == want
    # The configuration is an input, not a second place the environment
    # lands in (EngineConfig used to parse these two itself).
    assert _ecfg().write_then_attend is None
    assert _ecfg().ragged_attn is None


def test_mixed_program_is_the_plan_with_three_fields_replaced():
    plan = KernelPlan(decode_attn=True, kv_writers=True, mixed_step=True,
                      page_aligned=True)
    mixed = plan.mixed_program()
    assert (mixed.ragged_rows, mixed.write_then_attend,
            mixed.page_aligned) == (True, True, False)
    assert dataclasses.replace(
        mixed, ragged_rows=False, write_then_attend=False,
        page_aligned=True) == plan
    assert hash(plan) != hash(mixed)         # a jit static: hashable


def _prefill_args(eng, B, T, mp):
    return (eng.params, jnp.zeros((B, E._PREFILL_HDR + T + mp), jnp.int32),
            eng.kv, *eng._sampling_tensors([], B), jax.random.PRNGKey(0),
            None, None, None, *eng._batch_bias([], B, eng.cfg.vocab_size),
            None, T)


def _decode_args(eng, mp):
    B = eng.ecfg.max_batch_size
    return (eng.params, jnp.zeros((B, E._PACK_COLS + mp), jnp.int32),
            eng.kv, *eng._sampling_tensors([], B), jax.random.PRNGKey(0),
            None, *eng._batch_bias([], B, eng.cfg.vocab_size))


def test_engine_once_built_does_not_read_the_gates(monkeypatch, no_gates):
    """Every program an engine traces after construction — a new prefill
    bucket, a new table width, the mixed program — is
    traced under the plan it resolved, with no look at the environment:
    reading any kernel gate raises here."""
    monkeypatch.setenv("XLLM_PALLAS", "1")       # kernels on, interpreted
    monkeypatch.setenv("XLLM_PALLAS_PREFILL", "1")
    eng = E.Engine(ModelConfig.tiny(),
                   _ecfg(ragged_attn=True))
    assert eng.plan.decode_attn and eng.plan.prefill_attn \
        and eng.plan.mixed_step and eng.plan.interpret
    real = os.environ.get
    asked = []

    def guarded(name, default=None):
        if name.startswith("XLLM_PALLAS") or name in (
                "XLLM_RAGGED_ATTN", "XLLM_WRITE_THEN_ATTEND"):
            asked.append(name)
            raise AssertionError(f"{name} read after the engine was built")
        return real(name, default)
    monkeypatch.setattr(os.environ, "get", guarded)
    with pytest.raises(AssertionError):          # the guard itself works
        pallas.default_interpret()
    asked.clear()
    traced = [
        eng._jit_prefill.trace(*_prefill_args(eng, 1, 32, 8)),
        eng._jit_ragged.trace(*_prefill_args(eng, 2, 16, 4)),
        eng._jit_decode.trace(*_decode_args(eng, 4)),
    ]
    assert asked == []
    assert all("pallas_call" in str(t.jaxpr) for t in traced)


def test_flip_after_construction_changes_nothing(monkeypatch, no_gates):
    """The stale-trace hazard, closed: a gate flipped under a running
    engine changes neither its plan nor a program it traces afterwards;
    an engine built after the flip sees it."""
    monkeypatch.setenv("XLLM_PALLAS", "1")
    eng = E.Engine(ModelConfig.tiny(), _ecfg())
    plan = eng.plan
    assert "pallas_call" in str(eng._jit_decode.trace(
        *_decode_args(eng, 1)).jaxpr)
    monkeypatch.setenv("XLLM_PALLAS", "0")
    monkeypatch.setenv("XLLM_WRITE_THEN_ATTEND", "0")
    assert eng.plan is plan
    assert "pallas_call" in str(eng._jit_decode.trace(
        *_decode_args(eng, 2)).jaxpr)            # a width never traced
    after = E.Engine(ModelConfig.tiny(), _ecfg(), params=eng.params)
    assert not (after.plan.decode_attn or after.plan.kv_writers
                or after.plan.write_then_attend)
    assert "pallas_call" not in str(after._jit_decode.trace(
        *_decode_args(after, 1)).jaxpr)


def test_two_plans_are_two_cache_entries_of_one_jitted_forward(no_gates):
    """The plan is a static of the forward pass, so part of jit's cache
    key: one jitted function serves two plans as two programs, and can
    never hand the program of one to a call under the other (what
    chip_smoke's parity phase had to rebuild its closures for)."""
    cfg = ModelConfig.tiny()
    ps, pages, B = 8, 8, 2
    params = transformer.init_params(cfg, jax.random.PRNGKey(0))
    fwd = jax.jit(transformer.forward_decode,
                  static_argnames=("cfg", "plan"))
    reference = KernelPlan()
    served = KernelPlan(decode_attn=True, kv_writers=True,
                        write_then_attend=True, interpret=True)
    tok = jnp.asarray([5, 9], jnp.int32)
    pos = jnp.asarray([3, 0], jnp.int32)
    act = jnp.asarray([True, True])
    pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)

    def run(plan):
        kv = transformer.init_kv_cache(cfg, pages, ps)
        logits, _ = fwd(params, cfg, tok, pos, act, kv, pt, plan=plan)
        return np.asarray(logits, np.float32)
    a, b = run(reference), run(served)
    assert fwd._cache_size() == 2
    assert np.abs(a - b).max() / a.std() < 0.05      # bf16, two orders
    run(reference), run(served)
    assert fwd._cache_size() == 2
    assert "pallas_call" in str(jax.make_jaxpr(
        lambda *a: transformer.forward_decode(*a[:1], cfg, *a[1:],
                                              plan=served))(
        params, tok, pos, act,
        transformer.init_kv_cache(cfg, pages, ps), pt))


_SWITCHES = [f.name for f in dataclasses.fields(KernelPlan)
             if f.name != "interpret"]


@pytest.mark.parametrize("field", _SWITCHES)
def test_the_ring_is_written_in_place_under_ssm_decode_and_no_other_field(
        field):
    """A state layer's filter ring in a decode step (a window of ONE
    token): the in-place writer of ops/pallas/ring_update.py where
    ``plan.ssm_decode``, the bit that puts the layer's state update in
    place too, and XLA's scatter under every other field flipped alone;
    a prefill window keeps the scatter under every plan."""
    import types
    cfg = types.SimpleNamespace(conv_kernel=4)
    flipped = not getattr(KernelPlan(), field)
    plan = KernelPlan(**{field: flipped}, interpret=True)
    tails = jnp.zeros((3, 6, 4, 128), jnp.bfloat16)
    pt = jnp.asarray([[1, 2], [3, 4]], jnp.int32)
    start = jnp.asarray([9, 3], jnp.int32)

    def text(tokens):
        zz = jnp.zeros((2, 3 + tokens, 128), jnp.bfloat16)
        return str(jax.make_jaxpr(lambda t, z: transformer._ring_write(
            cfg, t, 1, pt, start, jnp.full((2,), tokens, jnp.int32), z, 8,
            plan))(tails, zz))
    assert ("pallas_call" in text(1)) == (field == "ssm_decode")
    assert ("scatter" in text(1)) == (field != "ssm_decode")
    assert "pallas_call" not in text(8) and "scatter" in text(8)


def test_no_thread_local_and_no_trace_time_gate_left():
    """``ops/pallas`` and ``ops/plan.py`` keep no per-thread state and no
    gate that a trace could call; ``models``, ``ops/attention`` and the
    kernels' package read no environment; the plan's module loads no
    kernel (an engine that needs them loads them when it is built)."""
    import subprocess
    import sys
    for mod in (pallas, plan_mod):
        assert "threading" not in inspect.getsource(mod)
        for gone in ("reference_path", "reference_only", "enabled",
                     "mla_kernel_enabled", "prefill_kernel_enabled",
                     "ragged_attn_enabled"):
            assert not hasattr(mod, gone), (mod.__name__, gone)
    from xllm_service_tpu.ops import attention
    for mod in (transformer, attention, pallas):
        assert "environ" not in inspect.getsource(mod), mod.__name__
    code = ("import sys, xllm_service_tpu.models.transformer, "
            "xllm_service_tpu.ops.plan; "
            "print('jax.experimental.pallas' in sys.modules)")
    out = subprocess.run([sys.executable, "-c", code], check=True,
                         capture_output=True, text=True, timeout=120,
                         env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.stdout.strip() == "False"
