"""What keeps a CPU run, a stale artefact or a moved cache from looking
like a chip run (PR 22): the compile cache is placed from outside or at
one fixed path, a backend that fails to initialise is an error, native
artefacts are named by their source, and ``chip_smoke.py`` fails off
the chip."""

import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


class TestCompileCachePlacement:
    def _updates(self, monkeypatch):
        """enable_compile_cache() with jax.config.update recorded, in a
        process that is not pinned to the CPU (conftest pins this one)."""
        import jax

        from xllm_service_tpu.utils import jaxcache
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
        seen = {}
        monkeypatch.setattr(jax.config, "update",
                            lambda k, v: seen.__setitem__(k, v))
        return jaxcache, seen

    def test_variable_set_code_sets_no_directory(self, monkeypatch,
                                                 tmp_path):
        jaxcache, seen = self._updates(monkeypatch)
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
        assert jaxcache.enable_compile_cache() == str(tmp_path)
        assert "jax_compilation_cache_dir" not in seen
        assert seen["jax_persistent_cache_min_compile_time_secs"] == 0.0

    def test_variable_unset_cache_is_in_the_checkout(self, monkeypatch):
        jaxcache, seen = self._updates(monkeypatch)
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        want = os.path.join(ROOT, ".jax_cache")
        assert jaxcache.enable_compile_cache() == want
        assert seen["jax_compilation_cache_dir"] == want

    def test_same_fixed_path_in_two_processes(self):
        """No pid, time or temporary name: the path is part of the
        cache's key, so a directory that moves never hits."""
        env = {k: v for k, v in os.environ.items()
               if k != "JAX_COMPILATION_CACHE_DIR"}
        code = ("from xllm_service_tpu.utils import jaxcache; "
                "print(jaxcache.cache_dir())")
        got = [subprocess.run([sys.executable, "-c", code], cwd=ROOT,
                              env=env, capture_output=True, text=True,
                              check=True, timeout=60).stdout.strip()
               for _ in range(2)]
        assert got == [os.path.join(ROOT, ".jax_cache")] * 2


def test_on_tpu_lets_a_backend_failure_through(monkeypatch):
    """A backend that fails to initialise is an error, not "off TPU":
    that answer would switch the kernels off and the interpreter on."""
    import jax

    from xllm_service_tpu.ops import plan

    def broken():
        raise RuntimeError("Unable to initialize backend 'tpu'")
    monkeypatch.setattr(jax, "devices", broken)
    monkeypatch.delenv("XLLM_PALLAS", raising=False)
    monkeypatch.delenv("XLLM_PALLAS_INTERPRET", raising=False)
    from xllm_service_tpu.config import EngineConfig, ModelConfig
    ecfg = EngineConfig(page_size=8, num_pages=16, max_model_len=64)
    for ask in (plan._on_tpu, plan.default_interpret,
                lambda: plan.KernelPlan.from_env(ModelConfig.tiny(), ecfg)):
        with pytest.raises(RuntimeError, match="Unable to initialize"):
            ask()


def test_native_artifact_name_follows_source_hash(monkeypatch, tmp_path):
    """A leftover build/native/* from older source can never be loaded
    for newer source: the name changes with the text, whatever the
    mtimes say."""
    from xllm_service_tpu.utils import native_build
    (tmp_path / "csrc").mkdir()
    src = tmp_path / "csrc" / "thing.cpp"
    src.write_text('extern "C" int answer() { return 1; }\n')
    monkeypatch.setattr(native_build, "_ROOT", str(tmp_path))
    flags = ("-O1", "-shared", "-fPIC")
    first = native_build.build_artifact("thing.cpp", "libthing", ".so",
                                        flags)
    if first is None:
        pytest.skip("no C++ toolchain")
    assert os.path.exists(first) and first == native_build.artifact_path(
        "thing.cpp", "libthing", ".so", flags)
    src.write_text('extern "C" int answer() { return 2; }\n')
    os.utime(src, (1, 1))           # source now looks OLDER than the .so
    second = native_build.build_artifact("thing.cpp", "libthing", ".so",
                                         flags)
    assert second != first and os.path.exists(second)
    assert native_build.artifact_path("missing.cpp", "x", "", ()) is None


def test_chip_smoke_fails_off_the_chip():
    """On the CPU the smoke exits non-zero and never prints ``"ok":
    true``: its parity child asks JAX for the device and refuses."""
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=ROOT,
                          capture_output=True, text=True, timeout=300,
                          env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert proc.returncode != 0
    assert '"ok": true' not in proc.stdout
    assert "parity needs a TPU" in proc.stdout + proc.stderr


def test_sharded_engine_traces_the_reference_path(monkeypatch, cpu_devices):
    """A Mosaic kernel cannot be partitioned over a mesh automatically
    (tests/test_chip_compile.py shows the compiler's refusal), so an
    engine on a mesh resolves its plan to the XLA reference everywhere,
    whatever the kernel gates say — and a single-device engine, under
    the same gates, keeps its kernels."""
    import jax
    import jax.numpy as jnp

    from xllm_service_tpu.config import EngineConfig, ModelConfig
    from xllm_service_tpu.parallel.mesh import MeshSpec, make_mesh
    from xllm_service_tpu.runtime import engine as E

    monkeypatch.setenv("XLLM_PALLAS", "1")      # on (interpreted here)
    ecfg = EngineConfig(page_size=8, num_pages=16, max_model_len=64,
                        max_batch_size=2, prefill_buckets=(16, 32))

    def decode_jaxpr(eng) -> str:
        B = ecfg.max_batch_size
        return str(eng._jit_decode.trace(
            eng.params, jnp.zeros((B, E._PACK_COLS + 1), jnp.int32),
            eng.kv, *eng._sampling_tensors([], B), jax.random.PRNGKey(0),
            None, *eng._batch_bias([], B, eng.cfg.vocab_size)).jaxpr)

    sharded = E.Engine(ModelConfig.tiny(), ecfg,
                       mesh=make_mesh(MeshSpec(tp=2)))
    from xllm_service_tpu.ops.plan import KernelPlan
    assert sharded.plan == KernelPlan(interpret=True)
    assert not sharded.kv_pinned
    assert "pallas_call" not in decode_jaxpr(sharded)
    single = E.Engine(ModelConfig.tiny(), ecfg)
    assert single.plan == KernelPlan(
        decode_attn=True, kv_writers=True, write_then_attend=True,
        interpret=True)
    assert single.kv_pinned
    assert "pallas_call" in decode_jaxpr(single)
