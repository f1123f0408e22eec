"""Test configuration: force JAX onto a virtual 8-device CPU platform.

Multi-chip TPU hardware is unavailable in CI; all sharding/parallelism tests
run against ``--xla_force_host_platform_device_count=8`` CPU devices, which
exercises the same Mesh/pjit/shard_map/collective code paths the TPU uses.
Must run before the first ``import jax`` anywhere in the test session.
"""

import os
import sys

os.environ["JAX_PLATFORMS"] = "cpu"
# One persistent compile cache for the session. The suite builds
# hundreds of engines and CLI workers whose step programs are the same
# few dozen; with the cache each distinct program is compiled once and
# every later engine, test and child process loads it. Fixed path inside
# the checkout (utils/jaxcache.py's rule); the caller's own setting wins.
os.environ.setdefault(
    "JAX_COMPILATION_CACHE_DIR",
    os.path.join(os.path.dirname(os.path.dirname(os.path.abspath(__file__))),
                 ".jax_cache", "cpu-tests"))
os.environ.setdefault("JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS", "0.3")
# Deterministic lock-order checking (utils/locks.py): every lock in the
# codebase is rank-ordered; inversions raise instead of deadlocking
# rarely. Must be set before any xllm_service_tpu import constructs locks.
os.environ.setdefault("XLLM_LOCK_CHECK", "1")
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture(scope="module")
def aot():
    """The offline v5e compile path (tools/aot_tpu.py: the TPU's compiler
    for a chip that is described and not attached; the runtime stays the
    CPU), or a skip where the image cannot describe the topology. JAX's
    persistent cache is off around it: an entry written for a described
    chip cannot be read back without one — the next compile of the same
    program would warn and compile again."""
    import jax
    import jax.numpy as jnp
    try:
        from tools.aot_tpu import aot_compile, sds
        sds((8, 128), jnp.float32)      # forces topology construction
    except Exception as e:  # noqa: BLE001 — environment-dependent
        pytest.skip(f"no offline TPU topology: {type(e).__name__}: {e}")
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_enable_compilation_cache
    jax.config.update("jax_enable_compilation_cache", False)
    compilation_cache.reset_cache()
    yield aot_compile, sds
    jax.config.update("jax_enable_compilation_cache", was)
    compilation_cache.reset_cache()


@pytest.fixture(scope="session")
def cpu_devices():
    import jax
    devs = jax.devices()
    assert len(devs) >= 8, f"expected >=8 virtual cpu devices, got {devs}"
    return devs


@pytest.fixture(autouse=True)
def _no_swallowed_lock_violations(request):
    """LockOrderViolation subclasses AssertionError, and several callback
    paths wrap client code in broad `except Exception` — a detected
    inversion could be swallowed there. The violation counter makes it
    fail the test anyway. Tests that provoke violations on purpose mark
    themselves ``expected_lock_violations``."""
    from xllm_service_tpu.utils import locks
    before = locks.violation_count()
    yield
    if request.node.get_closest_marker("expected_lock_violations"):
        return
    new = locks.violations()[before:]
    assert not new, f"lock-order violations were raised (and possibly " \
                    f"swallowed) during this test: {new}"


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "expected_lock_violations: test provokes lock-order "
        "violations on purpose (skips the swallowed-violation check)")
    config.addinivalue_line(
        "markers", "slow: excluded from the tier-1 budgeted run "
        "(`-m 'not slow'`); run explicitly or with -m slow")
