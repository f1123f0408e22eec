"""Where this repo's processes keep JAX's persistent compilation cache.

One rule, one helper. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX's
own reading of it stands and this code sets no directory — whoever
launches the process decides where the cache lives. Where it is not
set, the cache is ``<checkout>/.jax_cache``: a fixed path, because the
path is part of the cache key and a directory that moves never hits.
Nothing here is derived from a pid, a temporary name or the time.

Every process that compiles (worker, chip_smoke.py's children,
``__graft_entry__``) calls ``enable_compile_cache()`` before its first
compilation, so a program compiled once is loaded, not recompiled, by
every later process that shares the directory.

A process pinned to the CPU (``JAX_PLATFORMS=cpu``: the tests, the
rehearsals) keeps no cache. What it compiles is small, XLA:CPU
executables bake in the build host's machine features (an entry loaded
on a lesser host can SIGILL), and a program compiled offline for a
described TPU is written but can never be read back without the chip.
"""

from __future__ import annotations

import logging
import os

logger = logging.getLogger(__name__)

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(os.path.dirname(os.path.dirname(
    os.path.dirname(os.path.abspath(__file__)))), ".jax_cache")


def cache_dir() -> str:
    """The directory in effect — readable without importing JAX (the
    chip smoke's parent prints it and must stay off JAX)."""
    return os.environ.get(ENV_VAR) or DEFAULT_DIR


def enable_compile_cache() -> str:
    """Turn the persistent cache on for this process (unless it is
    pinned to the CPU) and return its directory. Sets the directory
    only where the environment does not."""
    if os.environ.get("JAX_PLATFORMS", "").strip().lower() == "cpu":
        return ""
    import jax
    if not os.environ.get(ENV_VAR):
        jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    # Cache every program: the step programs this repo serves compile in
    # tens of seconds, the small ones around them in well under JAX's
    # default one-second floor, and a warm start needs all of them.
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return cache_dir()


def disable_compile_cache(reason: str) -> None:
    """Switch the persistent cache off for the rest of this process.

    For programs that must never be LOADED from it: on jax 0.9.0 /
    libtpu 0.0.34 an executable read back from the persistent cache has
    lost every non-default entry layout it was compiled with (measured
    on a v5e, PR 22: a fresh compile honours ``Format(Layout(...))`` on
    inputs and outputs, the cache hit of the same program returns
    default-layout arrays). Programs compiled before the call stay
    cached; nothing compiled after it is read or written."""
    import jax
    from jax.experimental.compilation_cache import compilation_cache
    if jax.config.jax_compilation_cache_dir \
            and jax.config.jax_enable_compilation_cache:
        jax.config.update("jax_enable_compilation_cache", False)
        compilation_cache.reset_cache()
        logger.warning("persistent compile cache off for this process: %s",
                       reason)
