"""Central jax import point.

Every module that uses jax imports it via ``from shadow_tpu._jax import
jax, jnp`` so that x64 mode (int64 sim times) is enabled exactly once,
before any tracing, while jax-free paths (CLI --show-config, config
parsing, the pure-Python engine) never pay the jax import cost.
"""

import os

import jax
import jax.numpy as jnp

jax.config.update("jax_enable_x64", True)

# The checkout this package was imported from (the package is used in
# place, never installed).
_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def cache_root() -> str:
    """The one compile-cache rule. ``JAX_COMPILATION_CACHE_DIR`` when
    it is set (jax then places its own cache there and this package
    sets no other directory); otherwise the fixed, git-ignored
    ``<checkout>/.cache/jax``. JAX's persistent XLA cache lives in the
    root and the engine's AOT executables (device/aotcache.py) in its
    ``aot`` subdirectory."""
    return (os.environ.get("JAX_COMPILATION_CACHE_DIR")
            or os.path.join(_REPO_ROOT, ".cache", "jax"))


# persistent compilation cache: the tuning sweep, bench and profiler
# compile the same few programs across separate processes, and the
# disk cache turns every repeat into a hit. Opt-out via
# SHADOW_TPU_NO_CACHE. This is JAX's built-in TRACING-level cache; it
# also serves as the fallback for the engine's AOT executable cache on
# backends whose PJRT client cannot serialize executables.
if not os.environ.get("SHADOW_TPU_NO_CACHE"):
    if not os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        jax.config.update("jax_compilation_cache_dir", cache_root())
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 2.0)

shard_map = jax.shard_map

__all__ = ["jax", "jnp", "shard_map", "cache_root"]
