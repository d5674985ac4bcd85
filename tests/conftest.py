"""Test configuration.

Tests run on CPU with 8 virtual XLA devices so multi-chip sharding
(shard_map over a Mesh, all_to_all / all_gather collectives) is exercised
without TPU hardware. Both variables must be set before jax is first
imported.
"""

import os
import tempfile

flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8"
    ).strip()
os.environ["JAX_PLATFORMS"] = "cpu"
# one compile-cache root per session (shadow_tpu._jax.cache_root): the
# XLA cache and the default-on AOT cache (its aot/ subdirectory) are
# shared by the session's tests, so identical engine configs load
# instead of recompiling, but never touch the checkout's own cache
os.environ["JAX_COMPILATION_CACHE_DIR"] = tempfile.mkdtemp(
    prefix="shadow_tpu_cache_test_")

import jax  # noqa: E402

jax.config.update("jax_enable_x64", True)
