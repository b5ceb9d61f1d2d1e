"""Test harness: force a virtual 8-device CPU mesh so multi-chip sharding
logic is exercised without TPU hardware (SURVEY.md §4 lesson — single-host
stand-ins for the cluster). Pallas kernels run in interpret mode here.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402

jax.config.update("jax_platforms", "cpu")

assert jax.default_backend() == "cpu"
assert jax.device_count() == 8, jax.devices()

# Persistent XLA compile cache (the same helper bench.py uses): on a
# small CPU host the tier-1 wall clock is dominated by jit compiles of
# the distributed steps, and repeat runs — the common case for the
# verify loop — skip them entirely. Harmless when cold.
from scenery_insitu_tpu.utils.backend import enable_compile_cache  # noqa: E402

enable_compile_cache()
jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.3)


def pytest_configure(config):
    config.addinivalue_line(
        "markers", "slow: long-running tests excluded from tier-1")
    config.addinivalue_line(
        "markers", "multiproc: spawns real jax.distributed subprocesses "
        "(the multiproc CI lane selects these with -m multiproc)")
