"""Backend bootstrap helpers shared by every benchmark/driver entry point:
the virtual CPU mesh that tests and dry runs use in place of a multi-chip
host, and the persistent compile cache. One implementation here instead
of a copy per script."""

from __future__ import annotations

import os
import sys

# <repo>/.jax_cache — fixed and inside the checkout, because the cache
# directory is part of the cache key: a path that moves never hits
_REPO_CACHE = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def pin_cpu_backend() -> None:
    """Pin the current process to the CPU platform. Must run before any
    JAX backend initializes (importing jax is fine; touching devices is
    not)."""
    os.environ["JAX_PLATFORMS"] = "cpu"
    import jax

    jax.config.update("jax_platforms", "cpu")


def virtual_mesh_env(n_devices: int, base: dict = None) -> dict:
    """Environment for a child process with an n-device virtual CPU mesh."""
    env = dict(base if base is not None else os.environ)
    env["JAX_PLATFORMS"] = "cpu"
    flags = env.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        env["XLA_FLAGS"] = (
            flags + f" --xla_force_host_platform_device_count={n_devices}"
        ).strip()
    return env


def enable_compile_cache() -> str:
    """Make JAX's persistent compilation cache available and return its
    directory. Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already
    reads it and nothing is set in code; otherwise the cache lives at the
    fixed, git-ignored ``<repo>/.jax_cache``."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    import jax

    jax.config.update("jax_compilation_cache_dir", _REPO_CACHE)
    return _REPO_CACHE


def reexec_virtual_mesh(n_devices: int, marker: str) -> None:
    """Replace this process with a copy running on an n-device virtual CPU
    mesh; ``marker`` is the env flag that breaks the recursion (the child
    sees it set and proceeds, calling pin_cpu_backend())."""
    env = virtual_mesh_env(n_devices)
    env[marker] = "1"
    os.execve(sys.executable, [sys.executable] + sys.argv, env)
