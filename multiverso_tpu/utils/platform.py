"""Process-level JAX set-up: the virtual CPU mesh the tests run on and
the persistent compile cache every entry point shares.

Both must run before the first device use — ``jax.config`` updates only
take effect while no backend has been initialized.
"""

from __future__ import annotations

import os

# <checkout>/.jax_cache, derived from this file's location: the path is
# part of what makes a second run find the first run's entries, so it is
# the same from every working directory and for every process.
_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
DEFAULT_COMPILE_CACHE_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def force_cpu_mesh(n_devices: int = 8) -> bool:
    """Point JAX at an n-device virtual CPU mesh (the test/dryrun fixture:
    SURVEY §4's "mpirun -np N on one host" analogue). Returns False (instead
    of raising) if a backend is already live — callers honoring an explicit
    user request should surface that. ``JAX_PLATFORMS=cpu`` is exported too,
    so every process this one spawns stays on the CPU as well."""
    import jax

    try:
        jax.config.update("jax_platforms", "cpu")
        jax.config.update("jax_num_cpu_devices", n_devices)
    except RuntimeError:
        return False
    os.environ["JAX_PLATFORMS"] = "cpu"
    return True


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is set here — whoever launched the process placed the cache.
    Otherwise the cache lives at ``<checkout>/.jax_cache``. Child
    processes inherit the environment, so they resolve the same directory
    either way. Call before the first jit: the cache is bound at the first
    compile."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    import jax

    jax.config.update("jax_compilation_cache_dir", DEFAULT_COMPILE_CACHE_DIR)
    return DEFAULT_COMPILE_CACHE_DIR
