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


def _asks_for_result_layout(module) -> bool:
    """Whether a lowered MLIR module names a layout for any result of its
    ``main`` (``mhlo.layout_mode`` other than ``default``)."""
    from jax._src.lib.mlir import ir

    for op in module.body.operations:
        if (op.operation.name != "func.func"
                or ir.StringAttr(op.attributes["sym_name"]).value != "main"
                or "res_attrs" not in op.attributes):
            continue
        for attrs in ir.ArrayAttr(op.attributes["res_attrs"]):
            attrs = ir.DictAttr(attrs)
            if ("mhlo.layout_mode" in attrs and ir.StringAttr(
                    attrs["mhlo.layout_mode"]).value != "default"):
                return True
    return False


def compile_result_layouts_in_process() -> bool:
    """Keep programs that return arrays in a chosen layout away from the
    persistent compile cache: compiled in this process, never read from
    the cache nor written to it. Returns whether that holds from now on.

    Why (jax/jaxlib 0.9.0, libtpu 0.0.34, seen on a v5e): an executable
    read back from the cache no longer knows its result layouts
    (``PjRtExecutable::GetOutputLayouts`` cannot retrieve the HLO module
    of a deserialized TPU executable), so the arrays it returns describe
    themselves in the device's default layout whatever layout they lie
    in. The next program is then compiled for the described layout and
    fails at run time on the buffer's real size (``INVALID_ARGUMENT:
    expected parameter 0 of size ...``). A freshly compiled executable
    has no such fault, and a program that only *takes* laid-out arrays is
    cached as ever: its results are in the default layout, as described.

    The guard wraps ``jax._src.compiler.compile_or_get_cached``, which is
    private to JAX: where it is not there to wrap, this returns False and
    the caller keeps to default layouts (``table.row_program_layout``).
    Idempotent; called when the first table picks a layout of its own."""
    try:
        from jax._src import compiler
        inner = compiler.compile_or_get_cached
        compile_here = compiler.backend_compile_and_load
    except (ImportError, AttributeError):
        return False
    if getattr(inner, "result_layouts_in_process", False):
        return True

    def compile_or_get_cached(backend, computation, devices, compile_options,
                              host_callbacks, executable_devices, *rest,
                              **kw):
        if _asks_for_result_layout(computation):
            return compile_here(backend, computation, executable_devices,
                                compile_options, host_callbacks)
        return inner(backend, computation, devices, compile_options,
                     host_callbacks, executable_devices, *rest, **kw)

    compile_or_get_cached.result_layouts_in_process = True
    compiler.compile_or_get_cached = compile_or_get_cached
    return True
