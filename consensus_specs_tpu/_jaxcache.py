"""JAX process setup shared by the entry points and kernel modules: the
persistent XLA compilation cache, and the host backend beside a chip.

The heavy kernels (batched pairing, epoch deltas) cost minutes of XLA
compile per shape; the persistent cache makes that once-per-machine.
Called only from modules that already import jax — pure-SSZ import paths
never pay the jax import cost.
"""
from __future__ import annotations

import os

_configured = False


def configure() -> None:
    global _configured
    if _configured:
        return
    _configured = True
    if "JAX_COMPILATION_CACHE_DIR" in os.environ:
        return
    import jax

    if jax.config.jax_compilation_cache_dir is not None:
        return
    cache_dir = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), ".cache", "jax")
    try:
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
        jax.config.update("jax_persistent_cache_min_compile_time_secs", 2)
    except OSError:  # read-only checkout: in-memory cache only
        pass


def keep_host_backend() -> None:
    """Keep JAX's CPU backend beside an accelerator: the host-placed
    kernels (epoch deltas, KZG MSM) run there, and their device choice
    raises when it is missing.  A ``JAX_PLATFORMS`` that names only
    accelerators (a chip machine may set ``tpu``) gets ``cpu`` appended;
    the first platform stays the default.  Call before JAX initializes
    its backends."""
    platforms = os.environ.get("JAX_PLATFORMS")
    if platforms and "cpu" not in platforms.split(","):
        os.environ["JAX_PLATFORMS"] = platforms + ",cpu"
