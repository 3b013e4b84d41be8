"""Where entry points keep JAX's persistent compilation cache.

Each entry point (``chip_smoke.py``, ``python -m repro.launch.flow_serve``,
``python -m benchmarks.run``, ``python -m benchmarks.serve_bench``) calls
:func:`enable_compile_cache` once at start-up; importing a module never
does.  A process that compiles the full-width fused step pays for it once
per checkout instead of once per run.
"""

from __future__ import annotations

import os

ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fixed fallback: ``.jax_cache/`` at the checkout root (git-ignored).
#: The path is part of the cache key, so it never depends on a temporary
#: name, a process id or the time.
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))),
    ".jax_cache",
)


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory:
    ``$JAX_COMPILATION_CACHE_DIR`` when set, else :data:`DEFAULT_DIR`.
    Every compiled program is cached, however quick its compile."""
    import jax

    path = os.environ.get(ENV) or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
