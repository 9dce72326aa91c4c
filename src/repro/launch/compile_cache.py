"""JAX's persistent compilation cache for this repo's entry points.

Every entry point (``chip_smoke.py``, ``repro.launch.serve``, the
benchmarks) calls :func:`enable_compile_cache` at the start of its
``main``, never at import. Where ``JAX_COMPILATION_CACHE_DIR`` is set, the
cache lives in that directory and nowhere else. Otherwise it lives at
``<checkout>/.jax_cache`` (gitignored): a fixed path, so that a second run
finds what the first one compiled.
"""

from __future__ import annotations

import os

CHECKOUT = os.path.abspath(os.path.join(os.path.dirname(__file__),
                                        "..", "..", ".."))
DEFAULT_DIR = os.path.join(CHECKOUT, ".jax_cache")


def enable_compile_cache() -> str:
    """Turn the persistent cache on and return its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR") or DEFAULT_DIR
    jax.config.update("jax_compilation_cache_dir", path)
    # the session step compiles in seconds and each kernel in about one:
    # keep every compile, not only those past JAX's default 1 s floor
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    return path
