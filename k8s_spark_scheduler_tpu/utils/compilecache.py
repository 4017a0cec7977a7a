"""Where JAX's persistent compilation cache lives.

One rule for every entry point that compiles (the server, ``bench.py``,
``chip_smoke.py``): when ``JAX_COMPILATION_CACHE_DIR`` is set the
operator has placed the cache and nothing here overrides it; otherwise
the cache sits at ``<checkout>/.jax_cache``.  The directory is part of
the cache key, so the default is a fixed path — no host fingerprint,
pid or timestamp component that would make a second run miss.
"""

from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

_CHECKOUT = os.path.dirname(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
)
DEFAULT_DIR = os.path.join(_CHECKOUT, ".jax_cache")


def configure_compile_cache() -> str:
    """Enable the persistent compile cache and return the directory in
    use.  Call before the first compile."""
    import jax

    cache_dir = os.environ.get(ENV_VAR)
    if not cache_dir:
        cache_dir = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # the queue kernels compile in about a second each; JAX's default
    # thresholds would skip exactly those entries
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
