"""Persistent XLA compile-cache setup shared by every entrypoint.

Serving programs are large and a cold TPU start compiles for minutes; the
server (``__main__.py``), the benchmark (``bench.py``), ``chip_smoke.py``
and the probes under ``tools/`` all call the one function here, so the
policy — where the cache lives, what gets cached — cannot drift between
entrypoints.

The directory is placed from OUTSIDE the program: when
``JAX_COMPILATION_CACHE_DIR`` is set jax honours it natively and this
module sets no directory in code. Otherwise the cache lives at
``<checkout>/.jax_cache`` — a fixed path (it is part of the cache key's
lookup; a directory that moves never hits), never ``~``, a temp name, a
pid or a time.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__)
)))


def setup_compile_cache() -> str:
    """Enable jax's persistent compilation cache; returns its directory."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        os.makedirs(cache_dir, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    # serving programs are large; cache everything nontrivial
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 1.0)
    return cache_dir
