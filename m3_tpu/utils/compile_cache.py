"""Where XLA's persistent compilation cache lives, decided in ONE place.

The directory is placed from outside: when JAX_COMPILATION_CACHE_DIR is
set, JAX reads it itself and this module sets no directory in code.
Otherwise the cache goes to `<checkout>/.jax_cache` — a fixed path,
because the path is part of the cache key's lookup: a directory named
after a pid, a time or a temp name never hits.

One threshold for every caller: 0 s. The codec and plan warm-ups are
many programs that each compile in well under half a second; with JAX's
1 s default none of them would be written and a second start would pay
all of them again.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def configure() -> str:
    """Point this process's persistent compile cache; returns the
    directory in effect. Call before the first compile."""
    import jax

    cache_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not cache_dir:
        cache_dir = os.path.join(_CHECKOUT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", cache_dir)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return cache_dir
