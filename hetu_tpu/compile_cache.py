"""Where compiled programs are kept between processes.

One helper for every entry point that compiles on the chip
(``chip_smoke.py``, ``bench.py``, the examples): JAX's persistent
compilation cache, placed from outside when the environment says where
and otherwise at one fixed place inside the checkout.  The directory is
part of the cache key, so it must not move between runs: never ``/tmp``,
a pid or a timestamp.
"""

from __future__ import annotations

import os

_CHECKOUT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_HIT = "/jax/compilation_cache/cache_hits"
_MISS = "/jax/compilation_cache/cache_misses"


def enable_compile_cache():
    """Turn the persistent compilation cache on and return its directory.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX already uses that directory and
    nothing is set in code.  Unset: ``<checkout>/.jax_cache`` (git-ignored).
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax
    path = os.path.join(_CHECKOUT, ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path


def count_cache_events():
    """Start counting this process's persistent-cache hits and misses;
    returns the live ``{"hits": n, "misses": n}`` dict."""
    import jax
    counts = {"hits": 0, "misses": 0}

    def listen(event, **_):
        if event == _HIT:
            counts["hits"] += 1
        elif event == _MISS:
            counts["misses"] += 1

    jax.monitoring.register_event_listener(listen)
    return counts
