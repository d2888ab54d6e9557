"""Where JAX's persistent compilation cache lives.

One rule for every entry point that builds an engine (worker, run, bench,
the profiler sweep): when `JAX_COMPILATION_CACHE_DIR` is set the
environment owns the location and nothing here touches it; otherwise the
cache sits at one fixed, git-ignored path inside the checkout.  The path is
part of the cache key, so it is never a temp name, a pid or a timestamp —
a directory that moves never hits.
"""

from __future__ import annotations

import os

DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.abspath(__file__))), ".jax_cache"
)


def configure() -> str:
    """Turn the persistent cache on and return its directory.  Call before
    the first compilation."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_DIR
        jax.config.update("jax_compilation_cache_dir", path)
    # keep every program, not only those that took over a second to
    # compile: a restarted worker should compile nothing at all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
