"""JAX persistent compilation cache: one place decides where it lives.

A fresh process pays every jit compile again (r09: 4.4 obj/s cold vs
43.3 warm on recovery — the compile WAS the cold path; on the chip a
cold `chip_smoke.py` is mostly compile). The reference ships compiled
C++, so the closest analog is a stable on-disk cache: the first process
per (program, shape) compiles, every later one loads the executable.

The directory is placed from outside: where `JAX_COMPILATION_CACHE_DIR`
is set, jax already uses it and nothing here sets another. Otherwise the
cache goes to ONE fixed path inside the checkout — the path is part of
the cache key, so a directory named after a temp dir, a pid or a time
would never hit.
"""

from __future__ import annotations

import os

#: <checkout>/.jax_bench_cache (git-ignored)
DEFAULT_CACHE_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_bench_cache")


def enable_persistent_compile_cache() -> str:
    """Turn the persistent compilation cache on and return its
    directory. Thresholds drop to zero so even fast compiles are cached:
    the served path's programs are small one by one, and a daemon
    process is cold every time it starts."""
    import jax
    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = DEFAULT_CACHE_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    return path
