"""Persistent XLA compilation cache.

The reference pays graph (re)construction + session setup on every
process start (mnist_python_m.py:177-275) with nothing cached. Here
every jitted step is an XLA compile — over a minute cold for the
GPT-2-small train step on a v5e — so the framework enables JAX's
persistent compile cache by default: repeat runs (tests, bench, CLI
restarts, resume-after-crash) hit the disk cache instead of
recompiling.

The directory is placed from OUTSIDE the program: where
``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it, and no code
here sets another; where it is not, the cache is
``<checkout>/.cache/xla`` — a fixed path, because the path is part of
the cache key and a directory that moves never hits.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_DEFAULT_DIR = os.path.join(_REPO_ROOT, ".cache", "xla")


def enable_persistent_cache() -> str:
    """Idempotently turn on the JAX persistent compilation cache and
    return the directory in use (see the module docstring for the
    one rule that places it)."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = _DEFAULT_DIR
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.2)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", 0)
    return path
