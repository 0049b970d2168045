"""Where JAX's persistent compilation cache lives.

A cold process compiles every kernel and the detector backbone again; the
persistent cache lets the next process on the same kind of device reuse
them. The cache key includes the directory, so the directory is fixed.
"""

from __future__ import annotations

import os
import pathlib

import jax

#: the checkout this package was imported from (src/repro/launch/ -> root)
_CHECKOUT = pathlib.Path(__file__).resolve().parents[3]


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; returns its directory.

    Call before the first compile, never at import. When
    ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already keeps the cache
    there and nothing is changed; otherwise it goes to
    ``<checkout>/.jax_cache``.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(_CHECKOUT / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
