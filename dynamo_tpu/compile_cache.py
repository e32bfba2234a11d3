"""JAX persistent compilation cache: the one place its directory is chosen.

Every entry point that compiles (``dynamo_tpu.launch``, ``bench.py``,
``python -m dynamo_tpu.bench``, ``python -m dynamo_tpu.tuning``,
``chip_smoke.py``, ``tests/conftest.py``) calls :func:`enable_compile_cache`
before its first jit. The directory is part of the cache key's storage, so it
must not move between runs: no temp names, pids or timestamps.
"""

from __future__ import annotations

import os
import pathlib

CACHE_DIR_ENV = "JAX_COMPILATION_CACHE_DIR"


def enable_compile_cache() -> str:
    """Turn the persistent compile cache on; returns the directory in use.

    ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself — only make sure
    the directory exists and set no other path in code. Unset:
    ``<checkout>/.jax_cache`` next to the package (git-ignored). The
    runners' executable store (``dynamo_tpu/executable_store.py``) lies in
    ``executables/`` under either: JAX's LRU looks only at its own ``*-cache``
    files at the top level.
    """
    from dynamo_tpu import executable_store

    path = os.environ.get(CACHE_DIR_ENV)
    if path:
        os.makedirs(path, exist_ok=True)
    else:
        import jax

        path = str(pathlib.Path(__file__).resolve().parent.parent / ".jax_cache")
        os.makedirs(path, exist_ok=True)
        jax.config.update("jax_compilation_cache_dir", path)
    executable_store.set_cache_dir(path)
    return path
