"""JAX's persistent compilation cache, at a place that can be set from
outside.

Entry points that run on the chip call :func:`enable` once, before their
first compile (``chip_smoke.py``, the benchmark's programs under
``chipbench/``, a training script under the launcher).  ``import paddle_tpu``
does not: the tests stay cache-free.

The directory is part of every cache key's lookup, so it must not move
between runs.  Where ``JAX_COMPILATION_CACHE_DIR`` is set, jax itself
reads it and this module sets no directory in code.  Otherwise the cache
goes to one fixed path inside the checkout, ``<repo>/.jax_cache`` —
never a temporary name, a pid or a timestamp.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))


def enable():
    """Turn the persistent compile cache on; returns its directory."""
    import jax

    path = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if not path:
        path = os.path.join(_REPO_ROOT, ".jax_cache")
        jax.config.update("jax_compilation_cache_dir", path)
    # jax skips programs that compiled in under a second by default; a
    # cold start on the chip is mostly such programs, so keep them all
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return path


def entry_count(path):
    """Compiled programs cached under ``path`` (0 when it does not exist
    yet).  jax keeps an ``-atime`` file beside an entry when eviction is
    on; those are not entries."""
    if not os.path.isdir(path):
        return 0
    return sum(1 for name in os.listdir(path)
               if not name.endswith("-atime"))
