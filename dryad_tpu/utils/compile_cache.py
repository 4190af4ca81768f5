"""Where JAX's persistent compile cache lives — decided in ONE place.

The library itself never configures a cache; the processes that START
it do (``chip_smoke.py``, ``benchmarks/run.py``, both conftests), all
through :func:`enable_compile_cache`.

``JAX_COMPILATION_CACHE_DIR`` wins: jax reads it into
``jax_compilation_cache_dir`` on import, and a ``jax.config.update``
here would override it — so when it is set, no directory is set in
code and the cache can be placed from outside.  Otherwise the cache
goes to ``<checkout>/.jax_cache`` (git-ignored), derived from this
package's own location: the directory is part of every cache key's
lookup path, so a temp name, pid or timestamp would never hit twice.
"""

from __future__ import annotations

import os
from typing import Tuple

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"


def default_cache_dir() -> str:
    """``<checkout>/.jax_cache`` — three levels above this file."""
    here = os.path.abspath(__file__)
    checkout = os.path.dirname(os.path.dirname(os.path.dirname(here)))
    return os.path.join(checkout, ".jax_cache")


def enable_compile_cache() -> Tuple[str, str]:
    """Turn the persistent compile cache on for this process; returns
    ``(directory, origin)`` with origin ``"JAX_COMPILATION_CACHE_DIR"``
    or ``"default"``.  Every program is cached (no size / compile-time
    floor): the pow2 shape palette makes fresh contexts re-lower the
    same programs, and a cold process should pay none of them twice."""
    import jax

    directory = os.environ.get(ENV_VAR)
    if directory:
        origin = ENV_VAR
    else:
        directory, origin = default_cache_dir(), "default"
        jax.config.update("jax_compilation_cache_dir", directory)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)
    return directory, origin
