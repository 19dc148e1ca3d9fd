"""JAX's persistent compilation cache, placed from outside or at a fixed
path.

Each distinct chunk-step program of the sweep engine costs minutes to
compile for a TPU (the float64 Eq. 1-11 body), so the entry points — the
sweep server, the lease worker, the benchmark harness and
``chip_smoke.py`` — call :func:`enable_compile_cache` before their first
compile.  Importing the library never turns the cache on.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: Where the cache lives when ``JAX_COMPILATION_CACHE_DIR`` is unset: a
#: fixed directory of the checkout (ignored by git).  It must not move
#: between runs — a cache at a temp-, pid- or time-derived path never
#: hits.
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX reads the variable
    itself and nothing is set here; otherwise the cache goes to
    :data:`DEFAULT_DIR`."""
    placed = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if placed:
        return placed
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
