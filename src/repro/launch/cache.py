"""JAX's persistent compilation cache, in one fixed place.

The fleet's interpreters and the vmloop kernel take tens of seconds to
compile per ``(VMConfig, n)``.  Entry points (``chip_smoke.py``,
``benchmarks/run.py``, the examples) call :func:`enable_compile_cache`
first, so a second process with the same shapes reuses the compiled
programs.  The cache directory is part of each entry's key, so it never
moves: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX
reads that variable itself), else ``.jax_cache`` at the checkout's root.

An entry's key covers the programs' metadata too (source locations and
``jax.named_scope`` names).  Without it an executable loaded from the
cache carries the metadata of whatever code compiled it first, so a
profile of code with the round program's phase scopes could show an
executable compiled without them, or the reverse.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent compilation cache on; return its directory."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", True)
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
