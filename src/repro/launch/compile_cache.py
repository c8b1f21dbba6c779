"""Where JAX keeps its persistent compilation cache for this checkout.

Entry points (``chip_smoke.py``, ``repro.launch.train``,
``repro.launch.serve``) call :func:`enable_compile_cache` once before their
first compile; importing ``repro`` sets no cache. A fresh ``jax.jit`` of the
same step (a resumed ``Trainer``, a second worker's decode) then loads the
compiled program instead of compiling it again.
"""
from __future__ import annotations

import os
from pathlib import Path
from typing import Optional

import jax

__all__ = ["CACHE_DIR", "enable_compile_cache"]

#: the fixed in-checkout location (listed in .gitignore); the path is part
#: of the cache key, so it never depends on a temp dir, a pid or the time
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> Optional[str]:
    """Place the persistent compilation cache; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` set, JAX already reads it and no path
    is set here. Otherwise the cache goes to :data:`CACHE_DIR`.
    """
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    CACHE_DIR.mkdir(parents=True, exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
