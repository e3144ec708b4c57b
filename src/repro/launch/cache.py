"""Where JAX keeps its persistent compilation cache.

Every entry point (``chip_smoke.py``, ``repro.launch.pic_run``,
``benchmarks.run``) calls ``enable_compilation_cache`` before it compiles
anything, so a later process with the same program finds the executables
an earlier one wrote. The directory is part of what makes a hit, so it is
fixed: ``JAX_COMPILATION_CACHE_DIR`` when the environment sets it (JAX reads
that variable itself, and it stands), otherwise ``.jax_cache`` at the root
of this checkout, which git ignores.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_CACHE = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compilation_cache() -> str:
    """Turn JAX's persistent compilation cache on; return its directory.

    With ``JAX_COMPILATION_CACHE_DIR`` unset, the checkout's ``.jax_cache``
    is exported under that name too, so child processes share the cache.
    """
    path = os.environ.get(ENV)
    if not path:
        path = str(CHECKOUT_CACHE)
        os.environ[ENV] = path
        jax.config.update("jax_compilation_cache_dir", path)
    return path
