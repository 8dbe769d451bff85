"""JAX's persistent compilation cache for the repo's entry points.

Called from ``main()`` of ``launch/train.py`` and ``launch/serve.py`` and
from ``chip_smoke.py`` — never at import, so importing a module leaves the
process's JAX configuration alone.
"""

from __future__ import annotations

import os
from pathlib import Path

import jax

#: fixed per checkout: the cache directory is part of each entry's key, so
#: a temp, pid- or time-based path would never hit
CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache and return its directory.

    ``JAX_COMPILATION_CACHE_DIR``, when set, is JAX's own setting: it is
    left to JAX and nothing is set here.  Otherwise the cache lives in
    ``<checkout>/.jax_cache``."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
