"""Where JAX keeps its persistent compilation cache, for the entry points.

Only command-line entry points call :func:`enable_compile_cache`
(``chip_smoke.py``, ``repro.launch.serve_vision``, ``benchmarks/run.py``);
importing the library never does. Where the cache lives is the deployer's
decision: if ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
this module sets nothing. Otherwise the cache goes to one fixed directory
inside the checkout. A cache entry is found again only at the path it was
written to, so the directory never derives from a temp name, a pid or the
time.
"""

from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"

# <checkout>/.jax_cache (this file is <checkout>/src/repro/launch/...);
# listed in .gitignore
DEFAULT_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache and return its directory.

    Call before the first compile. With ``JAX_COMPILATION_CACHE_DIR`` set
    this only reports that directory; without it, it points JAX at
    :data:`DEFAULT_DIR`.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
