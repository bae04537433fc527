"""Where JAX keeps its persistent compilation cache.

Entry points call :func:`setup_compile_cache` from ``main()``; the
library never calls it while a module is imported.  When
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it and nothing here
overrides it.  Otherwise the cache goes to one fixed, git-ignored
directory inside the checkout: a compiled program is found again only
under the same path, so the directory never carries a temp name, a pid
or a time.
"""

from __future__ import annotations

import os
import pathlib
from typing import Tuple

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def setup_compile_cache() -> Tuple[str, bool]:
    """Turn on JAX's persistent compilation cache.

    Returns ``(directory, from_env)``.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env, True
    DEFAULT_DIR.mkdir(exist_ok=True)
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR), False
