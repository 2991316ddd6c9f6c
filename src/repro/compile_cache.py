"""Where the repository's entry points keep JAX's persistent compile cache.

Importing ``repro`` configures no cache.  Entry points (``chip_smoke.py``,
the ``benchmarks/`` scripts) call :func:`use_checkout_cache` under their
``__main__`` guard, so a second run of the same programs from the same
checkout loads them instead of compiling again.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
#: ``<checkout>/.jax_cache``: this file is ``<checkout>/src/repro/...``.
CHECKOUT_CACHE = Path(__file__).resolve().parents[2] / ".jax_cache"


def use_checkout_cache() -> str:
    """Keep compiled programs under ``<checkout>/.jax_cache``; return the dir.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and
    nothing is changed here.  Otherwise the directory is a fixed path in
    the checkout (never a temporary, per-process or per-run one): the
    cache only hits when later runs look in the same place.
    """
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CHECKOUT_CACHE))
    return str(CHECKOUT_CACHE)
