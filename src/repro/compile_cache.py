"""Where JAX's persistent compilation cache lives, decided in one place.

Entry points that compile for the chip (`chip_smoke.py`,
`benchmarks/run.py`) call `enable_compile_cache` before their first
compile, so the processes of one run share compiled programs.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

ENV = "JAX_COMPILATION_CACHE_DIR"

#: The cache directory when the environment names none. A fixed path:
#: it is part of the cache key, so a directory that moves never hits.
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; return its directory.

    Where ``$JAX_COMPILATION_CACHE_DIR`` is set, JAX already uses it and
    nothing is set here. Otherwise the cache goes to `REPO_CACHE_DIR`.
    """
    env = os.environ.get(ENV)
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
