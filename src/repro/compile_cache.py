"""JAX's persistent compilation cache: where it lives, and turning it on.

Entry points (``chip_smoke.py``, ``examples/train_mvc_agent.py``,
``repro.launch.solve_serve``) call :func:`setup_compile_cache` once before
their first compile.  A directory set in ``JAX_COMPILATION_CACHE_DIR`` is
read by JAX itself and wins; otherwise the cache lives at the fixed path
``<repo>/.jax_cache`` — fixed because the path is part of what a later
process must find again.
"""
from __future__ import annotations

import os
import pathlib

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache(cache_dir) -> None:
    """Persist every compiled executable under ``cache_dir`` — a restarted
    server's ``warmup()`` then deserializes instead of recompiling."""
    jax.config.update("jax_compilation_cache_dir", str(cache_dir))
    _persist_every_entry()


def _persist_every_entry() -> None:
    # the default thresholds skip small/fast-compiling executables; the
    # serving warmup wants EVERY bucket executable persisted
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0)
    jax.config.update("jax_persistent_cache_min_entry_size_bytes", -1)


def compile_cache_dir() -> str:
    """The cache directory in force: ``JAX_COMPILATION_CACHE_DIR`` when
    set, else ``<repo>/.jax_cache``."""
    return os.environ.get(ENV_VAR) or str(DEFAULT_DIR)


def setup_compile_cache() -> str:
    """Turn the persistent cache on and return its directory.  Sets no
    directory in code when ``JAX_COMPILATION_CACHE_DIR`` is set."""
    if os.environ.get(ENV_VAR):
        _persist_every_entry()
    else:
        enable_compile_cache(DEFAULT_DIR)
    return compile_cache_dir()
