"""Persistent XLA compilation cache for the entry points.

A call on a fresh machine compiles every program from scratch; the
cache lets the processes of one run, and later runs on the same disk,
share compiled executables. JAX keys entries by the cache path too, so
the path is fixed: ``$JAX_COMPILATION_CACHE_DIR`` when set (JAX reads
the variable itself), else ``.jax_cache`` at the root of the checkout.
"""
from __future__ import annotations

import os
import pathlib

import jax

REPO_CACHE_DIR = pathlib.Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn the persistent cache on before the first compile; returns
    the directory in use."""
    env_dir = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env_dir:
        return env_dir
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
