"""Persistent JAX compilation cache for the entry points.

Every command-line entry point (``repro.launch.*``, ``benchmarks/*``,
``chip_smoke.py``) calls :func:`enable_compile_cache` before it compiles
anything; library code and tests never do. Where ``JAX_COMPILATION_CACHE_DIR``
is set, JAX reads it itself and nothing is set here. Otherwise the cache
lives at :data:`CACHE_DIR`, a fixed path inside the checkout: the path is
part of what a later run must match to hit, so it is never a temporary,
per-process or time-stamped directory.
"""
from __future__ import annotations

import os
import pathlib

import jax

CACHE_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def enable_compile_cache() -> None:
    if os.environ.get("JAX_COMPILATION_CACHE_DIR"):
        return
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
