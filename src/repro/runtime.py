"""What the program asks of the JAX runtime, decided in one place.

* :func:`offload_x64` -- the 64-bit scope the offload compilers trace and
  call under, so int64/float64 accumulators keep the verifier's semantics
  while the model stack keeps JAX's 32-bit defaults;
* :func:`pallas_interpret` -- whether Pallas kernels run in the interpreter:
  compiled on a TPU backend, interpreted everywhere else;
* :func:`place_compile_cache` -- where the persistent compile cache lives.
  Entry-point scripts call it before their first compile; importing
  ``repro`` never does.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

__all__ = ["offload_x64", "pallas_interpret", "place_compile_cache",
           "COMPILE_CACHE_DIR"]

# <repo>/.jax_cache: a fixed path, because the path is part of the cache key
COMPILE_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def offload_x64():
    """Context manager: trace and run offload executables in 64-bit mode."""
    return jax.enable_x64(True)


def pallas_interpret() -> bool:
    """Interpret Pallas kernels unless the default backend is a TPU."""
    return jax.default_backend() != "tpu"


def place_compile_cache() -> str:
    """Point JAX's persistent compile cache at a fixed directory and return
    it. ``JAX_COMPILATION_CACHE_DIR``, when set, is read by JAX itself and
    wins; otherwise the cache goes to :data:`COMPILE_CACHE_DIR`."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(COMPILE_CACHE_DIR))
    return str(COMPILE_CACHE_DIR)
