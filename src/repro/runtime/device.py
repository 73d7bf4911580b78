"""The platform the program runs on, decided in one place.

Two decisions follow from the backend JAX found, and nothing else:

* whether Pallas kernels run in the interpreter: yes on the CPU (the
  test suite), no on the TPU, where Mosaic compiles them; any other
  backend is an error, since the kernels are written for the TPU;
* where compiled programs are kept across processes: JAX's persistent
  compilation cache, placed by ``JAX_COMPILATION_CACHE_DIR`` when that
  is set, and at ``<checkout>/.jax_cache`` otherwise.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

#: JAX reads this variable itself when it starts
CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: the fallback cache directory: fixed, because the path is part of what
#: makes a later process find the entries again
DEFAULT_CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def pallas_interpret() -> bool:
    """True when Pallas kernels must run in the interpreter (CPU backend)."""
    platform = jax.default_backend()
    if platform == "cpu":
        return True
    if platform == "tpu":
        return False
    raise RuntimeError(
        f"the Pallas kernels target the TPU (or the CPU interpreter); "
        f"JAX's backend is {platform!r}"
    )


def enable_compile_cache() -> Path:
    """Turn on the persistent compilation cache and return its directory.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set JAX already uses it and
    nothing is changed; otherwise the cache goes to the fixed checkout
    directory.  Idempotent; called when a ``Client`` is built, never at
    import.
    """
    if os.environ.get(CACHE_ENV):
        return Path(os.environ[CACHE_ENV])
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_CACHE_DIR))
    return DEFAULT_CACHE_DIR
