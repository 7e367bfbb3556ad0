"""Shared backend dispatch for kernels.

"pallas"    — compile for TPU (requires a TPU backend at runtime)
"interpret" — run the same kernel body in the Pallas interpreter (CPU OK);
              used by tests as the kernel-execution oracle check
"jnp"       — pure-jnp implementation with identical semantics; this is the
              path the pjit/dry-run model code uses (TPU Pallas calls cannot
              lower for the CPU mesh of this container)
"auto"      — "pallas" on TPU, "jnp" elsewhere
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

VALID_BACKENDS = ("auto", "pallas", "interpret", "jnp")


def resolve_backend(backend: str) -> str:
    if backend not in VALID_BACKENDS:
        raise ValueError(f"backend must be one of {VALID_BACKENDS}, got {backend!r}")
    if backend != "auto":
        return backend
    return "pallas" if jax.default_backend() == "tpu" else "jnp"


def enable_compile_cache() -> str:
    """Turn on JAX's persistent compilation cache for an entry point.

    Where ``JAX_COMPILATION_CACHE_DIR`` is set, JAX already reads it and
    nothing is set here. Otherwise the cache goes to ``.jax_cache`` at the
    root of this checkout: a fixed path, since the path is part of the
    cache key. Returns the directory in use."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    path = str(Path(__file__).resolve().parents[3] / ".jax_cache")
    jax.config.update("jax_compilation_cache_dir", path)
    return path
