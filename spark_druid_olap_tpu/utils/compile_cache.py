"""Where JAX's persistent compilation cache lives — decided in ONE place.

A cold TPC-H statement compiles for seconds to a minute on a TPU, and a
process started by the chip tool starts with no compiled code, so every
entry point that builds a ``Context`` shares one cache directory:

- ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself and this module
  sets nothing in code (the directory is placed from outside, e.g. where
  a tool keeps it between calls).
- otherwise: ``<checkout>/.jax_cache`` (git-ignored) — a FIXED path,
  because the path is part of the cache key and a directory that moves
  never hits.

Nothing else in the repository sets ``jax_compilation_cache_dir``.
"""

from __future__ import annotations

import os

import jax

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
CHECKOUT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure() -> str:
    """Point JAX at the cache directory (idempotent); returns it."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    if jax.config.jax_compilation_cache_dir != CHECKOUT_DIR:
        jax.config.update("jax_compilation_cache_dir", CHECKOUT_DIR)
    return CHECKOUT_DIR


def entries(path: str) -> int:
    """Number of cached executables under ``path`` (0 when absent)."""
    try:
        return sum(1 for n in os.listdir(path) if n.endswith("-cache"))
    except FileNotFoundError:
        return 0
