"""Persistent XLA compile cache: the one place that decides where it lives.

A cold process compiles everything (the ERNIE-large step alone took 227 s
on a v5e chip, PR 21); JAX's persistent cache turns the second process's
compile into a read. The cache directory is part of the contract, not of
the cache key's luck: a directory that moves never hits.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX reads it itself; nothing is set
  in code (the chip tool's machines come with it set, and keep what is
  cached there for the repository's next call).
* unset: one fixed path inside the checkout, ``<repo>/.jax_cache``
  (git-ignored) — never a temp dir, pid or time derived one.
"""

from __future__ import annotations

import os

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
REPO_CACHE_DIR = os.path.join(_REPO_ROOT, ".jax_cache")


def enable_compile_cache() -> str:
    """Point JAX's persistent compile cache at its directory (see the
    module docstring) and return that directory. Called once, from the
    package import."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    import jax

    jax.config.update("jax_compilation_cache_dir", REPO_CACHE_DIR)
    return REPO_CACHE_DIR
