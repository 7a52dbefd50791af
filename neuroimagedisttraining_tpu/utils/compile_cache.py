"""Where the persistent XLA compilation cache lives.

The flagship round program compiles for the better part of a minute on a
TPU, and every process that runs it (CLI run, benchmark cell, smoke) would pay
that again. JAX's persistent cache removes the repeat — but only when each
process looks in the same place, because the directory is where entries are
found. One rule, applied by every entry point (``experiments.runner.main``,
``benchmarks/run.py``, ``__graft_entry__.py``, ``chip_smoke.py``) before its
first
compile:

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it; nothing here
  touches the config.
* a directory the embedding process configured itself
  (``tests/conftest.py``): left alone.
* otherwise ``<checkout>/.jax_cache`` — fixed, git-ignored, no pid, no
  temp directory, no timestamp, so the next process finds what this one
  wrote.
"""
from __future__ import annotations

import os

CACHE_ENV = "JAX_COMPILATION_CACHE_DIR"

#: ``<checkout>/.jax_cache``: the directory holding the package directory
_DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def configure_compile_cache() -> str:
    """Apply the rule above; return the directory the process will use."""
    from_env = os.environ.get(CACHE_ENV)
    if from_env:
        return from_env
    import jax

    configured = jax.config.jax_compilation_cache_dir
    if configured:
        return configured
    jax.config.update("jax_compilation_cache_dir", _DEFAULT_DIR)
    return _DEFAULT_DIR
