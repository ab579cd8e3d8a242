"""Persistent JAX compilation cache for the entry points.

Scripts (``chip_smoke.py``, ``examples/compress_field.py``,
``examples/serve_archive.py``, ``benchmarks/run.py``) call :func:`setup`
once before their first compile; importing :mod:`repro` sets nothing.

* ``JAX_COMPILATION_CACHE_DIR`` set: JAX already reads it, so the cache is
  left entirely to JAX and nothing else is touched.
* unset: the cache goes to ``<repo>/.jax_cache`` — a fixed path next to the
  checkout's ``src/``, never derived from a temp dir, pid or clock, because
  the directory is part of what a later run must find again.
"""
from __future__ import annotations

import os
from pathlib import Path

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def setup() -> str:
    """Point JAX's persistent compilation cache at its directory and return
    that directory."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
