"""Where JAX's persistent compilation cache lives, for the entry points.

The cache key includes its directory, so a cache is only found again at a
fixed path.  :func:`configure` keeps it in one place:

  * ``JAX_COMPILATION_CACHE_DIR`` set — JAX reads the variable itself;
    nothing is set in code and that directory is the only one written.
  * unset — ``<repo>/.jax_cache`` inside the checkout (git-ignored).

``chip_smoke.py`` and ``benchmarks/run.py`` call it before their first
compile; library code and tests never do.
"""
from __future__ import annotations

import os
import pathlib

import jax

DEFAULT_DIR = pathlib.Path(__file__).resolve().parents[2] / ".jax_cache"


def configure() -> str:
    """Enable the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(DEFAULT_DIR))
    return str(DEFAULT_DIR)
