"""Where the entry points keep JAX's persistent compilation cache.

A process that compiles the fused round graphs at paper widths pays
seconds per graph; the persistent cache lets the next process on the
same machine skip that.  The cache directory is part of every entry's
key, so it must not move between runs: it is one fixed path in the
checkout, ``<repo>/.jax_cache`` (listed in ``.gitignore``).  Where
``JAX_COMPILATION_CACHE_DIR`` is set, JAX reads it itself and nothing
here overrides it.

Entry points (``chip_smoke.py``, the ``simulate`` / ``federate_serve``
/ ``train`` CLIs and the load driver's server process) call
:func:`enable_compile_cache` once before their first compile; library
code never does.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

CACHE_DIR = Path(__file__).resolve().parents[3] / ".jax_cache"


def enable_compile_cache() -> str:
    """Turn on the persistent compilation cache; returns its directory."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(CACHE_DIR))
    return str(CACHE_DIR)
