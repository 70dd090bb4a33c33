"""Where JAX keeps its persistent compilation cache.

A process that compiles the programs an earlier one compiled reads them back
from the cache instead of compiling again; at deployment widths that saves
about a minute of start-up on a TPU (the IVF build alone compiles for ~30 s).

`JAX_COMPILATION_CACHE_DIR`, when set, is JAX's own setting and is left as
it is.  Otherwise the cache goes to `<repo>/.jax_cache`, a fixed path: a
cache directory that moves between runs never hits.  Entry points call
`enable()`; tests do not.
"""
from __future__ import annotations

import os
from pathlib import Path

import jax

REPO_CACHE_DIR = Path(__file__).resolve().parents[2] / ".jax_cache"


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get("JAX_COMPILATION_CACHE_DIR")
    if env:
        return env
    jax.config.update("jax_compilation_cache_dir", str(REPO_CACHE_DIR))
    return str(REPO_CACHE_DIR)
