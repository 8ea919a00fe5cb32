"""JAX persistent compilation cache for the entry points.

`enable()` is called once by `chip_smoke.py`, `repro.launch.serve` and
`examples/serve_e2e.py`, before their first compile.  Where the
environment sets `JAX_COMPILATION_CACHE_DIR`, JAX reads it by itself and
nothing is set here.  Otherwise the cache lives at `<repo>/.jax_cache`, a
fixed, git-ignored path: the directory is part of what makes a later run
find an entry, so it never depends on a temporary name, a pid or the
time.  Tests do not call it.
"""
from __future__ import annotations

import os

ENV_VAR = "JAX_COMPILATION_CACHE_DIR"
DEFAULT_DIR = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))))), ".jax_cache")


def enable() -> str:
    """Turn the persistent cache on; returns the directory it uses."""
    env = os.environ.get(ENV_VAR)
    if env:
        return env
    import jax
    jax.config.update("jax_compilation_cache_dir", DEFAULT_DIR)
    return DEFAULT_DIR
