"""Where a process keeps JAX's persistent compilation cache.

One rule for every process that jits on the main path (train workers,
learners, bench.py, tools/bench_rl.py, chip_smoke.py): where
JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself — workers inherit
it from the node manager's environment — and no code names another
directory. Where it is unset the cache goes to `<checkout>/.jax_cache`.
The path is part of the cache's key, so it is fixed by the package's
location: a directory that moves with the process never hits.
"""

from __future__ import annotations

import os

_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")


def compile_cache_dir() -> str:
    """The directory enable_compile_cache() leaves JAX using. Imports no
    JAX, so a driver that must stay off the chip can print it."""
    return os.environ.get(_ENV) or _DEFAULT


def enable_compile_cache() -> str:
    """Call before the first jit of a process; returns the directory."""
    if not os.environ.get(_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    return compile_cache_dir()
