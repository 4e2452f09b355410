"""Where a process keeps JAX's persistent compilation cache, and what
it keeps there.

One rule for every process that jits on the main path (train workers,
learners, bench.py, tools/bench_rl.py, chip_smoke.py), in two halves.

Where: where JAX_COMPILATION_CACHE_DIR is set, JAX reads it itself —
workers inherit it from the node manager's environment — and no code
names another directory. Where it is unset the cache goes to
`<checkout>/.jax_cache`. The path is part of the cache's key, so it is
fixed by the package's location: a directory that moves with the process
never hits.

What: every program the process compiles. JAX asks the cache for every
program but by default writes only those that took the compiler a second
(`jax_persistent_cache_min_compile_time_secs`), so the eager ops a job
runs while it builds and checks its weights, a tenth of a second each and
hundreds of them, were compiled again in every run: 12-39% of a warm
set-up (PERF.md section 6, PR 56). Both of JAX's floors are put at 0, the
entry-size floor's value in jax 0.9.0 too, held so that an upgrade that
raises it shows. Where the environment names a floor
(JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS,
JAX_PERSISTENT_CACHE_MIN_ENTRY_SIZE_BYTES), JAX reads it itself and that
value stands, as the directory's does.
"""

from __future__ import annotations

import os
import sys

_ENV = "JAX_COMPILATION_CACHE_DIR"
_DEFAULT = os.path.join(
    os.path.dirname(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__)))), ".jax_cache")
_FLOORS = ("jax_persistent_cache_min_compile_time_secs",
           "jax_persistent_cache_min_entry_size_bytes")


def compile_cache_dir() -> str:
    """The directory enable_compile_cache() leaves JAX using. Imports no
    JAX, so a driver that must stay off the chip can print it."""
    return os.environ.get(_ENV) or _DEFAULT


def enable_compile_cache() -> str:
    """Call before the first jit of a process; returns the directory.

    Leaves JAX keeping every program it compiles, unless the environment
    sets JAX's own variable for a floor: then JAX's value stands. Imports
    JAX only to name the default directory: where the process has not
    loaded it yet, a floor goes into the environment, which JAX reads at
    its import (and which the process's children inherit)."""
    if not os.environ.get(_ENV):
        import jax
        jax.config.update("jax_compilation_cache_dir", _DEFAULT)
    loaded = sys.modules.get("jax")
    for floor in _FLOORS:
        if floor.upper() in os.environ:
            continue
        if loaded:
            loaded.config.update(floor, 0)
        else:
            os.environ[floor.upper()] = "0"
    return compile_cache_dir()
