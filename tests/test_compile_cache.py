"""What `_private/compile_cache.enable_compile_cache()` leaves JAX's
persistent compilation cache keeping: every program, in both placements
of the directory, unless the environment sets JAX's own floor. Where the
directory goes is `test_chip_smoke.py`'s."""

import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
TIME_FLOOR = "jax_persistent_cache_min_compile_time_secs"
SIZE_FLOOR = "jax_persistent_cache_min_entry_size_bytes"
KEYS = ("jax_compilation_cache_dir", TIME_FLOOR, SIZE_FLOOR)


@pytest.fixture
def jax_config(monkeypatch):
    """This process's jax config with both floors off zero and out of the
    environment; what it found is put back."""
    import jax
    before = {k: getattr(jax.config, k) for k in KEYS}
    for k in (TIME_FLOOR, SIZE_FLOOR):
        monkeypatch.delenv(k.upper(), raising=False)
    try:
        jax.config.update(TIME_FLOOR, 1.0)   # jax's default
        jax.config.update(SIZE_FLOOR, 4096)  # what an upgrade might bring
        yield jax.config
    finally:
        for k, v in before.items():
            jax.config.update(k, v)


@pytest.mark.parametrize("placement", ["variable_set", "variable_unset"])
def test_both_floors_read_zero_after_enable(placement, jax_config,
                                            monkeypatch, tmp_path):
    from ray_tpu._private.compile_cache import enable_compile_cache
    if placement == "variable_set":
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    enable_compile_cache()
    assert getattr(jax_config, TIME_FLOOR) == 0
    assert getattr(jax_config, SIZE_FLOOR) == 0
    # jax is loaded here, so its config was updated and nothing was left
    # in the environment for a later test to inherit
    assert TIME_FLOOR.upper() not in os.environ
    assert SIZE_FLOOR.upper() not in os.environ


def test_a_floor_from_the_environment_stands(jax_config, monkeypatch,
                                             tmp_path):
    from ray_tpu._private.compile_cache import enable_compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    monkeypatch.setenv(TIME_FLOOR.upper(), "0.5")
    jax_config.update(TIME_FLOOR, 0.5)   # what jax read at its import
    enable_compile_cache()
    assert getattr(jax_config, TIME_FLOOR) == 0.5
    assert os.environ[TIME_FLOOR.upper()] == "0.5"
    assert getattr(jax_config, SIZE_FLOOR) == 0   # each floor on its own


_SCRIPT = """
import os, sys
if sys.argv[1] == "jax_loaded_first":   # a reused pool worker
    import jax
from ray_tpu._private.compile_cache import enable_compile_cache
assert enable_compile_cache() == os.environ["JAX_COMPILATION_CACHE_DIR"]
print("IMPORTED", "jax" in sys.modules)
import jax, jax.numpy as jnp
from ray_tpu._private import spans
from ray_tpu.util import jax_sentinel
assert jax_sentinel.install()
def kept_though_small(v):
    for i in range(24):
        v = jnp.sin(v) * 2.0 + jnp.cos(v + i)
    return v
jax.jit(kept_though_small)(jnp.ones((4,))).block_until_ready()
for r in spans.snapshot()["spans"]:
    a = r[6] or {}
    if r[1] == "jax.compile":
        print("OUTCOME", a.get("fun"), a["cache"], r[3])
print("FLOORS", jax.config.jax_persistent_cache_min_compile_time_secs,
      jax.config.jax_persistent_cache_min_entry_size_bytes)
"""


def _run(mode, directory, **floors):
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               JAX_COMPILATION_CACHE_DIR=str(directory))
    for k in ("JAX_ENABLE_COMPILATION_CACHE", TIME_FLOOR.upper(),
              SIZE_FLOOR.upper()):
        env.pop(k, None)
    env.update(floors)
    out = subprocess.run([sys.executable, "-c", _SCRIPT, mode], env=env,
                         cwd=REPO, capture_output=True, text=True,
                         timeout=180)
    assert out.returncode == 0, out.stderr[-2000:]
    said = [ln.split() for ln in out.stdout.splitlines()]
    return ({ln[0]: ln[1:] for ln in said if ln[0] != "OUTCOME"},
            [ln[1:] for ln in said if ln[0] == "OUTCOME"])


@pytest.mark.parametrize("mode", ["jax_not_loaded", "jax_loaded_first"])
def test_a_second_process_loads_what_took_the_first_under_a_second(
        mode, tmp_path):
    """No floor set by hand, only enable_compile_cache(): the first
    process writes a program the CPU compiles in well under a second (and
    the eager ops around it), the second loads every one of them."""
    first, wrote = _run(mode, tmp_path)
    second, read = _run(mode, tmp_path)
    for said in (first, second):
        # the placement from outside imports no jax; the cache's own
        # initialisation left both floors where they were put
        assert said["IMPORTED"] == [str(mode == "jax_loaded_first")]
        assert [float(v) for v in said["FLOORS"]] == [0.0, 0.0]
    mine = [o for o in wrote if o[0] == "jit(kept_though_small)"]
    assert len(mine) == 1 and mine[0][1] == "miss"   # miss: written
    assert float(mine[0][2]) < 1.0                   # under jax's floor
    assert {o[1] for o in wrote} == {"miss"}
    assert {o[1] for o in read} == {"hit"}           # no `small` is left
    assert sorted(o[0] for o in read) == sorted(o[0] for o in wrote)


def test_a_floor_from_the_environment_stands_in_a_fresh_process(tmp_path):
    """The user's JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS is jax's to
    read: under it nothing is kept, as the user asked."""
    said, outcomes = _run("jax_not_loaded", tmp_path,
                          JAX_PERSISTENT_CACHE_MIN_COMPILE_TIME_SECS="30")
    assert [float(v) for v in said["FLOORS"]] == [30.0, 0.0]
    assert {o[1] for o in outcomes} == {"small"}
    assert not os.listdir(tmp_path)
