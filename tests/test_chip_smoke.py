"""chip_smoke.py's contract with the driver, as far as a CPU can show it:
the shape of the last line, failure without an accelerator, and where
the compile cache goes."""

import json
import os
import subprocess
import sys

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.mark.parametrize("ok,device", [
    (True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}),
    (True, {"platform": "tpu", "kind": "TPU v5 lite", "count": 4,
            "phase": "fsdp", "tokens_per_s": 1.0}),
    (False, {}),
])
def test_last_line_has_exactly_the_contract_keys(ok, device):
    import chip_smoke
    line = chip_smoke.render_last_line(ok, device)
    assert "\n" not in line
    parsed = json.loads(line)
    assert set(parsed) == {"ok", "device"}
    assert set(parsed["device"]) == {"platform", "kind", "count"}
    assert parsed["ok"] is ok
    assert parsed["device"]["count"] == device.get("count")


@pytest.mark.parametrize("args", [[], ["--chips", "4"]])
def test_fails_without_an_accelerator(args):
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run(
        [sys.executable, os.path.join(REPO, "chip_smoke.py"), *args],
        env=env, capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, None)
    assert '"ok": true' not in proc.stdout
    last = json.loads(proc.stdout.strip().splitlines()[-1])
    assert set(last) == {"ok", "device"} and last["ok"] is False
    assert set(last["device"]) == {"platform", "kind", "count"}


def test_fails_in_a_directory_without_the_repo(tmp_path):
    script = tmp_path / "chip_smoke.py"
    with open(os.path.join(REPO, "chip_smoke.py")) as src:
        script.write_text(src.read())
    env = {k: v for k, v in os.environ.items() if k != "PYTHONPATH"}
    proc = subprocess.run(
        [sys.executable, str(script)], env=env, cwd=tmp_path,
        capture_output=True, text=True, timeout=120)
    assert proc.returncode not in (0, None)
    assert '"ok": true' not in proc.stdout
    assert json.loads(proc.stdout.strip().splitlines()[-1])["ok"] is False


def test_compile_cache_dir_is_placed_from_outside_or_fixed(
        tmp_path, monkeypatch):
    import jax

    from ray_tpu._private.compile_cache import (_FLOORS, compile_cache_dir,
                                                enable_compile_cache)
    was = jax.config.jax_compilation_cache_dir
    floors = {k: getattr(jax.config, k) for k in _FLOORS}
    try:
        outside = str(tmp_path / "placed")
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", outside)
        assert enable_compile_cache() == outside == compile_cache_dir()
        # JAX reads the variable itself; no code sets another directory
        assert jax.config.jax_compilation_cache_dir == was

        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
        fixed = os.path.join(REPO, ".jax_cache")
        for cwd in (tmp_path, REPO):
            monkeypatch.chdir(cwd)
            assert enable_compile_cache() == fixed == compile_cache_dir()
            assert jax.config.jax_compilation_cache_dir == fixed
    finally:
        jax.config.update("jax_compilation_cache_dir", was)
        for k, v in floors.items():   # enable_compile_cache() puts them at 0
            jax.config.update(k, v)
