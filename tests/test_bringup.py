"""The chip bring-up surface that runs without a chip: the persistent
compile-cache helper and ``chip_smoke.py``'s refusal to run off a TPU."""
import json
import os
import subprocess
import sys

import jax
import pytest

REPO = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))


@pytest.fixture
def restore_cache_config():
    from jax.experimental.compilation_cache import compilation_cache
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      before[1])
    compilation_cache.reset_cache()


def test_compile_cache_honours_env_dir(monkeypatch, tmp_path,
                                       restore_cache_config):
    from repro.launch import compile_cache
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.use_persistent_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)
    # without the variable: one fixed, git-ignored directory in the repo
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert compile_cache.use_persistent_cache() == os.path.join(
        REPO, ".jax_cache")
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


def test_chip_smoke_fails_without_tpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    proc = subprocess.run([sys.executable, "chip_smoke.py"], cwd=REPO,
                          env=env, capture_output=True, text=True,
                          timeout=300)
    assert proc.returncode != 0
    assert "device: FAIL" in proc.stdout and "no TPU" in proc.stdout
    for line in proc.stdout.splitlines():
        with pytest.raises(json.JSONDecodeError):
            json.loads(line)
