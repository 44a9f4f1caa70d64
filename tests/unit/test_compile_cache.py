"""The persistent compile cache: JAX's env var wins, else a fixed repo path."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache as cc

_OPTS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def restore_config():
    saved = {k: getattr(jax.config, k) for k in _OPTS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_env_var_is_respected(monkeypatch, restore_config, tmp_path):
    env_dir = str(tmp_path / "cache")
    monkeypatch.setenv(cc.CACHE_ENV, env_dir)
    # JAX reads the variable at start-up; stand in for that here
    jax.config.update("jax_compilation_cache_dir", env_dir)
    assert cc.enable_compile_cache() == env_dir
    assert jax.config.jax_compilation_cache_dir == env_dir
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0


def test_fixed_repo_path_without_env(monkeypatch, restore_config):
    monkeypatch.delenv(cc.CACHE_ENV, raising=False)
    repo = Path(__file__).resolve().parents[2]
    assert cc.DEFAULT_CACHE_DIR == repo / ".jax_cache"
    assert cc.enable_compile_cache() == str(repo / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(repo / ".jax_cache")
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0
    # the same path on every call: nothing derived from time, pid or tmp
    assert cc.enable_compile_cache() == str(repo / ".jax_cache")
