"""Where the persistent compilation cache goes."""

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.compile_cache import ENV_VAR, compile_cache_dir, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    jax.config.update("jax_compilation_cache_dir", None)
    assert compile_cache_dir() == str(tmp_path)
    assert enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; nothing here sets another directory.
    assert jax.config.jax_compilation_cache_dir is None


@pytest.mark.parametrize("pid", [1, 4242])
def test_fixed_path_in_checkout(monkeypatch, tmp_path, pid, restore_cache_dir):
    monkeypatch.delenv(ENV_VAR, raising=False)
    monkeypatch.setenv("TMPDIR", str(tmp_path))
    monkeypatch.setattr(compile_cache.os, "getpid", lambda: pid)
    want = str(compile_cache.REPO_ROOT / ".jax_cache")
    assert compile_cache_dir() == want
    assert enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert (compile_cache.REPO_ROOT / "chip_smoke.py").exists()
