"""The compile-cache rule every entry point shares."""

from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import DEFAULT_DIR, ENV_VAR, enable_compile_cache

REPO = Path(__file__).resolve().parent.parent


@pytest.fixture
def cache_dir_restored():
    from jax.experimental.compilation_cache import compilation_cache
    was = jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", None)
    yield
    jax.config.update("jax_compilation_cache_dir", was)
    compilation_cache.reset_cache()


def test_env_var_dir_is_left_to_jax(monkeypatch, tmp_path,
                                    cache_dir_restored):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None  # nothing set here


def test_default_dir_is_fixed_in_the_checkout(monkeypatch,
                                              cache_dir_restored):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert DEFAULT_DIR == REPO / ".jax_cache"
    assert enable_compile_cache() == str(DEFAULT_DIR)
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
