"""The persistent compilation cache helper every entry point calls."""

from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache

from repro.compile_cache import (
    DEFAULT_DIR,
    ENV_VAR,
    compile_cache_dir,
    enable_compile_cache,
)

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_config():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)
    compilation_cache.reset_cache()


def test_env_var_names_the_directory(monkeypatch, tmp_path,
                                     restore_cache_config):
    monkeypatch.setenv(ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache_dir() == tmp_path
    assert enable_compile_cache() == tmp_path
    # JAX reads the variable itself; the helper sets no other directory.
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_in_checkout_path_without_env(monkeypatch,
                                            restore_cache_config):
    monkeypatch.delenv(ENV_VAR, raising=False)
    assert compile_cache_dir() == DEFAULT_DIR == REPO / ".jax_cache"
    assert enable_compile_cache() == DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().splitlines()


def test_empty_env_var_falls_back_to_checkout(monkeypatch,
                                              restore_cache_config):
    monkeypatch.setenv(ENV_VAR, "")
    assert compile_cache_dir() == DEFAULT_DIR
    assert enable_compile_cache() == DEFAULT_DIR
    assert jax.config.jax_compilation_cache_dir == str(DEFAULT_DIR)
