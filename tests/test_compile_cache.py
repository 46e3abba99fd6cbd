"""The persistent compile cache: ``$JAX_COMPILATION_CACHE_DIR`` when set
(left to JAX), one fixed directory inside the checkout otherwise; either
way its key keeps the programs' debug metadata (the stage scopes)."""
from pathlib import Path

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_dir_config():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_is_left_to_jax(monkeypatch, cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_path_in_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == first
    root = Path(__file__).resolve().parents[1]
    assert Path(first) == root / ".jax_cache"
    ignored = (root / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


@pytest.fixture
def metadata_key_config():
    was = jax.config.jax_compilation_cache_include_metadata_in_key
    yield
    jax.config.update("jax_compilation_cache_include_metadata_in_key", was)


@pytest.mark.parametrize("env_dir", ["/elsewhere/cache", None],
                         ids=["env-var", "checkout"])
def test_cache_key_keeps_debug_metadata(monkeypatch, cache_dir_config,
                                        metadata_key_config, env_dir):
    """A program that differs only in its ``jax.named_scope`` names must
    not load another's executable: the key keeps the debug metadata on
    both paths."""
    jax.config.update("jax_compilation_cache_include_metadata_in_key", False)
    if env_dir:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", env_dir)
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    compile_cache.enable_compile_cache()
    assert jax.config.jax_compilation_cache_include_metadata_in_key
