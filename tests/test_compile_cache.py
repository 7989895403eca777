"""`enable_compile_cache`: the environment's directory when it names one,
otherwise a fixed directory inside the checkout."""

import jax
import pytest

from repro.compile_cache import REPO_CACHE_DIR, enable_compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_environment_directory_is_used_and_nothing_set(monkeypatch,
                                                        restore_cache_dir):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/placed/from/outside")
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == "/placed/from/outside"
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_checkout_directory_otherwise(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    assert enable_compile_cache() == str(REPO_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == str(REPO_CACHE_DIR)
    assert REPO_CACHE_DIR.name == ".jax_cache"
    assert (REPO_CACHE_DIR.parent / "chip_smoke.py").exists()
