"""common/compile_cache: the one rule for JAX's persistent cache."""

import os

import jax
import pytest

from lighthouse_tpu.common import compile_cache

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture()
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_directory_is_left_to_jax(monkeypatch, tmp_path,
                                      restore_cache_dir):
    """With JAX_COMPILATION_CACHE_DIR set the code sets no directory:
    JAX reads the variable itself."""
    jax.config.update("jax_compilation_cache_dir", "sentinel")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == "sentinel"


def test_default_is_the_fixed_checkout_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    want = os.path.join(REPO, ".jax_cache")
    assert compile_cache.configure() == want
    assert jax.config.jax_compilation_cache_dir == want
    assert (jax.config.jax_persistent_cache_min_compile_time_secs
            == compile_cache.MIN_COMPILE_SECS)
