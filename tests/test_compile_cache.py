"""``repro.compile_cache``: where JAX's persistent compilation cache lives."""

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def cache_dir_config():
    """Restore JAX's cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


@pytest.mark.parametrize("env", ["/elsewhere/cache", None])
def test_enable_respects_env_else_fixed_checkout_path(
        monkeypatch, cache_dir_config, env):
    jax.config.update("jax_compilation_cache_dir", None)
    if env is None:
        monkeypatch.delenv(compile_cache.ENV, raising=False)
    else:
        monkeypatch.setenv(compile_cache.ENV, env)
    path = compile_cache.enable()
    if env is None:
        # a fixed path inside the checkout: the same on every run
        assert path == str(compile_cache.DEFAULT_DIR)
        assert compile_cache.DEFAULT_DIR.parent.joinpath("src").is_dir()
        assert jax.config.jax_compilation_cache_dir == path
    else:
        # JAX reads the variable itself; no other directory is set
        assert path == env
        assert jax.config.jax_compilation_cache_dir is None
