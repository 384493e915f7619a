"""Where the entry points' persistent compilation cache lands."""
import jax
import pytest

from repro.compile_cache import CHECKOUT_ROOT, enable_compile_cache


@pytest.fixture
def cache_dir_config():
    """Restores JAX's cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                         cache_dir_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_is_fixed_under_the_checkout(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = enable_compile_cache()
    assert first == str(CHECKOUT_ROOT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert (CHECKOUT_ROOT / "src" / "repro" / "compile_cache.py").exists()
    assert enable_compile_cache() == first  # no pid, time or temp name
