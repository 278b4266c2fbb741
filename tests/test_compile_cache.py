"""Where the entry points keep JAX's persistent compilation cache."""
import jax
import pytest

from repro import utils


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_env_cache_dir_is_left_to_jax(monkeypatch, cache_config):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert utils.enable_compile_cache() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_cache_dir_is_fixed_inside_the_checkout(monkeypatch,
                                                         cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = utils.enable_compile_cache()
    assert got == str(utils.REPO_COMPILE_CACHE) == utils.enable_compile_cache()
    assert jax.config.jax_compilation_cache_dir == got
    root = utils.REPO_COMPILE_CACHE.parent
    assert (root / "pyproject.toml").is_file()
    assert ".jax_cache/" in (root / ".gitignore").read_text().splitlines()
