"""Where the entry points put JAX's persistent compilation cache."""
import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_unset_env_places_the_cache_in_the_checkout(monkeypatch,
                                                    restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    got = compile_cache.place_compile_cache()
    assert got == str(compile_cache.CHECKOUT / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == got
    assert (compile_cache.CHECKOUT / "chip_smoke.py").exists()


def test_env_var_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                         restore_cache_dir):
    jax.config.update("jax_compilation_cache_dir", None)
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.place_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir is None
