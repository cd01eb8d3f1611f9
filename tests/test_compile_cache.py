"""The persistent compilation cache goes where the environment says, or
to one fixed directory in the checkout."""

from pathlib import Path

import jax
import pytest

from repro.launch.compile_cache import ENV, use_compile_cache


@pytest.fixture
def cache_dir_restored():
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_is_left_to_jax(monkeypatch, tmp_path, cache_dir_restored):
    monkeypatch.setenv(ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_checkout(monkeypatch, cache_dir_restored):
    monkeypatch.delenv(ENV, raising=False)
    checkout = Path(__file__).resolve().parents[1]
    first = use_compile_cache()
    assert first == str(checkout / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    assert use_compile_cache() == first
