"""The persistent compilation cache goes where JAX_COMPILATION_CACHE_DIR
says, else to one fixed directory inside the checkout."""
import pathlib

import jax
import pytest

from repro import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_wins_and_nothing_is_set(monkeypatch, restore_cache_dir, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_inside_the_checkout(monkeypatch, restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    got = compile_cache.configure()
    repo = pathlib.Path(__file__).resolve().parents[1]
    assert got == str(repo / ".jax_cache") == jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() == got  # no temp name, pid or time in it
    assert ".jax_cache/" in (repo / ".gitignore").read_text().splitlines()
