"""The persistent compilation cache is placed from outside, or at a fixed
path in the checkout."""

import os

import jax
import pytest

from repro.launch import compile_cache


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_is_left_to_jax(monkeypatch, restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/somewhere/else")
    assert compile_cache.enable() == "/somewhere/else"
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_the_checkout(monkeypatch,
                                              restore_cache_dir):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable()
    assert path == os.path.join(compile_cache.CHECKOUT, ".jax_cache")
    assert os.path.isfile(os.path.join(compile_cache.CHECKOUT,
                                       "chip_smoke.py"))
    assert jax.config.jax_compilation_cache_dir == path
    assert compile_cache.enable() == path  # the same on every call
