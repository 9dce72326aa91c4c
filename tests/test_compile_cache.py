"""The entry points' persistent compile cache lands where it is told to.

``JAX_COMPILATION_CACHE_DIR`` wins when set; otherwise the cache is the
fixed ``<checkout>/.jax_cache``. Each test points the cache at its own
temporary directory and restores JAX's cache settings afterwards.
"""

from __future__ import annotations

import os

import jax
import jax.numpy as jnp
import pytest
from jax._src import compilation_cache

from repro.launch import compile_cache


@pytest.fixture
def fresh_cache():
    """Restore the process's cache settings (and drop the initialized
    cache object) around a test that enables the cache."""
    keys = ("jax_compilation_cache_dir",
            "jax_persistent_cache_min_compile_time_secs")
    saved = {k: getattr(jax.config, k) for k in keys}
    compilation_cache.reset_cache()
    yield
    compilation_cache.reset_cache()
    for k, v in saved.items():
        jax.config.update(k, v)


def _compile_something(k: int):
    # a distinct program per test, so no in-memory cache hit hides the write
    jax.jit(lambda x: x * k + 1).lower(jnp.zeros((3,), jnp.float32)).compile()


def test_default_dir_is_in_the_checkout():
    root = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert compile_cache.DEFAULT_DIR == os.path.join(root, ".jax_cache")


@pytest.mark.parametrize("from_env", [True, False])
def test_cache_lands_in_its_directory(fresh_cache, monkeypatch, tmp_path,
                                      from_env):
    want = tmp_path / "cache"
    if from_env:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(want))
        monkeypatch.setattr(compile_cache, "DEFAULT_DIR",
                            str(tmp_path / "unused"))
    else:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
        monkeypatch.setattr(compile_cache, "DEFAULT_DIR", str(want))
    assert compile_cache.enable_compile_cache() == str(want)
    _compile_something(3 if from_env else 5)
    assert want.is_dir() and any(want.iterdir())
    assert not (tmp_path / "unused").exists()
