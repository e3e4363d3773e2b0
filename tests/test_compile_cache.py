"""Where the persistent compilation cache goes
(``chainermn_tpu.utils.compile_cache``): the directory is part of JAX's
cache key, so it is the environment's or one fixed path — never a name
that moves between runs."""

import os

import jax
import pytest

import chainermn_tpu
from chainermn_tpu.utils.compile_cache import enable_compile_cache


@pytest.fixture
def cache_dir_config():
    """The ``jax_compilation_cache_dir`` setting, restored afterwards."""
    was = jax.config.jax_compilation_cache_dir
    yield lambda: jax.config.jax_compilation_cache_dir
    jax.config.update("jax_compilation_cache_dir", was)


def test_cpu_rehearsals_get_no_cache(monkeypatch, cache_dir_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = cache_dir_config()
    assert jax.default_backend() == "cpu"
    assert enable_compile_cache() is None
    assert cache_dir_config() == before


def test_environment_wins_and_nothing_is_set_in_code(
        monkeypatch, cache_dir_config, tmp_path):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = cache_dir_config()
    assert enable_compile_cache() == str(tmp_path)
    assert cache_dir_config() == before


def test_default_is_fixed_under_the_checkout(monkeypatch,
                                             cache_dir_config):
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    checkout = os.path.dirname(os.path.dirname(chainermn_tpu.__file__))
    expected = os.path.join(checkout, ".jax_cache")
    assert enable_compile_cache() == expected
    assert enable_compile_cache() == expected  # same path every call
    assert cache_dir_config() == expected
