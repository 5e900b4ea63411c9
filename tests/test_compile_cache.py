"""Where the persistent compilation cache goes (`repro.compile_cache`)."""

from __future__ import annotations

import os

import jax
import pytest

from repro.compile_cache import ENV, enable_compile_cache


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after the test."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_directory_wins_and_config_is_untouched(cache_config, monkeypatch,
                                                    tmp_path):
    monkeypatch.setenv(ENV, str(tmp_path / "outside"))
    before = jax.config.jax_compilation_cache_dir
    assert enable_compile_cache(str(tmp_path)) == str(tmp_path / "outside")
    assert jax.config.jax_compilation_cache_dir == before


@pytest.mark.parametrize("relative", [False, True])
def test_default_is_fixed_directory_in_checkout(cache_config, monkeypatch,
                                                tmp_path, relative):
    monkeypatch.delenv(ENV, raising=False)
    monkeypatch.chdir(tmp_path)
    root = "." if relative else str(tmp_path)
    want = os.path.join(str(tmp_path), ".jax_cache")
    assert enable_compile_cache(root) == want
    assert jax.config.jax_compilation_cache_dir == want
    # the same checkout always maps to the same directory
    assert enable_compile_cache(root) == want
