"""The persistent compilation cache goes where the environment says, or
to a fixed ``<root>/.jax_cache``; the helper never picks a second one."""
import os

import jax
import pytest

from repro.compile_cache import CACHE_DIRNAME, ENV_VAR, use_compile_cache


@pytest.fixture
def cache_config():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


def test_environment_wins_and_nothing_is_set(tmp_path, monkeypatch,
                                             cache_config):
    monkeypatch.setenv(ENV_VAR, str(tmp_path / "outside"))
    before = jax.config.jax_compilation_cache_dir
    assert use_compile_cache(str(tmp_path)) == str(tmp_path / "outside")
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_directory_under_root(tmp_path, monkeypatch, cache_config):
    monkeypatch.delenv(ENV_VAR, raising=False)
    want = os.path.join(str(tmp_path), CACHE_DIRNAME)
    assert use_compile_cache(str(tmp_path)) == want
    assert jax.config.jax_compilation_cache_dir == want
    # a second call from anywhere else in the tree lands in the same place
    assert use_compile_cache(str(tmp_path) + os.sep) == want
