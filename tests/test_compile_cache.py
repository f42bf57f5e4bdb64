"""Where the entry points keep JAX's persistent compile cache
(launch/compile_cache.py): the directory named by
JAX_COMPILATION_CACHE_DIR when it is set, else one fixed, git-ignored
path inside the checkout."""
import os

import jax
import pytest

from repro.launch import compile_cache as CC

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_env_dir_wins(monkeypatch, tmp_path, restore_cache_dir):
    monkeypatch.setenv(CC.ENV, str(tmp_path))
    assert CC.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == str(tmp_path)


def test_default_is_fixed_ignored_repo_path(monkeypatch, restore_cache_dir):
    monkeypatch.delenv(CC.ENV, raising=False)
    flags = {k: os.environ.get(k) for k in ("XLA_FLAGS", "LIBTPU_INIT_ARGS")}
    path = CC.enable_compile_cache()
    assert path == os.path.join(REPO, ".jax_cache")
    assert CC.enable_compile_cache() == path        # never a fresh name
    assert jax.config.jax_compilation_cache_dir == path
    assert {k: os.environ.get(k) for k in flags} == flags
    with open(os.path.join(REPO, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()
