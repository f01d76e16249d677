"""The persistent compile cache: the env var wins, else a fixed in-checkout
directory that git ignores."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    before = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", before)


@pytest.mark.parametrize("env_dir", [None, "elsewhere"])
def test_cache_dir_follows_env_else_checkout(monkeypatch, tmp_path,
                                             restore_cache_dir, env_dir):
    if env_dir is None:
        monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
        want = str(REPO / ".jax_cache")
    else:
        want = str(tmp_path / env_dir)
        monkeypatch.setenv(compile_cache.ENV_VAR, want)
    assert compile_cache.enable_compile_cache() == want
    assert jax.config.jax_compilation_cache_dir == want


def test_default_cache_dir_is_git_ignored():
    rel = compile_cache.DEFAULT_DIR.relative_to(REPO)
    ignored = (REPO / ".gitignore").read_text().split()
    assert f"{rel}/" in ignored or str(rel) in ignored
