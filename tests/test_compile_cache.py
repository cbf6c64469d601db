"""Placement of JAX's persistent compilation cache by the entry points."""

from pathlib import Path

import jax
import pytest

from repro.runtime import compile_cache


@pytest.fixture()
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", prev)


def test_set_variable_is_left_to_jax(monkeypatch, tmp_path,
                                     restore_cache_dir):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_unset_variable_uses_fixed_checkout_path(monkeypatch,
                                                 restore_cache_dir):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.configure()
    assert first == compile_cache.configure()  # fixed, not per-call
    assert jax.config.jax_compilation_cache_dir == first
    root = Path(__file__).resolve().parents[1]
    assert Path(first) == root / ".jax_cache"
    assert ".jax_cache/" in (root / ".gitignore").read_text().split()
