"""Where the entry points keep JAX's persistent compile cache."""
from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache

REPO = Path(__file__).resolve().parents[1]
_KEYS = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs")


@pytest.fixture
def cache_config():
    prev = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in prev.items():
        jax.config.update(k, v)


def test_env_dir_is_left_to_jax(monkeypatch, cache_config, tmp_path):
    monkeypatch.setenv(compile_cache.ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.configure() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_default_dir_is_fixed_in_checkout_and_ignored(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV, raising=False)
    first = compile_cache.configure()
    assert first == str(REPO / ".jax_cache") == compile_cache.configure()
    assert jax.config.jax_compilation_cache_dir == first
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert ".jax_cache/" in (REPO / ".gitignore").read_text().split()
