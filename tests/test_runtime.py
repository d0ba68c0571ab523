"""Where the persistent compilation cache lives (``launch.runtime``).

``JAX_COMPILATION_CACHE_DIR`` set: JAX's own setting stands and no other
directory is configured.  Unset: one fixed directory inside the checkout,
the same on every run, so a later run finds what an earlier one cached.
"""
from pathlib import Path

import jax
import pytest
from jax.experimental.compilation_cache import compilation_cache as cc

from repro.launch import runtime

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def cache_config():
    saved = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", saved)
    cc.reset_cache()


def test_env_dir_is_left_as_given(monkeypatch, tmp_path, cache_config):
    given = str(tmp_path / "jax-cache")
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", given)
    # JAX reads the variable when it is imported; this process imported
    # it earlier, so stand in for that read
    jax.config.update("jax_compilation_cache_dir", given)
    assert runtime.maybe_enable_compilation_cache() == given
    assert jax.config.jax_compilation_cache_dir == given
    assert not Path(given).exists()          # nothing set up in its place


def test_unset_uses_the_fixed_in_checkout_dir(monkeypatch, cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = runtime.maybe_enable_compilation_cache()
    assert first == str(REPO / ".jax_cache") == runtime.default_cache_dir()
    assert jax.config.jax_compilation_cache_dir == first
    assert Path(first).is_dir()
    assert runtime.maybe_enable_compilation_cache() == first   # stable
    ignored = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in ignored
