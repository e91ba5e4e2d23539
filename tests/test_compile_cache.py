"""Where ``repro.compile_cache.use_compile_cache`` puts JAX's persistent
compilation cache: ``JAX_COMPILATION_CACHE_DIR`` when it is set (and then
nothing is set in code), else the fixed, git-ignored ``<checkout>/.jax_cache``.
"""

from pathlib import Path

import pytest

import jax
import jax.numpy as jnp
from jax.experimental.compilation_cache import compilation_cache

from repro import compile_cache

ROOT = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_jax_cache_config():
    keys = ("jax_compilation_cache_dir", "jax_persistent_cache_min_compile_time_secs",
            "jax_enable_compilation_cache")
    was = {k: getattr(jax.config, k) for k in keys}
    yield
    for k, v in was.items():
        jax.config.update(k, v)
    compilation_cache.reset_cache()


def test_fallback_is_the_checkouts_ignored_directory():
    assert compile_cache.CHECKOUT_CACHE_DIR == ROOT / ".jax_cache"
    ignored = (ROOT / ".gitignore").read_text().splitlines()
    assert ".jax_cache/" in ignored


def test_environment_wins_and_nothing_is_set_in_code(monkeypatch, tmp_path,
                                                     restore_jax_cache_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "env"))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path / "env")
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_without_environment_programs_land_in_the_fixed_directory(
        monkeypatch, tmp_path, restore_jax_cache_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    target = tmp_path / ".jax_cache"
    monkeypatch.setattr(compile_cache, "CHECKOUT_CACHE_DIR", target)
    jax.config.update("jax_enable_compilation_cache", True)
    assert compile_cache.use_compile_cache() == str(target)
    compilation_cache.reset_cache()

    # a program no other test compiles, so this is a cache miss and a write
    jax.jit(lambda x: jnp.cos(x) * 3.25 + 17.0)(jnp.arange(11.0)).block_until_ready()
    assert target.is_dir() and any(target.iterdir())
