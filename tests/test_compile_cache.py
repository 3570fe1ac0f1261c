"""Placement of the persistent compilation cache by the entry points."""
import jax
import pytest

from repro.utils import compile_cache

_KEYS = ("jax_compilation_cache_dir",
         "jax_persistent_cache_min_compile_time_secs",
         "jax_persistent_cache_min_entry_size_bytes")


@pytest.fixture
def restore_config():
    saved = {k: getattr(jax.config, k) for k in _KEYS}
    yield
    for k, v in saved.items():
        jax.config.update(k, v)


def test_cache_stays_where_the_environment_puts_it(monkeypatch, tmp_path,
                                                   restore_config):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper sets no second directory
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == 0


def test_cache_defaults_to_a_fixed_checkout_path(monkeypatch, restore_config):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    first = compile_cache.enable_compile_cache()
    assert first == str(compile_cache.DEFAULT_CACHE_DIR)
    assert jax.config.jax_compilation_cache_dir == first
    assert compile_cache.DEFAULT_CACHE_DIR.parent.joinpath(
        "chip_smoke.py").is_file()  # the repository root, not a temp dir
    assert compile_cache.enable_compile_cache() == first  # same every run
