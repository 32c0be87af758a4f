"""The compile-cache helper every entry point calls first."""

import jax
import pytest

from repro.launch import device


@pytest.fixture
def cache_config():
    """Restore the cache directory the helper may set."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_env_dir_left_to_jax(cache_config, monkeypatch, tmp_path):
    monkeypatch.setenv(device.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert device.enable_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_fixed_repo_dir_without_env(cache_config, monkeypatch):
    monkeypatch.delenv(device.CACHE_ENV, raising=False)
    path = device.enable_compile_cache()
    root = device.REPO_CACHE_DIR.parent
    assert path == str(root / ".jax_cache")
    assert (root / "chip_smoke.py").is_file()
    assert jax.config.jax_compilation_cache_dir == path
    # the same path on every call: the directory is part of the cache key
    assert device.enable_compile_cache() == path


def test_device_info_names_the_backend():
    info = device.device_info()
    assert info["platform"] == jax.devices()[0].platform
    assert info["count"] == len(jax.devices())
