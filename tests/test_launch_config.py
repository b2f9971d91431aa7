"""Launch-time configuration: the compile-cache directory and the per-chip
peak table."""

import jax
import pytest

from repro.launch import compile_cache
from repro.launch.mesh import PEAKS, chip_peaks


@pytest.fixture
def jax_cache_config():
    saved = (jax.config.jax_compilation_cache_dir,
             jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", saved[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs", saved[1])


def test_compile_cache_follows_the_environment(jax_cache_config, monkeypatch,
                                               tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself; the helper names no other directory
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0


def test_compile_cache_defaults_to_the_checkout(jax_cache_config,
                                                monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    path = compile_cache.enable_compile_cache()
    assert path == str(compile_cache.CHECKOUT_CACHE_DIR)
    assert compile_cache.CHECKOUT_CACHE_DIR.parent.joinpath(
        "chip_smoke.py").exists()
    assert jax.config.jax_compilation_cache_dir == path


@pytest.mark.parametrize("kind", sorted(PEAKS))
def test_peaks_are_published_numbers(kind):
    peaks = chip_peaks(kind)
    assert peaks["flops_bf16"] > 0 and peaks["hbm_bw"] > 0


def test_unknown_chip_has_no_default_peaks():
    with pytest.raises(KeyError, match="no published peaks"):
        chip_peaks("TPU v99")
