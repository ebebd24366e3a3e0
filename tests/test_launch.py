"""Launch helpers: the compile-cache rule and the per-device peak table."""

from pathlib import Path

import jax
import pytest

from repro.launch import compile_cache
from repro.launch import mesh as mesh_lib

REPO = Path(__file__).resolve().parents[1]


@pytest.fixture
def restore_cache_dir():
    prev = jax.config.jax_compilation_cache_dir
    try:
        yield
    finally:
        jax.config.update("jax_compilation_cache_dir", prev)


def test_compile_cache_env_wins_and_nothing_is_set(monkeypatch, tmp_path,
                                                   restore_cache_dir):
    monkeypatch.setenv(compile_cache.CACHE_ENV, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.use_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_fixed_checkout_dir(monkeypatch,
                                                      restore_cache_dir):
    monkeypatch.delenv(compile_cache.CACHE_ENV, raising=False)
    assert compile_cache.CHECKOUT_CACHE == REPO / ".jax_cache"
    assert compile_cache.use_compile_cache() == str(REPO / ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == str(REPO / ".jax_cache")
    # the same path on every call: it is part of every entry's key
    assert compile_cache.use_compile_cache() == str(REPO / ".jax_cache")


def test_checkout_cache_is_ignored_by_git():
    lines = (REPO / ".gitignore").read_text().split()
    assert ".jax_cache/" in lines


def test_peaks_of_a_known_device_kind():
    v5e = mesh_lib.chip_peaks("TPU v5 lite")
    assert (v5e.flops_bf16, v5e.hbm_bw, v5e.hbm_bytes) == (197e12, 819e9,
                                                           16e9)
    assert mesh_lib.chip_peaks(mesh_lib.PRODUCTION_DEVICE_KIND) == v5e


@pytest.mark.parametrize("kind", ["cpu", "TPU v4", "TPU v5p", ""])
def test_unknown_device_kind_is_an_error(kind):
    with pytest.raises(KeyError, match="no peak rates"):
        mesh_lib.chip_peaks(kind)
