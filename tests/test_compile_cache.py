"""The compile cache and the tuning tables stay where the checkout says."""
import jax

from repro.kernels import tuning
from repro.launch import cache


def _cache_dir():
    return jax.config.jax_compilation_cache_dir


def test_env_cache_dir_is_left_to_jax(monkeypatch, tmp_path):
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    before = _cache_dir()
    assert cache.use_compile_cache() == str(tmp_path)
    assert _cache_dir() == before            # nothing set in code


def test_default_cache_dir_is_fixed_inside_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = _cache_dir()
    try:
        first = cache.use_compile_cache()
        assert first == _cache_dir() == str(cache.CHECKOUT / ".jax_cache")
        assert cache.use_compile_cache() == first   # same path every call
        assert (cache.CHECKOUT / "src" / "repro").is_dir()
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_local_tuning_table_lives_in_checkout(monkeypatch):
    monkeypatch.delenv("REPRO_CACHE_DIR", raising=False)
    path = tuning.cache_table_path("tpu")
    assert cache.CHECKOUT in path.parents


def test_tables_read_names_loaded_files(monkeypatch, tmp_path):
    monkeypatch.setenv("REPRO_CACHE_DIR", str(tmp_path))
    monkeypatch.setattr(tuning, "TABLES_READ", [])
    tuning.invalidate_measured_cache()
    try:
        tuning._load_measured("cpu")
        assert tuning.TABLES_READ == [str(tuning.package_table_path("cpu"))]
        tuning._load_measured("tpu")             # no table for the chip
        assert len(tuning.TABLES_READ) == 1
    finally:
        tuning.invalidate_measured_cache()
