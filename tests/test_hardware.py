"""Per-device hardware constants and the compile-cache location."""
import jax
import pytest

from repro.kernels import hardware
from repro.runtime import compile_cache


def test_v5e_entry_is_keyed_by_device_kind():
    v5e = hardware.spec("TPU v5 lite")
    assert v5e.peak_flops_bf16 == 197e12 and v5e.hbm_bw == 819e9
    assert v5e.vmem_bytes == 128 * 1024 * 1024


def test_cpu_has_an_explicit_entry():
    assert hardware.spec("cpu").vmem_bytes == 0
    assert hardware.spec() is hardware.spec("cpu")  # this process runs on CPU


def test_unknown_tpu_kind_is_an_error():
    with pytest.raises(KeyError, match="TPU v99"):
        hardware.spec("TPU v99")


def test_kernels_target_the_default_chip_off_tpu():
    assert hardware.tpu_spec() is hardware.spec(hardware.DEFAULT_TPU_KIND)


def test_compile_cache_respects_the_environment(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", "/elsewhere/cache")
    assert compile_cache.configure() == "/elsewhere/cache"
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_to_a_fixed_path_in_the_checkout(monkeypatch):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    try:
        path = compile_cache.configure()
        assert path == str(compile_cache.DEFAULT_DIR) == compile_cache.configure()
        assert path.endswith(".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
