"""What the program decides from the device it finds: the compile-cache
directory, interpret vs compiled kernels, and the peaks it reads."""
import os
from types import SimpleNamespace

import jax
import pytest

from repro.analysis import hw_specs
from repro.kernels import ops, tpu
from repro.launch import compile_cache


@pytest.fixture
def cache_config():
    """Restore JAX's cache directory after a test that sets it."""
    was = jax.config.jax_compilation_cache_dir
    yield
    jax.config.update("jax_compilation_cache_dir", was)


def test_compile_cache_honours_env(monkeypatch, tmp_path, cache_config):
    monkeypatch.setenv(compile_cache.ENV_VAR, str(tmp_path))
    before = jax.config.jax_compilation_cache_dir
    assert compile_cache.enable_compile_cache() == str(tmp_path)
    # JAX reads the variable itself: nothing else is set in code
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_fixed_in_repo_path(monkeypatch, cache_config):
    monkeypatch.delenv(compile_cache.ENV_VAR, raising=False)
    first = compile_cache.enable_compile_cache()
    assert compile_cache.enable_compile_cache() == first
    repo = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    assert first == os.path.join(repo, ".jax_cache")
    assert jax.config.jax_compilation_cache_dir == first
    with open(os.path.join(repo, ".gitignore")) as f:
        assert ".jax_cache/" in f.read().split()


@pytest.mark.parametrize("backend,interpret", [
    ("cpu", True), ("tpu", False), ("gpu", False)])
def test_interpret_only_on_cpu_backend(monkeypatch, backend, interpret):
    monkeypatch.setattr(jax, "default_backend", lambda: backend)
    assert tpu.interpret() is interpret


def test_kernels_told_the_budget_kernel_fits_checks():
    params = tpu.compiler_params("parallel", "arbitrary")
    assert params.vmem_limit_bytes == ops.VMEM_BUDGET
    assert params.dimension_semantics == ("parallel", "arbitrary")


def test_hw_specs_by_device_kind():
    spec = hw_specs.for_device(SimpleNamespace(device_kind="TPU v5 lite"))
    assert spec is hw_specs.TPU_V5E and spec.source
    assert spec.peak_flops(1) == 2 * spec.peak_flops_bf16


def test_hw_specs_unknown_device_kind_raises():
    with pytest.raises(KeyError, match="no peaks"):
        hw_specs.for_device(SimpleNamespace(device_kind="cpu"))
