"""Settings derived from the device: target, Pallas interpret mode, and the
persistent compilation cache's location."""
import types

import jax
import pytest

from repro.core.simulator import Simulator
from repro.core.target import (CPU_TEST, TPU_V5E, device_target,
                               resolve_interpret)
from repro.engine import BatchExecutor
from repro.launch import compile_cache as CC


def _device(platform, kind):
    return types.SimpleNamespace(platform=platform, device_kind=kind)


def test_cpu_maps_to_cpu_test():
    assert device_target(_device("cpu", "cpu")) is CPU_TEST
    assert device_target() is CPU_TEST          # tests run on the CPU


def test_tpu_v5e_maps_to_tpu_v5e():
    assert device_target(_device("tpu", "TPU v5 lite")) is TPU_V5E


@pytest.mark.parametrize("platform,kind", [
    ("tpu", "TPU v4"), ("tpu", "TPU v6 lite"), ("gpu", "NVIDIA H100")])
def test_unknown_device_raises(platform, kind):
    with pytest.raises(ValueError, match="no target"):
        device_target(_device(platform, kind))


def test_interpret_follows_platform():
    cpu, tpu = _device("cpu", "cpu"), _device("tpu", "TPU v5 lite")
    assert resolve_interpret(None, cpu) is True
    assert resolve_interpret(None, tpu) is False
    assert resolve_interpret(False, cpu) is False
    with pytest.raises(ValueError, match="TPU"):
        resolve_interpret(True, tpu)


def test_front_doors_default_to_the_device():
    assert Simulator().target is device_target()
    assert Simulator(backend="pallas").interpret is True
    assert BatchExecutor().target is device_target()


def test_cache_dir_honours_env(monkeypatch, tmp_path):
    monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
    assert CC.cache_dir() == str(tmp_path)


def test_cache_dir_default_is_fixed_in_checkout(monkeypatch):
    monkeypatch.delenv(CC.ENV_VAR, raising=False)
    assert CC.cache_dir() == CC.cache_dir() == str(CC.DEFAULT_DIR)
    assert CC.DEFAULT_DIR.name == ".jax_cache"
    assert (CC.DEFAULT_DIR.parent / "src" / "repro").is_dir()


@pytest.mark.parametrize("from_env", [False, True])
def test_enable_compile_cache(monkeypatch, tmp_path, from_env):
    before = jax.config.jax_compilation_cache_dir
    if from_env:
        monkeypatch.setenv(CC.ENV_VAR, str(tmp_path))
    else:
        monkeypatch.delenv(CC.ENV_VAR, raising=False)
    try:
        path = CC.enable_compile_cache()
        if from_env:
            assert path == str(tmp_path)
            assert jax.config.jax_compilation_cache_dir == before
        else:
            assert path == str(CC.DEFAULT_DIR)
            assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)
