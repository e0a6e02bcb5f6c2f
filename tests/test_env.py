"""Tests for repro/launch/env.py — environment assembly for workers.

Pure string/dict behaviour is tested directly; the in-process setters'
after-initialisation guard is tested against this process's already-
initialised JAX (every test session imports jax), which is exactly the
footgun the guard exists for.
"""
import os
import pathlib

import pytest

import jax

from repro.launch import env


def test_merged_flags_replaces_in_place_preserving_others():
    existing = "--a=1 --xla_force_host_platform_device_count=8 --b=2"
    out = env.merged_xla_flags(existing, env.DEVICE_COUNT_FLAG, 4)
    assert out == "--a=1 --xla_force_host_platform_device_count=4 --b=2"


def test_merged_flags_appends_when_absent_and_handles_empty():
    out = env.merged_xla_flags(None, env.DEVICE_COUNT_FLAG, 2)
    assert out == "--xla_force_host_platform_device_count=2"
    out = env.merged_xla_flags("--a=1", "--b", "x")
    assert out == "--a=1 --b=x"


def test_host_device_flags_rejects_nonpositive():
    with pytest.raises(ValueError):
        env.host_device_flags(0)


def test_worker_env_pins_platform_and_devices_without_mutating_base():
    base = {"PYTHONPATH": "/x", "XLA_FLAGS": "--a=1"}
    out = env.worker_env(3, base=base, platform="cpu")
    assert out["JAX_PLATFORMS"] == "cpu"
    assert "--a=1" in out["XLA_FLAGS"]
    assert "--xla_force_host_platform_device_count=3" in out["XLA_FLAGS"]
    assert out["PYTHONPATH"] == "/x"
    assert base == {"PYTHONPATH": "/x", "XLA_FLAGS": "--a=1"}, \
        "worker_env must return a copy"


def test_worker_env_defaults_to_os_environ():
    out = env.worker_env(2)
    # The platform is inherited, never chosen for the child.
    assert out.get("JAX_PLATFORMS") == os.environ.get("JAX_PLATFORMS")
    # Inherits unrelated variables from the real environment.
    assert out.get("PATH") == os.environ.get("PATH")


@pytest.mark.parametrize("platforms", [None, "tpu"])
def test_worker_env_fakes_no_device_count_off_cpu(platforms):
    base = {"XLA_FLAGS": "--a=1"}
    if platforms is not None:
        base["JAX_PLATFORMS"] = platforms
    out = env.worker_env(4, base=base)
    assert out.get("JAX_PLATFORMS") == platforms
    assert out["XLA_FLAGS"] == "--a=1"


def test_worker_env_inherited_cpu_gets_device_count():
    out = env.worker_env(4, base={"JAX_PLATFORMS": "cpu"})
    assert out["XLA_FLAGS"] == "--xla_force_host_platform_device_count=4"


def test_compile_cache_off_under_the_test_suite():
    # tests/conftest.py switches the cache off for the whole suite.
    assert jax.config.jax_enable_compilation_cache is False
    assert env.enable_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir is None


@pytest.mark.parametrize("preset", [None, "/elsewhere/cache"])
def test_compile_cache_dir_env_wins_else_fixed_checkout_path(
        monkeypatch, preset):
    if preset is None:
        monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    else:
        monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", preset)
    jax.config.update("jax_enable_compilation_cache", True)
    updates = []
    try:
        with monkeypatch.context() as m:
            m.setattr(jax.config, "update",
                      lambda k, v: updates.append((k, v)))
            got = env.enable_compile_cache()
    finally:
        jax.config.update("jax_enable_compilation_cache", False)
    if preset is None:
        want = str(pathlib.Path(env.__file__).resolve().parents[3]
                   / ".jax_cache")
        assert got == want
        assert updates == [("jax_compilation_cache_dir", want)]
    else:
        assert got == preset and updates == []


def test_setters_raise_after_jax_initialised():
    jax.devices()                       # force backend initialisation
    with pytest.raises(RuntimeError, match="worker_env"):
        env.set_host_device_count(4)
    with pytest.raises(RuntimeError, match="worker_env"):
        env.set_platform("cpu")


def test_describe_reports_effective_environment():
    jax.devices()
    d = env.describe()
    assert d["jax_imported"] is True
    assert d["pid"] == os.getpid()
    assert d["platform"] == jax.default_backend()
    assert d["device_count"] == jax.device_count()
    assert isinstance(d["x64"], bool)


def test_enable_x64_round_trip():
    try:
        env.enable_x64(True)
        assert jax.config.read("jax_enable_x64") is True
    finally:
        env.enable_x64(False)
    assert jax.config.read("jax_enable_x64") is False
