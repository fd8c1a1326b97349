"""m3_tpu/device.py: the one rule for "is this the chip", require_device,
and the compile-cache helper."""

import os
import subprocess
import sys

import jax
import pytest

from m3_tpu import device

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def test_on_tpu_is_false_on_the_cpu_mesh():
    assert device.on_tpu() is False


def test_require_device_accepts_cpu_only_when_named(monkeypatch):
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    platform, count, kind = device.require_device()
    assert (platform, count) == ("cpu", len(jax.devices()))
    assert kind == jax.devices()[0].device_kind


@pytest.mark.parametrize("value", [None, "", "tpu"])
def test_require_device_raises_on_cpu_backend_not_named(monkeypatch, value):
    if value is None:
        monkeypatch.delenv("JAX_PLATFORMS", raising=False)
    else:
        monkeypatch.setenv("JAX_PLATFORMS", value)
    with pytest.raises(RuntimeError, match="device tier"):
        device.require_device()


def test_compile_cache_left_alone_when_env_names_one(monkeypatch, tmp_path):
    before = jax.config.jax_compilation_cache_dir
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert device.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before


def test_compile_cache_defaults_inside_the_checkout(monkeypatch):
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    try:
        path = device.configure_compile_cache()
        assert path == os.path.join(REPO, ".jax_cache")
        assert jax.config.jax_compilation_cache_dir == path
    finally:
        jax.config.update("jax_compilation_cache_dir", before)


def test_dbnode_with_device_tier_and_no_chip_exits_nonzero(tmp_path):
    """A dbnode asked for a device tier on a machine with no chip does not
    carry on as a host TSDB. (jax is pinned to the cpu backend through its
    config flag's own variable, so JAX_PLATFORMS stays unset — the
    explicit-choice rule is what is under test.)"""
    env = {k: v for k, v in os.environ.items() if k != "JAX_PLATFORMS"}
    env["JAX_PLATFORM_NAME"] = "cpu"
    res = subprocess.run(
        [sys.executable, "-m", "m3_tpu.services.dbnode", "--base-dir",
         str(tmp_path), "--no-mediator", "--resident-bytes", str(1 << 20)],
        cwd=REPO, env=env, capture_output=True, text=True, timeout=120,
    )
    assert res.returncode != 0
    assert "LISTENING" not in res.stdout
    assert "device tier" in res.stderr
