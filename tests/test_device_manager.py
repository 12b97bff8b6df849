"""What the device manager resolved is on record, and nothing about the
device or the compile cache is assumed silently (memory/device.py,
package __init__)."""

import os

import jax
import pytest

import spark_rapids_tpu
from spark_rapids_tpu.memory.device import TpuDeviceManager


class _FakeDevice:
    def __init__(self, platform, stats):
        self.platform, self._stats = platform, stats

    def memory_stats(self):
        return self._stats


def test_tpu_without_bytes_limit_is_an_error_not_16gib():
    probe = TpuDeviceManager._probe_hbm_bytes
    assert probe(_FakeDevice("tpu", {"bytes_limit": 123})) == 123
    assert probe(_FakeDevice("cpu", None)) == 16 << 30  # the test mesh
    for stats in (None, {}, {"bytes_in_use": 1}):
        with pytest.raises(RuntimeError, match="bytes_limit"):
            probe(_FakeDevice("tpu", stats))


def test_resolved_device_is_recorded_and_served(session):
    from spark_rapids_tpu.obs import monitor
    dm = session.device_manager
    d = jax.devices()[0]
    assert (dm.platform, dm.device_kind) == (d.platform, d.device_kind)
    assert dm.num_local_devices == len(jax.devices())
    assert set(dm.hbm_per_device) == set(jax.devices())
    dev = monitor.status_snapshot()["device"]
    assert dev["platform"] == "cpu" and dev["deviceKind"] == d.device_kind
    assert dev["compileCacheDir"] == dm.compile_cache_dir


@pytest.fixture
def _cache_config():
    names = ("jax_compilation_cache_dir",
             "jax_persistent_cache_min_compile_time_secs",
             "jax_persistent_cache_min_entry_size_bytes")
    before = {n: getattr(jax.config, n) for n in names}
    yield
    for n, v in before.items():
        jax.config.update(n, v)


def test_compile_cache_goes_where_the_environment_says(
        monkeypatch, tmp_path, _cache_config):
    # XLA:CPU with nothing asked: off, and no directory is made up
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR", raising=False)
    before = jax.config.jax_compilation_cache_dir
    assert spark_rapids_tpu.configure_compile_cache() is None
    assert jax.config.jax_compilation_cache_dir == before
    # asked: that directory, untouched by code, with nothing filtered out
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path))
    assert spark_rapids_tpu.configure_compile_cache() == str(tmp_path)
    assert jax.config.jax_compilation_cache_dir == before
    assert jax.config.jax_persistent_cache_min_compile_time_secs == 0.0
    assert jax.config.jax_persistent_cache_min_entry_size_bytes == -1
    # an accelerator with nothing asked: one fixed path in the checkout
    monkeypatch.delenv("JAX_COMPILATION_CACHE_DIR")
    assert spark_rapids_tpu._REPO_CACHE_DIR == os.path.join(
        os.path.dirname(os.path.dirname(spark_rapids_tpu.__file__)),
        ".jax_cache")
    monkeypatch.setattr(jax, "default_backend", lambda: "tpu")
    monkeypatch.setattr(spark_rapids_tpu, "_REPO_CACHE_DIR",
                        str(tmp_path / ".jax_cache"))
    assert spark_rapids_tpu.configure_compile_cache() \
        == str(tmp_path / ".jax_cache")
    assert (tmp_path / ".jax_cache").is_dir()
