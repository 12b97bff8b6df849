"""Test configuration: run everything on a virtual 8-device CPU mesh.

Multi-chip hardware is not available in CI; sharding/collective paths are
validated on ``xla_force_host_platform_device_count=8``. The environment
alone decides the platform, so this must run before jax is imported
anywhere.
"""

import os

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()

import jax  # noqa: E402
import numpy as np  # noqa: E402
import pandas as pd  # noqa: E402
import pytest  # noqa: E402


def _drop_compiled_programs():
    import gc
    jax.clear_caches()
    from spark_rapids_tpu.utils import kernelcache
    kernelcache.clear()
    gc.collect()


_TESTS_SINCE_CLEAR = {"n": 0}


@pytest.fixture(autouse=True)
def _clear_jax_caches_periodically():
    """The XLA CPU compiler segfaults deep in compilation after a few
    hundred tests' worth of accumulated executables on this single-core
    box (observed at test ~270 of the full run, q9's join kernel —
    standalone the same test passes; no public JAX issue number known,
    reproducible only at this executable count). Dropping compiled
    programs every 20 tests keeps the compiler healthy — measured
    sufficient on its own: the full 475-test suite passes with ONLY this
    periodic clear (the per-module clear this suite used to carry was
    removed after that measurement)."""
    yield
    _TESTS_SINCE_CLEAR["n"] += 1
    if _TESTS_SINCE_CLEAR["n"] >= 20:
        _TESTS_SINCE_CLEAR["n"] = 0
        _drop_compiled_programs()


@pytest.fixture
def rng():
    return np.random.default_rng(42)


@pytest.fixture
def session():
    from spark_rapids_tpu.session import TpuSparkSession
    s = TpuSparkSession.builder().app_name("test").get_or_create()
    yield s
    s.reset_conf()
