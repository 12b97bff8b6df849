"""Exclusive device discovery tests (reference:
ExclusiveModeGpuDiscoveryPlugin.scala claim-one-device-per-executor)."""

import os

import pytest

from spark_rapids_tpu.memory import discovery

pytestmark = pytest.mark.smoke  # fast cross-section (see pyproject)


@pytest.fixture(autouse=True)
def lock_dir(tmp_path, monkeypatch):
    monkeypatch.setenv("SPARK_RAPIDS_TPU_LOCK_DIR", str(tmp_path))
    return tmp_path


def test_claim_and_release():
    with discovery.discover_and_claim([0, 1]) as claim:
        assert claim.ordinal == 0
        # exclusivity is cross-process (flock); within this process just
        # check the lock file exists and names us
        path = os.path.join(str(os.environ["SPARK_RAPIDS_TPU_LOCK_DIR"]),
                            "device-0.lock")
        assert os.path.exists(path)
        assert open(path).read() == str(os.getpid())


def test_cross_process_exclusion(tmp_path):
    import subprocess
    import sys
    with discovery.discover_and_claim([0]):
        # a second *process* must fail to claim ordinal 0
        code = (
            "import os, sys\n"
            "sys.path.insert(0, '/root/repo')\n"
            "from spark_rapids_tpu.memory import discovery\n"
            "try:\n"
            "    discovery.discover_and_claim([0])\n"
            "    print('CLAIMED')\n"
            "except RuntimeError:\n"
            "    print('BLOCKED')\n")
        env = dict(os.environ)
        out = subprocess.run([sys.executable, "-c", code], env=env,
                             capture_output=True, text=True, timeout=60)
        assert "BLOCKED" in out.stdout, (out.stdout, out.stderr)

    # after release the next process can claim it
    code2 = (
        "import os, sys\n"
        "sys.path.insert(0, '/root/repo')\n"
        "from spark_rapids_tpu.memory import discovery\n"
        "c = discovery.discover_and_claim([0]); print('ORD', c.ordinal)\n")
    out2 = subprocess.run([os.sys.executable, "-c", code2],
                          env=dict(os.environ),
                          capture_output=True, text=True, timeout=60)
    assert "ORD 0" in out2.stdout, (out2.stdout, out2.stderr)


def test_all_claimed_raises():
    import subprocess
    import sys
    import time
    hold = subprocess.Popen(
        [sys.executable, "-c",
         "import sys, time\n"
         "sys.path.insert(0, '/root/repo')\n"
         "from spark_rapids_tpu.memory import discovery\n"
         "c = discovery.discover_and_claim([5])\n"
         "print('HELD', flush=True)\n"
         "time.sleep(30)\n"],
        env=dict(os.environ), stdout=subprocess.PIPE, text=True)
    try:
        assert hold.stdout.readline().strip() == "HELD"
        with pytest.raises(RuntimeError, match="no unclaimed TPU device"):
            discovery.discover_and_claim([5])
    finally:
        hold.kill()


def test_chips_counted_from_the_environment_without_a_backend(monkeypatch):
    """What a launcher may hand out is decided before any backend
    exists: nothing off the TPU platform, and exactly the chips this
    process was itself narrowed to."""
    monkeypatch.setenv("JAX_PLATFORMS", "cpu")
    monkeypatch.setenv("TPU_VISIBLE_CHIPS", "2,3")
    assert discovery.local_chip_ordinals() == []
    monkeypatch.setenv("JAX_PLATFORMS", "tpu,cpu")
    assert discovery.local_chip_ordinals() == [2, 3]
    monkeypatch.delenv("TPU_VISIBLE_CHIPS")
    assert discovery.local_chip_ordinals() == []  # this host has no chip
    env = discovery.one_chip_env(3)
    assert env["TPU_VISIBLE_CHIPS"] == "3"
    assert env["TPU_PROCESS_BOUNDS"] == "1,1,1"
    assert env["TPU_CHIPS_PER_PROCESS_BOUNDS"] == "1,1,1"
