"""Exclusive-mode device discovery.

The reference ships a Spark ``ResourceDiscoveryPlugin`` that probes GPUs
and claims one per executor in PROCESS_EXCLUSIVE mode so co-located
executors never share a device
(sql-plugin/.../ExclusiveModeGpuDiscoveryPlugin.scala:42+ probing via
setGpuDeviceAndAcquire, GpuDeviceManager.scala:72-96). The TPU analogue:
enumerate the chips of this host and claim one with an exclusive OS file
lock — two executor processes racing for the same chip resolve through
``flock``, exactly the role CUDA's exclusive-process compute mode plays
in the reference.

A chip belongs to one process: the first ``jax.devices()`` in a process
takes every chip it can see, and a second process then fails to
initialise. So everything here runs BEFORE backend initialisation —
chips are counted from PCI, and a process is narrowed to its one chip
through environment variables libtpu reads at start-up
(``one_chip_env``), set by whoever launches it.
"""

from __future__ import annotations

import glob
import os
import tempfile
from typing import Dict, List, Optional


class DeviceClaim:
    """A held exclusive claim on one local device ordinal."""

    def __init__(self, ordinal: int, lock_path: str, lock_fd: int):
        self.ordinal = ordinal
        self._lock_path = lock_path
        self._lock_fd = lock_fd

    def release(self) -> None:
        if self._lock_fd is not None:
            try:
                os.close(self._lock_fd)
            except OSError:
                pass
            self._lock_fd = None

    def __enter__(self):
        return self

    def __exit__(self, *exc):
        self.release()


def _lock_dir() -> str:
    d = os.environ.get("SPARK_RAPIDS_TPU_LOCK_DIR",
                       os.path.join(tempfile.gettempdir(),
                                    "spark-rapids-tpu-locks"))
    os.makedirs(d, exist_ok=True)
    return d


def _try_claim(ordinal: int) -> Optional[DeviceClaim]:
    import fcntl
    path = os.path.join(_lock_dir(), f"device-{ordinal}.lock")
    fd = os.open(path, os.O_CREAT | os.O_RDWR, 0o644)
    try:
        fcntl.flock(fd, fcntl.LOCK_EX | fcntl.LOCK_NB)
    except OSError:
        os.close(fd)
        return None
    os.ftruncate(fd, 0)
    os.write(fd, str(os.getpid()).encode())
    return DeviceClaim(ordinal, path, fd)


def local_chip_ordinals() -> List[int]:
    """Ordinals of the TPU chips this process may use or hand to
    children, learned without initialising a backend. The PCI scan jax
    itself uses says how many chips the host has; the device nodes
    (``/dev/accel*``, or ``/dev/vfio/<group>`` from v5e on) say how many
    this process can open — a sandbox may be given fewer than the host
    holds — so the count is the smaller. TPU_VISIBLE_CHIPS wins when this
    process was itself narrowed. Empty when JAX_PLATFORMS keeps the
    process off the TPU or there is no chip."""
    platforms = os.environ.get("JAX_PLATFORMS", "")
    if platforms and "tpu" not in platforms.split(","):
        return []
    visible = os.environ.get("TPU_VISIBLE_CHIPS", "")
    if visible:
        return [int(c) for c in visible.split(",")]
    from jax._src import hardware_utils
    on_pci, _ = hardware_utils.num_available_tpu_chips_and_device_id()
    nodes = (glob.glob("/dev/accel[0-9]*")
             or glob.glob("/dev/vfio/[0-9]*"))
    return list(range(min(on_pci, len(nodes))))


def one_chip_env(ordinal: int) -> Dict[str, str]:
    """Environment that narrows a child process to chip ``ordinal`` as a
    one-chip topology of its own (the variables jax's multi-process test
    launcher sets, jax/_src/test_multiprocess.py). Must be in the
    child's environment before it imports jax."""
    port = 8476 + ordinal
    return {
        "TPU_VISIBLE_CHIPS": str(ordinal),
        "TPU_CHIPS_PER_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_BOUNDS": "1,1,1",
        "TPU_PROCESS_ADDRESSES": f"localhost:{port}",
        "TPU_PROCESS_PORT": str(port),
        "CLOUD_TPU_TASK_ID": "0",
        "ALLOW_MULTIPLE_LIBTPU_LOAD": "1",
    }


def discover_and_claim(ordinals: Optional[List[int]] = None) -> DeviceClaim:
    """Claim the first unclaimed local device; raises if every device is
    held by another process (the reference's executor init likewise fails
    fast rather than oversubscribing, Plugin.scala:129-136)."""
    if ordinals is None:
        ordinals = local_chip_ordinals()
    for o in ordinals:
        claim = _try_claim(o)
        if claim is not None:
            return claim
    raise RuntimeError(
        f"no unclaimed TPU device among ordinals {ordinals}; every device "
        "is exclusively held by another executor process")
