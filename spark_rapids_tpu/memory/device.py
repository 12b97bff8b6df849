"""TPU device manager (reference: GpuDeviceManager.scala, 243 LoC).

Responsibilities mapped from the reference:
  * device selection & 1-accelerator-per-process invariant
    (GpuDeviceManager.scala:98-112) -> pick/pin one jax device;
  * RMM pool init with alloc fraction (:152-198) -> an HBM *budget* the
    spill framework enforces (XLA owns the physical allocator; we meter
    framework buffers against conf'd fraction of device memory and spill
    when exceeded — same contract, different mechanism);
  * pinned host pool (:200-206) -> host staging arena (memory/hostpool.py).
"""

from __future__ import annotations

import threading
from typing import Optional

import jax


class TpuDeviceManager:
    _instance: Optional["TpuDeviceManager"] = None
    _lock = threading.Lock()

    def __init__(self, conf):
        self.conf = conf
        devices = jax.devices()
        self.device = devices[0]
        # what the backend resolved to, on record: a run that meant the
        # chip and got XLA:CPU must be able to see that (obs/monitor.py
        # status, benchmarks/run.py, chip_smoke.py)
        self.platform = self.device.platform
        self.device_kind = self.device.device_kind
        self.num_local_devices = len(devices)
        # backend is resolved now: safe point to decide the persistent
        # compile cache (see package __init__)
        from spark_rapids_tpu import configure_compile_cache
        self.compile_cache_dir = configure_compile_cache()
        self.hbm_per_device = {d: self._probe_hbm_bytes(d) for d in devices}
        # the budget meter is global (one number for every device), so it
        # is sized for one chip: the smallest
        self.hbm_total = min(self.hbm_per_device.values())
        self.hbm_budget = int(self.hbm_total * conf.alloc_fraction)
        self._allocated = 0
        self._alloc_lock = threading.Lock()
        self._oom_handlers = []  # callbacks: (needed_bytes) -> freed_bytes
        # per-device residency accounting for mesh execution: committed
        # batches meter against THEIR device, so tests can assert the
        # funnel-free property (no single device's peak ever approaches
        # the whole dataset) through the metering hooks rather than by
        # inspecting internals
        self._per_device: dict = {}
        self._per_device_peak: dict = {}

    @classmethod
    def get(cls, conf) -> "TpuDeviceManager":
        with cls._lock:
            if cls._instance is None:
                cls._instance = cls(conf)
            return cls._instance

    @classmethod
    def current(cls) -> Optional["TpuDeviceManager"]:
        """The live instance, or None before any session exists — lets
        layer-agnostic code meter allocations without creating one."""
        return cls._instance

    @classmethod
    def reset(cls) -> None:
        with cls._lock:
            cls._instance = None

    def meter_batch(self, batch) -> None:
        """Meter a transient engine batch against the HBM budget, freeing
        automatically when the batch is garbage collected (streaming
        batches have no close() discipline of their own; catalog-registered
        buffers are metered by DeviceStore.add_batch instead)."""
        import weakref
        size = batch.device_memory_size()
        if size:
            dev = self._committed_device(batch)
            self.track_alloc(size, device=dev)
            weakref.finalize(batch, self.track_free, size, dev)

    @staticmethod
    def _committed_device(batch):
        """The single device EVERY column of a batch is committed to, or
        None (uncommitted / sharded / split batches meter only globally —
        attributing a split batch to one column's device would undercount
        the others')."""
        dev = None
        try:
            for col in batch.columns:
                # validity, not data: lazy (codes-only) string columns
                # must not materialize chars just to be metered
                devs = col.validity.devices()
                if len(devs) != 1:
                    return None
                d = next(iter(devs))
                if dev is None:
                    dev = d
                elif d != dev:
                    return None
        except Exception:  # pragma: no cover - non-jax columns
            return None
        return dev

    @staticmethod
    def _probe_hbm_bytes(device) -> int:
        stats = device.memory_stats()
        if stats and "bytes_limit" in stats:
            return int(stats["bytes_limit"])
        if device.platform == "tpu":
            raise RuntimeError(
                f"{device} reports no memory_stats()['bytes_limit']; "
                "refusing to assume an HBM size on a TPU")
        # XLA:CPU (the test mesh) reports no memory stats: meter against
        # a nominal 16 GiB per device
        return 16 << 30

    # --- budget accounting (the Rmm pool + event-handler contract,
    # DeviceMemoryEventHandler.scala:37-93) -------------------------------
    def register_oom_handler(self, handler) -> None:
        if handler not in self._oom_handlers:
            self._oom_handlers.append(handler)

    def unregister_oom_handler(self, handler) -> None:
        if handler in self._oom_handlers:
            self._oom_handlers.remove(handler)

    def track_alloc(self, nbytes: int, device=None) -> None:
        """Meter a framework allocation against the HBM budget; drive spill
        handlers synchronously when over budget (the reference spills on
        RMM alloc-failure callbacks, RapidsBufferStore.scala:148-188)."""
        with self._alloc_lock:
            self._allocated += nbytes
            if device is not None:
                cur = self._per_device.get(device, 0) + nbytes
                self._per_device[device] = cur
                if cur > self._per_device_peak.get(device, 0):
                    self._per_device_peak[device] = cur
            over = self._allocated - self.hbm_budget
        if over > 0:
            for h in self._oom_handlers:
                freed = h(over)
                over -= freed
                if over <= 0:
                    break

    def track_free(self, nbytes: int, device=None) -> None:
        with self._alloc_lock:
            self._allocated -= nbytes
            if device is not None and device in self._per_device:
                self._per_device[device] -= nbytes

    def per_device_peaks(self) -> dict:
        """Snapshot of peak metered bytes per device (mesh tests)."""
        with self._alloc_lock:
            return dict(self._per_device_peak)

    def reset_per_device_peaks(self) -> None:
        with self._alloc_lock:
            self._per_device_peak = {d: v for d, v in
                                     self._per_device.items() if v > 0}

    @property
    def allocated(self) -> int:
        return self._allocated
