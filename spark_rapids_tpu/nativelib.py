"""ctypes bindings for the native C++ runtime (native/src/tpu_native.cpp).

The reference framework consumes its native components (RMM pool, pinned
host pool, AddressSpaceAllocator, HashedPriorityQueue, JCudfSerialization)
through JNI; this module is the equivalent seam: the shared library is
built from C++ with `make -C native` (invoked lazily on first use),
loaded over ctypes, and every consumer carries a pure-Python fallback so
a host without a C++ toolchain still works — but says so: a failed
build or load is logged with the compiler's output and kept in
``load_error()``.

Set SPARK_RAPIDS_TPU_DISABLE_NATIVE=1 to force the Python fallbacks.
"""

from __future__ import annotations

import ctypes
import logging
import os
import subprocess
import threading
from typing import Optional

_REPO_ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
_NATIVE_DIR = os.path.join(_REPO_ROOT, "native")
_LIB_PATH = os.path.join(_NATIVE_DIR, "build", "libtpunative.so")

_lib = None
_lib_lock = threading.Lock()
_load_attempted = False
_load_error: Optional[str] = None


def _build() -> Optional[str]:
    """Run the (dependency-tracked) native build; None on success, else
    what went wrong, with make's output."""
    try:
        proc = subprocess.run(["make", "-C", _NATIVE_DIR, "-s"],
                              capture_output=True, text=True, timeout=120)
    except (OSError, subprocess.SubprocessError) as e:
        return f"make -C {_NATIVE_DIR}: {type(e).__name__}: {e}"
    if proc.returncode != 0:
        return (f"make -C {_NATIVE_DIR} exited {proc.returncode}:\n"
                f"{proc.stdout}{proc.stderr}")
    return None


def _declare(lib) -> None:
    c = ctypes
    u64, i64 = c.c_uint64, c.c_int64
    p = c.c_void_p
    u8p = c.POINTER(c.c_uint8)
    # arena
    lib.tpu_arena_create.restype = p
    lib.tpu_arena_create.argtypes = [u64, u64]
    lib.tpu_arena_destroy.argtypes = [p]
    lib.tpu_arena_base.restype = u8p
    lib.tpu_arena_base.argtypes = [p]
    for fn in ("tpu_arena_capacity", "tpu_arena_allocated", "tpu_arena_peak",
               "tpu_arena_largest_free"):
        getattr(lib, fn).restype = u64
        getattr(lib, fn).argtypes = [p]
    lib.tpu_arena_alloc.restype = u64
    lib.tpu_arena_alloc.argtypes = [p, u64]
    lib.tpu_arena_free.restype = u64
    lib.tpu_arena_free.argtypes = [p, u64]
    # hpq
    lib.tpu_hpq_create.restype = p
    lib.tpu_hpq_destroy.argtypes = [p]
    lib.tpu_hpq_size.restype = i64
    lib.tpu_hpq_size.argtypes = [p]
    lib.tpu_hpq_contains.restype = c.c_int
    lib.tpu_hpq_contains.argtypes = [p, i64]
    lib.tpu_hpq_push.restype = c.c_int
    lib.tpu_hpq_push.argtypes = [p, i64, i64]
    lib.tpu_hpq_pop_min.restype = i64
    lib.tpu_hpq_pop_min.argtypes = [p]
    lib.tpu_hpq_peek_min.restype = i64
    lib.tpu_hpq_peek_min.argtypes = [p]
    lib.tpu_hpq_peek_min_priority.restype = i64
    lib.tpu_hpq_peek_min_priority.argtypes = [p]
    lib.tpu_hpq_remove.restype = c.c_int
    lib.tpu_hpq_remove.argtypes = [p, i64]
    # wire
    lib.tpu_pack_bits.argtypes = [u8p, i64, u8p]
    lib.tpu_unpack_bits.argtypes = [u8p, i64, u8p]
    lib.tpu_wire_frame_size.restype = u64
    lib.tpu_wire_frame_size.argtypes = [
        c.c_uint32, c.c_uint32, c.POINTER(c.c_uint16), u8p,
        c.POINTER(u64), c.POINTER(u64)]
    lib.tpu_wire_write_frame.restype = u64
    lib.tpu_wire_write_frame.argtypes = [
        u8p, c.c_uint32, c.c_uint32,
        c.POINTER(u8p), c.POINTER(c.c_uint16),
        c.POINTER(u8p), u8p,
        c.POINTER(u8p), c.POINTER(u64),
        c.POINTER(u8p),
        c.POINTER(u8p), c.POINTER(u64)]


def get_lib():
    """The loaded native library, or None when unavailable/disabled."""
    global _lib, _load_attempted, _load_error
    if _load_attempted:
        return _lib
    with _lib_lock:
        if _load_attempted:
            return _lib
        _load_attempted = True
        if os.environ.get("SPARK_RAPIDS_TPU_DISABLE_NATIVE") == "1":
            return None
        # make is dependency-tracked: a fresh .so is a no-op, a stale one
        # (older sources) is rebuilt so symbol lookups can't go stale
        _load_error = _build()
        if _load_error is None:
            try:
                lib = ctypes.CDLL(_LIB_PATH)
                _declare(lib)
                _lib = lib
            except (OSError, AttributeError) as e:
                _load_error = f"loading {_LIB_PATH}: {e}"
        if _load_error is not None:
            logging.getLogger(__name__).warning(
                "native runtime unavailable, using the Python "
                "fallbacks: %s", _load_error)
    return _lib


def native_available() -> bool:
    return get_lib() is not None


def load_error() -> Optional[str]:
    """Why the native library is absent (build or load failure), or None
    when it loaded, was disabled by the environment, or was not tried."""
    return _load_error


class HostArena:
    """Aligned host memory pool with best-fit sub-allocation — the pinned
    host staging pool (reference: PinnedMemoryPool + AddressSpaceAllocator).
    Falls back to plain bytearray slabs when the native library is absent."""

    def __init__(self, capacity: int, alignment: int = 64):
        self.capacity = capacity
        self.alignment = alignment
        self._lock = threading.Lock()
        self._closed = False
        lib = get_lib()
        self._lib = lib
        self._native = lib is not None
        if self._native:
            self._handle = lib.tpu_arena_create(capacity, alignment)
            if not self._handle:
                raise MemoryError(f"arena of {capacity} bytes failed")
            self._base = lib.tpu_arena_base(self._handle)
        else:
            # fallback slabs allocate lazily, one bytearray per extent —
            # never the full capacity up front (a 1 GiB default limit
            # would otherwise commit 1 GiB of zeros per catalog)
            self._handle = None
            self._fb_slabs: dict = {}   # offset -> bytearray
            self._fb_next = 0
            self._fb_allocated = 0
            self._fb_peak = 0

    def _check_open(self) -> None:
        if self._closed:
            raise ValueError("arena is closed")

    def alloc(self, size: int) -> Optional[int]:
        """Returns an offset, or None when the arena cannot fit ``size``."""
        with self._lock:
            self._check_open()
            if self._native:
                off = self._lib.tpu_arena_alloc(self._handle, size)
                return None if off == (1 << 64) - 1 else off
            need = max(1, (size + self.alignment - 1)
                       & ~(self.alignment - 1))
            if self._fb_allocated + need > self.capacity:
                return None
            off = self._fb_next
            self._fb_next += need
            self._fb_slabs[off] = bytearray(need)
            self._fb_allocated += need
            self._fb_peak = max(self._fb_peak, self._fb_allocated)
            return off

    def free(self, offset: int) -> int:
        with self._lock:
            if self._closed:
                return 0
            if self._native:
                return self._lib.tpu_arena_free(self._handle, offset)
            slab = self._fb_slabs.pop(offset, None)
            if slab is None:
                return 0
            self._fb_allocated -= len(slab)
            return len(slab)

    def view(self, offset: int, size: int):
        """Writable view over an allocated extent."""
        with self._lock:
            self._check_open()
            if self._native:
                addr = ctypes.addressof(self._base.contents) + offset
                return (ctypes.c_uint8 * size).from_address(addr)
            return memoryview(self._fb_slabs[offset])[:size]

    def write(self, offset: int, data: bytes) -> None:
        with self._lock:
            self._check_open()
            if self._native:
                ctypes.memmove(
                    ctypes.addressof(self._base.contents) + offset,
                    data, len(data))
            else:
                self._fb_slabs[offset][:len(data)] = data

    def read(self, offset: int, size: int) -> bytes:
        with self._lock:
            self._check_open()
            if self._native:
                return ctypes.string_at(
                    ctypes.addressof(self._base.contents) + offset, size)
            return bytes(self._fb_slabs[offset][:size])

    @property
    def allocated(self) -> int:
        with self._lock:
            if self._closed:
                return 0
            if self._native:
                return self._lib.tpu_arena_allocated(self._handle)
            return self._fb_allocated

    @property
    def peak(self) -> int:
        with self._lock:
            if self._native and not self._closed:
                return self._lib.tpu_arena_peak(self._handle)
            if not self._native:
                return self._fb_peak
            return 0

    def largest_free(self) -> int:
        with self._lock:
            if self._closed:
                return 0
            if self._native:
                return self._lib.tpu_arena_largest_free(self._handle)
            return self.capacity - self._fb_allocated

    def close(self) -> None:
        with self._lock:
            if self._closed:
                return
            self._closed = True
            if self._native and self._handle is not None:
                self._lib.tpu_arena_destroy(self._handle)
                self._handle = None
            if not self._native:
                self._fb_slabs.clear()

    def __del__(self):  # noqa: D105
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass


class HashedPriorityQueue:
    """O(log n) min-priority queue with O(1) membership, used for spill
    ordering (reference: HashedPriorityQueue.java). Python-heap fallback."""

    def __init__(self):
        lib = get_lib()
        self._lib = lib
        self._lock = threading.Lock()
        if lib is not None:
            self._handle = lib.tpu_hpq_create()
        else:
            self._handle = None
            self._prio = {}

    def push(self, item_id: int, priority: int) -> None:
        with self._lock:
            if self._handle is not None:
                self._lib.tpu_hpq_push(self._handle, item_id, priority)
            else:
                self._prio[item_id] = priority

    def pop_min(self) -> Optional[int]:
        with self._lock:
            if self._handle is not None:
                v = self._lib.tpu_hpq_pop_min(self._handle)
                return None if v == -(1 << 63) else v
            if not self._prio:
                return None
            item = min(self._prio.items(), key=lambda kv: (kv[1], kv[0]))[0]
            del self._prio[item]
            return item

    def peek_min(self) -> Optional[int]:
        with self._lock:
            if self._handle is not None:
                v = self._lib.tpu_hpq_peek_min(self._handle)
                return None if v == -(1 << 63) else v
            if not self._prio:
                return None
            return min(self._prio.items(), key=lambda kv: (kv[1], kv[0]))[0]

    def remove(self, item_id: int) -> bool:
        with self._lock:
            if self._handle is not None:
                return bool(self._lib.tpu_hpq_remove(self._handle, item_id))
            return self._prio.pop(item_id, None) is not None

    def __contains__(self, item_id: int) -> bool:
        with self._lock:
            if self._handle is not None:
                return bool(self._lib.tpu_hpq_contains(self._handle, item_id))
            return item_id in self._prio

    def __len__(self) -> int:
        with self._lock:
            if self._handle is not None:
                return self._lib.tpu_hpq_size(self._handle)
            return len(self._prio)

    def close(self) -> None:
        with self._lock:
            if self._handle is not None:
                self._lib.tpu_hpq_destroy(self._handle)
                self._handle = None

    def __del__(self):  # noqa: D105
        try:
            self.close()
        except Exception:  # noqa: BLE001
            pass
