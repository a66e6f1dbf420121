"""Python side of the shared-memory object store.

``StoreServer`` is the ctypes binding over the native allocator
(src/shm_store.cc) — instantiated only inside the raylet process, which is
the metadata authority for its node (reference: the plasma store runs inside
the raylet process too, src/ray/object_manager/plasma/store_runner.cc).

``StoreMapping`` is the client-side zero-copy view: any process on the node
mmaps the same arena file and reads/writes object bytes directly at offsets
handed out by the raylet over RPC (reference: plasma client protocol,
src/ray/object_manager/plasma/client.h — clients receive fds + offsets and
memcpy into shared memory themselves).
"""

from __future__ import annotations

import ctypes
import glob
import hashlib
import mmap
import os
import subprocess
import threading

from ray_tpu._private import locksan

_LIB_LOCK = locksan.make_lock("shm_store._LIB_LOCK")
_LIB = None

_SRC = os.path.join(os.path.dirname(__file__), "..", "..", "src", "shm_store.cc")


def _build_lib() -> str:
    """Path of the library built from src/shm_store.cc AS IT IS NOW,
    building it if need be.  The file name carries a digest of the
    source, so a library left behind by another source (a copied working
    tree, a checkout whose times say nothing) is never loaded for it."""
    src = os.path.abspath(_SRC)
    with open(src, "rb") as f:
        digest = hashlib.sha256(f.read()).hexdigest()[:16]
    here = os.path.dirname(os.path.abspath(__file__))
    so = os.path.join(here, f"_shm_store.{digest}.so")
    if not os.path.exists(so):
        tmp = so + f".tmp{os.getpid()}"
        subprocess.check_call(
            ["g++", "-O2", "-std=c++17", "-shared", "-fPIC", "-o", tmp, src])
        os.replace(tmp, so)
        for stale in glob.glob(os.path.join(here, "_shm_store*.so")):
            if stale != so:
                os.unlink(stale)  # safe under a process that has it mapped
    return so


def _load_lib():
    global _LIB
    with _LIB_LOCK:
        if _LIB is not None:
            return _LIB
        so = _build_lib()
        lib = ctypes.CDLL(so)
        lib.store_create.restype = ctypes.c_void_p
        lib.store_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64]
        lib.store_destroy.argtypes = [ctypes.c_void_p]
        lib.store_alloc.restype = ctypes.c_int
        lib.store_alloc.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                    ctypes.c_uint64, ctypes.POINTER(ctypes.c_uint64)]
        lib.store_seal.restype = ctypes.c_int
        lib.store_seal.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.store_get.restype = ctypes.c_int
        lib.store_get.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                  ctypes.POINTER(ctypes.c_uint64),
                                  ctypes.POINTER(ctypes.c_uint64),
                                  ctypes.POINTER(ctypes.c_int)]
        lib.store_release.restype = ctypes.c_int
        lib.store_release.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.store_abort.restype = ctypes.c_int
        lib.store_abort.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.store_delete.restype = ctypes.c_int
        lib.store_delete.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.store_contains.restype = ctypes.c_int
        lib.store_contains.argtypes = [ctypes.c_void_p, ctypes.c_char_p]
        lib.store_used.restype = ctypes.c_uint64
        lib.store_used.argtypes = [ctypes.c_void_p]
        lib.store_capacity.restype = ctypes.c_uint64
        lib.store_capacity.argtypes = [ctypes.c_void_p]
        lib.store_evict.restype = ctypes.c_int
        lib.store_evict.argtypes = [ctypes.c_void_p, ctypes.c_uint64]
        lib.store_stats.restype = None
        lib.store_stats.argtypes = [ctypes.c_void_p] + \
            [ctypes.POINTER(ctypes.c_uint64)] * 6
        _LIB = lib
        return lib


class StoreServer:
    """Owns the arena; runs inside the raylet process."""

    def __init__(self, path: str, capacity: int):
        self.lib = _load_lib()
        self.path = path
        self.capacity = capacity
        self.handle = self.lib.store_create(path.encode(), capacity)
        if not self.handle:
            raise RuntimeError(f"failed to create shm store at {path}")

    def alloc(self, object_id: bytes, size: int) -> int | None:
        if not self.handle:  # closed: callers treat as OOM / absent
            return None
        off = ctypes.c_uint64()
        rc = self.lib.store_alloc(self.handle, object_id, size, ctypes.byref(off))
        if rc == 0:
            return off.value
        if rc == -2:
            raise KeyError(f"object {object_id.hex()} already exists")
        return None  # OOM

    def seal(self, object_id: bytes) -> bool:
        if not self.handle:
            return False
        return self.lib.store_seal(self.handle, object_id) == 0

    def get(self, object_id: bytes):
        """Returns (offset, size, sealed) or None; pins when sealed."""
        if not self.handle:
            return None
        off = ctypes.c_uint64()
        size = ctypes.c_uint64()
        sealed = ctypes.c_int()
        rc = self.lib.store_get(self.handle, object_id, ctypes.byref(off),
                                ctypes.byref(size), ctypes.byref(sealed))
        if rc != 0:
            return None
        return off.value, size.value, bool(sealed.value)

    def release(self, object_id: bytes) -> bool:
        if not self.handle:
            return False
        return self.lib.store_release(self.handle, object_id) == 0

    def abort(self, object_id: bytes) -> bool:
        """Drop an UNSEALED creation (creator pin + extent) — the only
        legal way to free an in-progress allocation; release() refuses
        unsealed entries (src/shm_store.cc Release: -3)."""
        if not self.handle:
            return False
        return self.lib.store_abort(self.handle, object_id) == 0

    def delete(self, object_id: bytes) -> bool:
        if not self.handle:
            return False
        return self.lib.store_delete(self.handle, object_id) == 0

    def contains(self, object_id: bytes) -> bool:
        if not self.handle:
            return False
        return self.lib.store_contains(self.handle, object_id) == 1

    def used(self) -> int:
        if not self.handle:
            return 0
        return self.lib.store_used(self.handle)

    def stats(self) -> dict:
        """Fragmentation/pin diagnostics (largest_free is the biggest
        contiguous hole — the real bound on the next large alloc)."""
        if not self.handle:
            return {k: 0 for k in ("used", "largest_free", "lru_bytes",
                                   "pinned_bytes", "unsealed_bytes",
                                   "n_objects")}
        vals = [ctypes.c_uint64() for _ in range(6)]
        self.lib.store_stats(self.handle, *[ctypes.byref(v) for v in vals])
        keys = ("used", "largest_free", "lru_bytes", "pinned_bytes",
                "unsealed_bytes", "n_objects")
        return dict(zip(keys, (v.value for v in vals)))

    def close(self):
        if self.handle:
            self.lib.store_destroy(self.handle)
            self.handle = None
        try:
            os.unlink(self.path)
        except OSError:
            pass


class StoreMapping:
    """Client-side mmap of the node's arena file (zero-copy data plane).

    ``readonly=True`` maps a PEER raylet's arena for the same-host
    zero-copy pull fast path — reads only, the peer stays the metadata
    authority and the reader must hold a remote pin for the duration."""

    def __init__(self, path: str, capacity: int, readonly: bool = False):
        self.path = path
        self.capacity = capacity
        self._fd = os.open(path, os.O_RDONLY if readonly else os.O_RDWR)
        self._mmap = mmap.mmap(
            self._fd, capacity,
            access=mmap.ACCESS_READ if readonly else mmap.ACCESS_WRITE)
        self.view = memoryview(self._mmap)

    def slice(self, offset: int, size: int) -> memoryview:
        return self.view[offset:offset + size]

    def writable(self, offset: int, size: int) -> memoryview:
        """Writable view of an UNSEALED allocation for in-place receive:
        the transfer plane copies socket bytes straight into this view
        (protocol blob frames), relying on the alloc-time creator pin to
        keep the extent stable until seal/abort.  Never hand one out for
        a sealed object — readers may hold zero-copy views of it."""
        return self.view[offset:offset + size]

    def close(self):
        try:
            self.view.release()
            self._mmap.close()
            os.close(self._fd)
        except Exception:
            pass


def default_store_path(session_dir: str, node_id_hex: str) -> str:
    shm = "/dev/shm"
    if os.path.isdir(shm) and os.access(shm, os.W_OK):
        return os.path.join(shm, f"rt_store_{node_id_hex[:12]}_{os.getpid()}")
    return os.path.join(session_dir, f"rt_store_{node_id_hex[:12]}")
