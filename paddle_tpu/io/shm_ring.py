"""ctypes binding for the native shm ring (csrc/shm_ring/shm_ring.cc).

Reference analog: shared-memory tensor transport between DataLoader worker
processes and the trainer (`fluid/memory/allocation/mmap_allocator.cc`,
`fluid/dataloader/worker.py`)."""
from __future__ import annotations

import ctypes
import os

_LIB = None


def _lib():
    global _LIB
    if _LIB is not None:
        return _LIB
    # NO package imports here: this module is loaded standalone inside
    # JAX-free DataLoader worker children (see _worker.py) — pulling in
    # paddle_tpu.sysconfig would import the whole package and JAX with it.
    # Build-on-demand mirrors sysconfig.ensure_native_built (make decides
    # what is stale), incl. the flock guard against concurrent builds.
    here = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
    lib_dir = os.path.join(here, "lib")
    so = os.path.join(lib_dir, "libshmring.so")
    src = os.path.join(os.path.dirname(here), "csrc")
    if os.path.exists(os.path.join(src, "Makefile")):
        import subprocess

        os.makedirs(lib_dir, exist_ok=True)
        with open(os.path.join(lib_dir, ".build.lock"), "w") as lock:
            try:
                import fcntl

                fcntl.flock(lock, fcntl.LOCK_EX)
            except ImportError:
                pass
            subprocess.run(
                ["make", "-C", src, "../paddle_tpu/lib/libshmring.so"],
                check=True, capture_output=True)
    lib = ctypes.CDLL(so)
    lib.ptshm_create.restype = ctypes.c_void_p
    lib.ptshm_create.argtypes = [ctypes.c_char_p, ctypes.c_uint64,
                                 ctypes.c_uint64]
    lib.ptshm_open.restype = ctypes.c_void_p
    lib.ptshm_open.argtypes = [ctypes.c_char_p]
    lib.ptshm_write.restype = ctypes.c_int
    lib.ptshm_write.argtypes = [ctypes.c_void_p, ctypes.c_char_p,
                                ctypes.c_uint64, ctypes.c_uint64]
    lib.ptshm_read.restype = ctypes.c_int64
    lib.ptshm_read.argtypes = [ctypes.c_void_p, ctypes.c_void_p,
                               ctypes.c_uint64,
                               ctypes.POINTER(ctypes.c_uint64),
                               ctypes.c_int64]
    lib.ptshm_slot_size.restype = ctypes.c_uint64
    lib.ptshm_slot_size.argtypes = [ctypes.c_void_p]
    lib.ptshm_close.argtypes = [ctypes.c_void_p]
    _LIB = lib
    return lib


class ShmRing:
    """Multi-producer / single-consumer shared-memory message ring."""

    def __init__(self, name: str, n_slots=8, slot_size=32 << 20,
                 create=True):
        self._libref = _lib()
        self.name = name.encode()
        if create:
            self._h = self._libref.ptshm_create(self.name, n_slots,
                                                slot_size)
        else:
            self._h = self._libref.ptshm_open(self.name)
        if not self._h:
            raise OSError(f"shm ring {'create' if create else 'open'} "
                          f"failed for {name}")
        self.slot_size = self._libref.ptshm_slot_size(self._h)
        # single consumer → one reusable read buffer (avoids a 32MB calloc
        # per batch on the hot input path)
        self._read_buf = None

    def write(self, payload: bytes, tag: int = 0):
        rc = self._libref.ptshm_write(self._h, payload, len(payload), tag)
        if rc == -1:
            raise ValueError(
                f"payload of {len(payload)} bytes exceeds slot size "
                f"{self.slot_size}; raise DataLoader slot_size")
        return rc

    def read(self, timeout_ms: int = -1):
        """Returns (payload bytes, tag) or None on timeout."""
        if self._read_buf is None:
            self._read_buf = ctypes.create_string_buffer(int(self.slot_size))
        buf = self._read_buf
        tag = ctypes.c_uint64(0)
        n = self._libref.ptshm_read(self._h, buf, self.slot_size,
                                    ctypes.byref(tag), timeout_ms)
        if n == -2:
            return None
        if n < 0:
            raise OSError(f"shm ring read failed (rc={n})")
        return ctypes.string_at(buf, int(n)), int(tag.value)

    def close(self):
        if self._h:
            self._libref.ptshm_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:
            pass
