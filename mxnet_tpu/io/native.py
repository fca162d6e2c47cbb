"""ctypes bridge to the native RecordIO reader (src_native/).

The reference's high-throughput IO is C++ (src/io/iter_image_recordio_2.cc
— mmap'd RecordIO chunks + OMP JPEG decode). This module compiles and
loads the TPU-native equivalent, `src_native/recordio_native.cc`:
mmap indexing + threaded libjpeg batch decode into a caller-owned NHWC
uint8 buffer. The library is built on demand with g++ into the
git-ignored `src_native/build/`, named by the hash of its source (a
fresh checkout has arbitrary mtimes, and a stale binary must never be
preferred over the source); when the toolchain or libjpeg is missing,
callers fall back to the portable Python/PIL path in `mxnet_tpu.image`.
"""
from __future__ import annotations

import ctypes
import hashlib
import os
import subprocess

import numpy as onp

_REPO = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
_SRC = os.path.join(_REPO, "src_native", "recordio_native.cc")
_BUILD = os.path.join(_REPO, "src_native", "build")

_lib = None
_load_error = None


def _build_if_needed():
    """Path of the library built from the source as it is now."""
    with open(_SRC, "rb") as f:
        tag = hashlib.sha256(f.read()).hexdigest()[:16]
    so = os.path.join(_BUILD, f"librecordio_native-{tag}.so")
    if os.path.exists(so):
        return so
    os.makedirs(_BUILD, exist_ok=True)
    tmp = f"{so}.{os.getpid()}.tmp"   # concurrent builders never share
    cmd = ["g++", "-O3", "-std=c++17", "-shared", "-fPIC", _SRC,
           "-o", tmp, "-ljpeg", "-pthread"]
    proc = subprocess.run(cmd, capture_output=True, text=True, timeout=120)
    if proc.returncode != 0:
        raise RuntimeError(f"native recordio build failed:\n{proc.stderr}")
    os.replace(tmp, so)
    return so


def get_lib():
    """Load (building if necessary) the native library, or raise."""
    global _lib, _load_error
    if _lib is not None:
        return _lib
    if _load_error is not None:
        raise _load_error
    try:
        lib = ctypes.CDLL(_build_if_needed())
        lib.rio_open.restype = ctypes.c_void_p
        lib.rio_open.argtypes = [ctypes.c_char_p]
        lib.rio_count.restype = ctypes.c_long
        lib.rio_count.argtypes = [ctypes.c_void_p]
        lib.rio_get.restype = ctypes.c_long
        lib.rio_get.argtypes = [ctypes.c_void_p, ctypes.c_long,
                                ctypes.POINTER(ctypes.POINTER(ctypes.c_ubyte))]
        lib.rio_decode_batch.restype = ctypes.c_int
        lib.rio_decode_batch.argtypes = [
            ctypes.c_void_p, ctypes.POINTER(ctypes.c_long), ctypes.c_int,
            ctypes.c_int, ctypes.c_int,
            ctypes.POINTER(ctypes.c_ubyte), ctypes.POINTER(ctypes.c_float),
            ctypes.c_int, ctypes.c_int]
        lib.rio_close.restype = None
        lib.rio_close.argtypes = [ctypes.c_void_p]
        _lib = lib
        return _lib
    except Exception as e:  # noqa: BLE001 — record, callers fall back
        _load_error = RuntimeError(f"native recordio unavailable: {e}")
        raise _load_error


def available():
    try:
        get_lib()
        return True
    except RuntimeError:
        return False


class NativeImageRecordReader:
    """Random-access JPEG RecordIO reader backed by the native lib.

    `read_batch(indices, (h, w))` returns (images NHWC uint8, labels
    (n, label_width) float32) decoded by `nthreads` native threads.
    """

    def __init__(self, path_imgrec, label_width=1, nthreads=None):
        self._lib = get_lib()
        self._h = self._lib.rio_open(path_imgrec.encode())
        if not self._h:
            raise IOError(f"cannot open RecordIO file {path_imgrec!r}")
        self.label_width = label_width
        self.nthreads = nthreads or min(os.cpu_count() or 4, 16)

    def __len__(self):
        return int(self._lib.rio_count(self._h))

    def read_raw(self, i):
        """Zero-copy bytes of record i (IRHeader + payload)."""
        ptr = ctypes.POINTER(ctypes.c_ubyte)()
        n = self._lib.rio_get(self._h, int(i), ctypes.byref(ptr))
        if n < 0:
            raise IndexError(i)
        return bytes(ctypes.cast(
            ptr, ctypes.POINTER(ctypes.c_ubyte * n)).contents)

    def read_batch(self, indices, shape):
        h, w = int(shape[0]), int(shape[1])
        n = len(indices)
        idx = (ctypes.c_long * n)(*[int(i) for i in indices])
        out = onp.empty((n, h, w, 3), dtype=onp.uint8)
        labels = onp.zeros((n, self.label_width), dtype=onp.float32)
        fails = self._lib.rio_decode_batch(
            self._h, idx, n, h, w,
            out.ctypes.data_as(ctypes.POINTER(ctypes.c_ubyte)),
            labels.ctypes.data_as(ctypes.POINTER(ctypes.c_float)),
            self.label_width, self.nthreads)
        if fails:
            raise IOError(f"{fails}/{n} records failed to decode")
        return out, labels

    def close(self):
        if getattr(self, "_h", None):
            self._lib.rio_close(self._h)
            self._h = None

    def __del__(self):
        try:
            self.close()
        except Exception:  # noqa: BLE001 — interpreter teardown
            pass
