"""ctypes loader for the native data-plane library (native/).

The first load in every process runs `make -C native`, so the library
is always built from the .c files the checkout holds and `make` alone
decides staleness (a no-op when it is up to date).  Every entry point
keeps a pure-python fallback so the framework still runs on a host
without a C toolchain — but that fallback is a per-byte loop (a 4 MiB
frame takes seconds), so a failed build is LOUD: it warns with the
compiler's output, `build_error()` keeps it, and chip_smoke.py fails
on `available() == False`.  The native path is also what makes the CPU
baseline honest (reference analog: crc32c_intel_fast + ISA-L/
gf-complete SIMD kernels vs their table fallbacks).
"""

from __future__ import annotations

import ctypes
import fcntl
import subprocess
import threading
import warnings
from pathlib import Path

_NATIVE_DIR = Path(__file__).resolve().parent.parent.parent / "native"
_LIB_PATH = _NATIVE_DIR / "libceph_tpu_native.so"

_lock = threading.Lock()
_lib: ctypes.CDLL | None = None
_tried = False
_build_error: str | None = None


def _build() -> None:
    """`make -C native` under an exclusive file lock: ProcCluster boots
    many daemon processes at once, and two makes linking the same .so
    would hand a third a half-written library."""
    with open(_NATIVE_DIR / "Makefile") as lockf:
        fcntl.flock(lockf, fcntl.LOCK_EX)
        subprocess.run(["make", "-C", str(_NATIVE_DIR), "-s"],
                       check=True, capture_output=True, text=True,
                       timeout=120)


def load() -> ctypes.CDLL | None:
    """Build (when stale) and load the native library; None — after a
    loud warning — if it cannot be built or loaded."""
    global _lib, _tried, _build_error
    with _lock:
        if _lib is not None or _tried:
            return _lib
        _tried = True
        try:
            _build()
            lib = ctypes.CDLL(str(_LIB_PATH))
        except (OSError, subprocess.SubprocessError) as e:
            _build_error = f"{e!r}: {getattr(e, 'stderr', '') or ''}"
            warnings.warn(
                f"native library unavailable, using the slow "
                f"pure-python crc/GF paths: {_build_error}",
                RuntimeWarning, stacklevel=2)
            return None
        lib.ceph_tpu_crc32c.restype = ctypes.c_uint32
        lib.ceph_tpu_crc32c.argtypes = [
            ctypes.c_uint32, ctypes.c_void_p, ctypes.c_size_t]
        lib.ceph_tpu_crc32c_zeros.restype = ctypes.c_uint32
        lib.ceph_tpu_crc32c_zeros.argtypes = [ctypes.c_uint32, ctypes.c_uint64]
        lib.ceph_tpu_crc32c_combine.restype = ctypes.c_uint32
        lib.ceph_tpu_crc32c_combine.argtypes = [
            ctypes.c_uint32, ctypes.c_uint32, ctypes.c_uint64]
        lib.gf8_init.restype = None
        lib.gf8_mul_region_xor.restype = None
        lib.gf8_mul_region_xor.argtypes = [
            ctypes.c_uint8, ctypes.c_void_p, ctypes.c_void_p, ctypes.c_size_t]
        lib.gf8_encode.restype = None
        lib.gf8_encode.argtypes = [
            ctypes.c_int, ctypes.c_int, ctypes.c_void_p,
            ctypes.POINTER(ctypes.c_void_p), ctypes.POINTER(ctypes.c_void_p),
            ctypes.c_size_t]
        lib.gf8_init()
        _lib = lib
        return _lib


def available() -> bool:
    return load() is not None


def build_error() -> str | None:
    """Why load() returned None (make's stderr included)."""
    return _build_error


def gf8_matvec(mat, chunks):
    """Native GF(2^8) matrix x chunks product: (r, k) x (k, n) -> (r, n).

    Returns None when the native library is unavailable (caller falls
    back to the numpy LUT path).
    """
    import numpy as np
    lib = load()
    if lib is None:
        return None
    mat = np.ascontiguousarray(mat, dtype=np.uint8)
    chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
    r, k = mat.shape
    n = chunks.shape[1]
    out = np.empty((r, n), dtype=np.uint8)
    data_ptrs = (ctypes.c_void_p * k)(
        *[chunks[j].ctypes.data for j in range(k)])
    par_ptrs = (ctypes.c_void_p * r)(
        *[out[i].ctypes.data for i in range(r)])
    lib.gf8_encode(k, r, mat.ctypes.data, data_ptrs, par_ptrs, n)
    return out
