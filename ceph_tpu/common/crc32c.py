"""crc32c (Castagnoli) — the framework's data-plane checksum.

Same conventions as the reference's `bufferlist::crc32c`
(src/include/buffer.h:1199, src/common/crc32c.cc): reflected polynomial
0x82F63B78, caller-supplied seed, no final xor (callers that want the
RFC "crc32c of a message" semantics pass 0xffffffff and invert).

Paths: native SSE4.2/slice-by-8 via ctypes (ceph_tpu.common.native),
numpy table fallback, plus `crc32c_zeros`/`combine` (extend a crc over a
gap without touching memory — the reference's ceph_crc32c_zeros role,
and the host-side half of the TPU fused-crc design: per-tile crcs from
the kernel are folded together with combine).
"""

from __future__ import annotations

import functools

import numpy as np

from . import native

POLY_REFLECTED = 0x82F63B78


@functools.lru_cache(maxsize=1)
def _sw_table() -> np.ndarray:
    t = np.zeros(256, dtype=np.uint32)
    for i in range(256):
        c = i
        for _ in range(8):
            c = (c >> 1) ^ POLY_REFLECTED if c & 1 else c >> 1
        t[i] = c
    return t


def _crc32c_sw(crc: int, data: bytes) -> int:
    t = _sw_table()
    c = np.uint32(crc)
    tl = t
    for b in np.frombuffer(data, dtype=np.uint8):
        c = np.uint32(tl[(c ^ b) & np.uint32(0xFF)] ^ (c >> np.uint32(8)))
    return int(c)


def crc32c(data, crc: int = 0xFFFFFFFF) -> int:
    """crc32c of `data` seeded with `crc` (default matches bufferlist's -1
    convention for standalone checksums).  `data` is bytes or any other
    buffer (a bytearray, a view of a received frame's body, an ndarray),
    which is hashed where it lies, never copied first."""
    crc &= 0xFFFFFFFF
    lib = native.load()
    if isinstance(data, bytes):
        if lib is not None:
            return lib.ceph_tpu_crc32c(crc, data, len(data))
        return _crc32c_sw(crc, data)
    view = memoryview(data)
    if not view.c_contiguous:
        return crc32c(bytes(data), crc)
    arr = np.frombuffer(view, dtype=np.uint8)
    if lib is not None:
        return lib.ceph_tpu_crc32c(crc, arr.ctypes.data, arr.size)
    return _crc32c_sw(crc, arr)


def crc32c_rows(rows: np.ndarray, seeds) -> list[int]:
    """Per-row crc32c of a (R, L) byte matrix, row r seeded seeds[r] —
    the host fold of one encoded run's k+m shard rows in a single pass
    (HashInfo.append and the ECBackend plain-path drain fold).  Native
    path: one C call per row, no intermediate Python structures; table
    fallback: ONE walk over the byte axis updating all R states per
    column (R-wide vectorized, vs R separate byte loops)."""
    rows = np.ascontiguousarray(rows, dtype=np.uint8)
    lib = native.load()
    if lib is not None:
        return [lib.ceph_tpu_crc32c(int(s) & 0xFFFFFFFF,
                                    rows[r].tobytes(), rows.shape[1])
                for r, s in enumerate(seeds)]
    t = _sw_table()
    c = np.array([int(s) & 0xFFFFFFFF for s in seeds], dtype=np.uint32)
    for col in rows.T:
        c = t[(c ^ col) & np.uint32(0xFF)] ^ (c >> np.uint32(8))
    return [int(v) for v in c]


def crc32c_zeros(crc: int, length: int) -> int:
    """Advance `crc` over `length` zero bytes in O(log length)."""
    if length == 0:
        return crc
    lib = native.load()
    if lib is not None:
        return lib.ceph_tpu_crc32c_zeros(crc & 0xFFFFFFFF, length)
    return _zeros_sw(crc & 0xFFFFFFFF, length)


def crc32c_combine(crc_a: int, crc_b: int, len_b: int) -> int:
    """crc of A||B from crc(A) (seeded arbitrarily) and crc(B) (seeded 0)."""
    return crc32c_zeros(crc_a, len_b) ^ crc_b


# -- software combine (GF(2) matrix squaring, zlib-style) -------------------

def _gf2_times(mat: list[int], vec: int) -> int:
    s = 0
    i = 0
    while vec:
        if vec & 1:
            s ^= mat[i]
        vec >>= 1
        i += 1
    return s


def _gf2_square(mat: list[int]) -> list[int]:
    return [_gf2_times(mat, m) for m in mat]


@functools.lru_cache(maxsize=1)
def _byte_matrix() -> tuple[int, ...]:
    odd = [POLY_REFLECTED] + [1 << (i - 1) for i in range(1, 32)]
    even = _gf2_square(odd)    # 2 bits
    odd = _gf2_square(even)    # 4
    even = _gf2_square(odd)    # 8 bits = 1 byte
    return tuple(even)


def _zeros_sw(crc: int, length: int) -> int:
    cur = list(_byte_matrix())
    n = length
    while True:
        if n & 1:
            crc = _gf2_times(cur, crc)
        n >>= 1
        if not n:
            return crc
        cur = _gf2_square(cur)
