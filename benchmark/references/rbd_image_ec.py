"""Plain reference for an RBD image whose data objects lie on an
erasure-coded pool that accepts overwrites.

Independent of the system under test: it imports nothing of ceph_tpu
and nothing of the other reference; numpy does the field arithmetic
through a 256 x 256 product table built here from the polynomial,
crc32c comes from the `google_crc32c` wheel the container ships.

The semantics it reproduces (upstream doc/rados/operations/
erasure-code.rst "Erasure coding with overwrites", doc/dev/
osd_internals/erasure_coding, src/librbd striping at its defaults):

- the image is one array of `size` bytes; data object N is bytes
  [N * 2^order, (N + 1) * 2^order); a write at an offset replaces
  exactly those bytes, whatever was in flight beside it;
- object N on a k+m pool: zero-padded to whole stripes of k *
  stripe_unit bytes, stripe i gives chunk c = bytes [i*k*su + c*su,
  +su), shard c is its chunks over the stripes, and the m parity
  shards are P = C . D over GF(2^8) (polynomial 0x11d), C[i][j] =
  1 / ((k + i) xor j) (ISA-L gf_gen_cauchy1_matrix) — stripe by
  stripe, so an overwrite of one chunk changes that chunk and the m
  parity chunks of its stripe and nothing else;
- integrity: a shard object that was ever overwritten carries
  `chunk_crc`, the crc32c of ALL its bytes (seed 0xffffffff, no final
  inversion: ceph_crc32c); one that was only ever appended to keeps
  the append-time crcs of all k+m shards in its hinfo.  Both are the
  crc of the shard's whole bytes, so one function gives both.
"""

from __future__ import annotations

import numpy as np

GF_POLY = 0x11D


def _gf_tables() -> tuple[np.ndarray, np.ndarray]:
    exp = np.zeros(512, dtype=np.int32)
    log = np.zeros(256, dtype=np.int32)
    x = 1
    for i in range(255):
        exp[i] = x
        log[x] = i
        x <<= 1
        if x & 0x100:
            x ^= GF_POLY
    exp[255:510] = exp[:255]
    return exp, log


_EXP, _LOG = _gf_tables()


def gf_mul_table() -> np.ndarray:
    """T[a][b] = a * b in GF(2^8)."""
    a = np.arange(256)
    t = _EXP[(_LOG[a][:, None] + _LOG[a][None, :])]
    t[0, :] = 0
    t[:, 0] = 0
    return t.astype(np.uint8)


_MUL = gf_mul_table()


def gf_inv(a: int) -> int:
    if a == 0:
        raise ZeroDivisionError("0 has no inverse in GF(2^8)")
    return int(_EXP[255 - _LOG[a]])


def cauchy_parity_matrix(k: int, m: int) -> np.ndarray:
    """The m x k parity rows of ISA-L's Cauchy generator."""
    return np.array([[gf_inv((k + i) ^ j) for j in range(k)]
                     for i in range(m)], dtype=np.uint8)


def crc32c_ceph(data) -> int:
    """ceph_crc32c(0xffffffff, data): the standard CRC-32C without its
    final inversion."""
    import google_crc32c
    return google_crc32c.value(bytes(data)) ^ 0xFFFFFFFF


def expected_shards(data, k: int, m: int, stripe_unit: int
                    ) -> tuple[np.ndarray, list[int]]:
    """All k+m shards of one object's bytes as they must lie in the
    stores, and the crc32c of each shard's whole bytes."""
    raw = np.frombuffer(bytes(data), dtype=np.uint8) \
        if not isinstance(data, np.ndarray) else data
    width = k * stripe_unit
    padded = -(-raw.size // width) * width
    buf = np.zeros(padded, dtype=np.uint8)
    buf[:raw.size] = raw
    d = np.ascontiguousarray(
        buf.reshape(-1, k, stripe_unit).transpose(1, 0, 2).reshape(k, -1))
    coef = cauchy_parity_matrix(k, m)
    par = np.zeros((m, d.shape[1]), dtype=np.uint8)
    for i in range(m):
        for j in range(k):
            par[i] ^= _MUL[coef[i, j]][d[j]]
    full = np.concatenate([d, par], axis=0)
    return full, [crc32c_ceph(row.tobytes()) for row in full]


class ImageModel:
    """The image as one byte array; `overlay` is a write."""

    def __init__(self, size: int, order: int):
        self.size = size
        self.object_bytes = 1 << order
        self.bytes = np.zeros(size, dtype=np.uint8)
        self.overwritten: set[int] = set()    # objects written twice

    @property
    def objects(self) -> int:
        return -(-self.size // self.object_bytes)

    def fill(self, offset: int, data) -> None:
        """The first write of a range (the prefill): an append."""
        raw = np.frombuffer(data, dtype=np.uint8)
        self.bytes[offset:offset + raw.size] = raw

    def overlay(self, offset: int, data) -> None:
        """An acknowledged overwrite."""
        raw = np.frombuffer(data, dtype=np.uint8)
        self.bytes[offset:offset + raw.size] = raw
        self.overwritten.update(
            range(offset // self.object_bytes,
                  (offset + raw.size - 1) // self.object_bytes + 1))

    def object(self, n: int) -> np.ndarray:
        lo = n * self.object_bytes
        return self.bytes[lo:min(self.size, lo + self.object_bytes)]
