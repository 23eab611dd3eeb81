"""Plain reference for an erasure-coded pool: Reed-Solomon over
GF(2^8) with ISA-L's Cauchy generator, striped as Ceph stripes an
object, and crc32c per shard.

Independent of the system under test: it imports nothing of ceph_tpu
and takes no table from it.  numpy does the field arithmetic through a
256 x 256 product table built here from the polynomial; crc32c comes
from the `google_crc32c` wheel the container ships (checked against
the standard test vector in selfcheck.py).

What an erasure-coded object looks like in the stores (the semantics
the reference reproduces; upstream doc/dev/osd_internals/
erasure_coding, src/erasure-code/isa/ErasureCodeIsa.cc):

- the object is zero-padded to a multiple of the stripe width
  k * stripe_unit; stripe i gives chunk c = bytes [i*k*su + c*su,
  +su); shard c is the concatenation of its chunks over the stripes;
- the m parity shards are P = C . D over GF(2^8) (polynomial 0x11d),
  C[i][j] = 1 / ((k + i) xor j)  (ISA-L gf_gen_cauchy1_matrix);
- every shard carries the crc32c of all its bytes, seeded with
  0xffffffff and NOT inverted at the end (ceph_crc32c), kept for all
  k+m shards in the `hinfo` attribute of every shard.
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


def stripe(data: bytes, k: int, stripe_unit: int) -> np.ndarray:
    """(k, shard_len) data shards of one object."""
    width = k * stripe_unit
    padded = -(-len(data) // width) * width
    buf = np.zeros(padded, dtype=np.uint8)
    buf[:len(data)] = np.frombuffer(data, dtype=np.uint8)
    return np.ascontiguousarray(
        buf.reshape(-1, k, stripe_unit).transpose(1, 0, 2)
        .reshape(k, -1))


def encode(shards: np.ndarray, m: int) -> np.ndarray:
    """(m, shard_len) parity of (k, shard_len) data shards."""
    k = shards.shape[0]
    coef = cauchy_parity_matrix(k, m)
    out = np.zeros((m, shards.shape[1]), dtype=np.uint8)
    for i in range(m):
        for j in range(k):
            out[i] ^= _MUL[coef[i, j]][shards[j]]
    return out


def crc32c_ceph(data) -> int:
    """ceph_crc32c(0xffffffff, data): the standard CRC-32C without its
    final inversion."""
    import google_crc32c
    return google_crc32c.value(bytes(data)) ^ 0xFFFFFFFF


def expected_shards(data: bytes, k: int, m: int, stripe_unit: int
                    ) -> tuple[np.ndarray, list[int]]:
    """All k+m shards of `data` as they must lie in the stores, and
    the crc each must carry."""
    d = stripe(data, k, stripe_unit)
    full = np.concatenate([d, encode(d, m)], axis=0)
    return full, [crc32c_ceph(row.tobytes()) for row in full]
