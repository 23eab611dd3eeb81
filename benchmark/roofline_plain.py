"""What a PLAIN erasure-code parity launch costs at the least — the
launch an overwrite makes: parity of k data shards, no crc (an
overwritten object's integrity is the shard-kept `chunk_crc`, computed
on the host).  Counted from the algorithm's shapes and not from any
implementation, like roofline.py's fused work, so that a later kernel
is read against the same work:

- bytes: k * chunk read, m * chunk written, each once, against HBM;
- operations: P = C . D over GF(2^8) as a bit-matrix product, 2 * 8m *
  8k per byte column, against the int8 peak.
"""

from __future__ import annotations


def plain_encode_work(k: int, m: int, chunk_bytes: int) -> dict:
    """Least bytes and operations for the parity of k shards of
    chunk_bytes: k shards in, m shards out, no crcs."""
    return {
        "bytes_in": k * chunk_bytes,
        "bytes_out": m * chunk_bytes,
        "ops": 2 * (8 * m) * (8 * k) * chunk_bytes,
    }
