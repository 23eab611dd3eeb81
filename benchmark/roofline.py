"""What an erasure-code launch costs at the least, counted from the
algorithm's shapes and not from any implementation, so that a later
kernel is read against the same work.

Encoding one stripe set of k data shards of `chunk` bytes into m
parity shards and k+m crcs:

- bytes: k * chunk read, m * chunk + 4 * (k + m) written, each once,
  against the chip's HBM bandwidth;
- operations: P = C . D over GF(2^8) done as a bit-matrix product is
  an (8m x 8k) by (8k x 1) product per byte column: 8m * 8k
  multiply-adds = 2 * 64 * m * k operations per column, against the
  chip's int8 peak.  (crc32c is linear too, but its table form needs
  no products; it is counted in the bytes only.)

The roofline time is the larger of the two; `bound` names it.
"""

from __future__ import annotations

import json
import os

_HERE = os.path.dirname(os.path.abspath(__file__))


def peaks(device_kind: str) -> dict:
    with open(os.path.join(_HERE, "peaks.json")) as f:
        table = json.load(f)
    if device_kind not in table:
        raise KeyError(
            f"no published peaks for device_kind {device_kind!r}: add "
            f"it to benchmark/peaks.json with its source")
    return table[device_kind]


def encode_work(k: int, m: int, chunk_bytes: int) -> dict:
    """Least bytes and operations to encode one run (one object's
    stripes): k shards of chunk_bytes in, m shards and k+m crcs out."""
    return {
        "bytes_in": k * chunk_bytes,
        "bytes_out": m * chunk_bytes + 4 * (k + m),
        "ops": 2 * (8 * m) * (8 * k) * chunk_bytes,
    }


def chunk_bytes(object_bytes: int, k: int, stripe_unit: int) -> int:
    """Bytes per shard of an object after padding to whole stripes."""
    width = k * stripe_unit
    return -(-object_bytes // width) * stripe_unit


def roofline_seconds(work: dict, device_kind: str) -> tuple[float, str]:
    """(least seconds, which bound) for `work` on one chip."""
    p = peaks(device_kind)
    t_mem = (work["bytes_in"] + work["bytes_out"]) / p["hbm_bytes_per_s"]
    t_ops = work["ops"] / p["int8_ops_per_s"]
    return (t_mem, "hbm") if t_mem >= t_ops else (t_ops, "int8")
