"""What the launches of the `gf_bitmatmul` kernel cost at the least
when one window mixes its two uses — the PLAIN parity launch of an
overwrite and the DECODE launch of a degraded pre-read, one XLA module
with two matrices.  The rule is this file's, counted from the
algorithm's shapes and not from any implementation: the program says
only how wide its launches of each kind were (the rows it handed to
the kernel, `ec_host_launch_in_bytes.<kind>`), and what a launch of
that width NEEDS is decided here:

- plain: k data rows in, m parity rows out
  (roofline_plain.plain_encode_work);
- decode: the k SURVIVOR rows in, and out only the rows the READ
  lacked — one for each data shard whose holder is down.  The rows a
  kernel computes beyond that (today the parity shard the pre-read
  never asked for: `decode_chunks` rebuilds every erased shard) are
  waste, not least work: a program that stops computing them reads
  HIGHER against this work, not lower;
- either way R = M . S over GF(2^8) as a bit-matrix product is
  (8 r x 8 k) by (8 k x 1) per byte column: 2 * 8 r * 8 k operations,
  r the rows out.
"""

from __future__ import annotations

from roofline_plain import plain_encode_work


def decode_work(k: int, lost: int, width_bytes: int) -> dict:
    """Least bytes and operations to give a read the `lost` data rows
    it lacks from k surviving rows of width_bytes."""
    return {"bytes_in": k * width_bytes,
            "bytes_out": lost * width_bytes,
            "ops": 2 * (8 * lost) * (8 * k) * width_bytes}


def bitmatmul_work(k: int, m: int, lost: int, plain_in_bytes: float,
                   decode_in_bytes: float) -> dict:
    """The two kinds added up, each from the bytes of the k rows its
    launches carried in (a launch's width is that over k)."""
    plain = plain_encode_work(k, m, plain_in_bytes / k)
    decode = decode_work(k, lost, decode_in_bytes / k)
    return {key: plain[key] + decode[key] for key in plain}
