#!/usr/bin/env python3
"""Faults planted underneath an OVERWRITE cell's timed path, and their
entry: one run of the cell with one of them, whose result line must
say "correct": false.  For the builder, on the chip, at the cell's own
size, and for tests/test_rbd_cell.py tiny on the CPU; never part of a
benchmark run.  (faults.py's four break the fused append path, which
an overwrite's window never takes.)

    python3 benchmark/faults_overwrite.py --fault plain_parity_flip \\
        --workload <cell> --seed <n> --seconds <s>

- plain_parity_flip  THE CONTROL.  Breaks the `integrity` guarantee
                     where the device's result reaches the host: one
                     byte of the first parity shard of every plain
                     launch is flipped.  A healthy read returns data
                     shards only, so the read-back passes; only the
                     audit of the stores can see it.
- stale_rmw_read     every 5th pre-read of a stripe is answered with
                     zeros: the bytes around the write are lost, the
                     read-back must see it.
- chunk_crc_stale    shard 1 skips its chunk_crc upkeep: its bytes are
                     right and the crc it carries is an older one (or
                     none).
- torn_block         breaks `atomicity`: every 7th data write lands
                     half and is acknowledged whole.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def plain_parity_flip() -> None:
    import numpy as np
    from ceph_tpu.ec.plugins.ec_jax import ErasureCodeJax
    real = ErasureCodeJax.encode_chunks_finalize

    def broken(self, handle):
        parity = np.array(real(self, handle), copy=True)
        parity[0, 0] ^= 1
        return parity
    ErasureCodeJax.encode_chunks_finalize = broken


def stale_rmw_read() -> None:
    import numpy as np
    from ceph_tpu.osd.ec_backend import ECBackend
    real = ECBackend._rmw_read_complete
    seen = [0]

    def broken(self, op, oid, e, logical):
        seen[0] += 1
        if seen[0] % 5 == 0:
            logical = np.zeros_like(logical)
        return real(self, op, oid, e, logical)
    ECBackend._rmw_read_complete = broken


def chunk_crc_stale() -> None:
    from ceph_tpu.osd import ec_util
    real = ec_util.refresh_chunk_crcs

    def broken(store, cid, shard, entries, spans_on=False):
        if shard == 1:
            return 0
        return real(store, cid, shard, entries, spans_on)
    ec_util.refresh_chunk_crcs = broken


def torn_block() -> None:
    from ceph_tpu.rados.client import IoCtx
    real = IoCtx.write
    seen = [0]

    def broken(self, name, data, offset=0):
        if name.startswith("rbd_data.") and len(data) <= 8192:
            seen[0] += 1
            if seen[0] % 7 == 0:
                data = bytes(data)[:len(data) // 2]
        return real(self, name, data, offset)
    IoCtx.write = broken


FAULTS = {f.__name__: f for f in (plain_parity_flip, stale_rmw_read,
                                  chunk_crc_stale, torn_block)}

if __name__ == "__main__":
    import argparse

    import run
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    known, rest = ap.parse_known_args()
    if "--rehearse" in rest:
        os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[known.fault]()
    print(f"faults_overwrite: fault {known.fault} planted",
          file=sys.stderr)
    # a control that crashes has failed: cli() says so and leaves
    run.cli(rest + ["--trace", "0"])
