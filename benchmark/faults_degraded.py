#!/usr/bin/env python3
"""Faults planted underneath the DEGRADED overwrite cell's timed path,
and their entry: one run of the cell with one of them, whose result
line must say "correct": false.  For the builder, on the chip, at the
cell's own size, and for tests/test_degraded_cell.py tiny on the CPU;
never part of a benchmark run.

    python3 benchmark/faults_degraded.py --fault decode_flip \\
        --workload <cell> --seed <n> --seconds <s>

- decode_flip          THE CONTROL.  Breaks `read_after_ack` and
                       `integrity` where the device's DECODE result
                       reaches the host: one byte of the first rebuilt
                       shard of every decode launch is flipped.  A
                       degraded read returns it (blocks nobody wrote
                       read back differing), a reconstructing
                       overwrite encodes parity from it (parity bytes
                       wrong in the audit).
- write_to_down_shard  the shard transaction a primary must NOT send
                       (its holder is down) is applied to the dead
                       OSD's store: what the dead store holds is no
                       longer what it held at the kill.
- stale_rmw_read, chunk_crc_stale, torn_block
                       faults_overwrite.py's, unchanged: the overwrite
                       path's own guarantees hold degraded too.
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

from faults_overwrite import (chunk_crc_stale,  # noqa: E402 — after
                              stale_rmw_read, torn_block)   # the path


def decode_flip() -> None:
    import numpy as np
    from ceph_tpu.ec.plugins.ec_jax import ErasureCodeJax
    real = ErasureCodeJax.decode_chunks

    def broken(self, dense, erasures):
        out = np.array(real(self, dense, erasures), copy=True)
        out[min(erasures), 0] ^= 1
        return out
    ErasureCodeJax.decode_chunks = broken


def write_to_down_shard() -> None:
    from ceph_tpu.osd.daemon import MessengerShardBackend
    from ceph_tpu.osd.pg_log import entry_to_wire
    from ceph_tpu.osd.types import eversion_t, spg_t
    from ceph_tpu.tools.vstart import Cluster
    clusters = []
    real_kill = Cluster.kill_osd

    def kill_osd(self, osd_id):
        clusters.append((self, osd_id))
        return real_kill(self, osd_id)
    Cluster.kill_osd = kill_osd
    real = MessengerShardBackend.sub_write

    def broken(self, shard, txn, on_commit, log_entries=None,
               at_version=None, rollforward_to=None, trace=None,
               top=None):
        if self._osd_for(shard) is None and clusters:
            cluster, dead = clusters[-1]
            cluster.osds[dead].apply_sub_write(
                spg_t(self.pgid, shard), txn,
                [entry_to_wire(e) for e in (log_entries or [])],
                at_version or eversion_t(), rollforward_to)
        return real(self, shard, txn, on_commit, log_entries,
                    at_version, rollforward_to, trace, top)
    MessengerShardBackend.sub_write = broken


FAULTS = {f.__name__: f for f in (decode_flip, write_to_down_shard,
                                  stale_rmw_read, chunk_crc_stale,
                                  torn_block)}

if __name__ == "__main__":
    import argparse

    import run
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    known, rest = ap.parse_known_args()
    if "--rehearse" in rest:
        os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[known.fault]()
    print(f"faults_degraded: fault {known.fault} planted",
          file=sys.stderr)
    # a control that crashes has failed: cli() says so and leaves
    run.cli(rest + ["--trace", "0"])
