#!/usr/bin/env python3
"""Faults planted underneath the OSD-restart cell's timed path, and
their entry: one run of the cell with one of them, whose result line
must say "correct": false.  For a run on the chip at the
cell's own size, and for benchmark/tests/test_restart_cell.py tiny on
the CPU; never part of a benchmark run.

    python3 benchmark/faults_restart.py --fault recovery_decode_flip \\
        --workload <cell> --seed <n> --seconds <s>

- recovery_decode_flip  THE CONTROL.  Breaks `integrity` where a
                        recovery decode's result reaches the host: one
                        byte of every shard recovery rebuilt is flipped
                        before it is checked and pushed.  The program
                        checks a rebuilt shard against the crc its
                        hinfo carries and refuses it, so the shard
                        stays missing on the restarted OSD, the PG
                        never turns clean, and the audit says so.
- recovery_skip         the restarted OSD's missing set is dropped:
                        the primaries' recovery leaves every shard it
                        lacks on that OSD alone (breaks `recovery`).
- ack_below_min_size    while a placement group has a shard without a
                        live holder, its primary acknowledges writes
                        that two more live shards never received:
                        k = 8 < min_size = 9 shards committed (breaks
                        `ack`; the look into the live stores at the
                        restart sees it).
"""

from __future__ import annotations

import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)


def recovery_decode_flip() -> None:
    from ceph_tpu.osd.ec_backend import ECBackend
    real = ECBackend._push_recovered

    def broken(self, sts, rebuilt_per_st, push_for):
        flipped = []
        for rebuilt in rebuilt_per_st:
            out = {}
            for shard, data in rebuilt.items():
                data = data.copy()
                data.reshape(-1)[0] ^= 1
                out[shard] = data
            flipped.append(out)
        return real(self, sts, flipped, push_for)
    ECBackend._push_recovered = broken


def recovery_skip() -> None:
    from ceph_tpu.osd.daemon import OSDDaemon
    from ceph_tpu.tools.vstart import Cluster
    revived: set[int] = set()
    real_revive = Cluster.revive_osd

    def revive_osd(self, osd_id):
        revived.add(osd_id)
        return real_revive(self, osd_id)
    Cluster.revive_osd = revive_osd
    real = OSDDaemon._recover_object

    def broken(self, pgid, acting, be, prev_acting, up_osds, oid,
               missing, *args, **kwargs):
        missing = [s for s in missing if acting[s] not in revived]
        if not missing:
            return True
        return real(self, pgid, acting, be, prev_acting, up_osds, oid,
                    missing, *args, **kwargs)
    OSDDaemon._recover_object = broken


def ack_below_min_size() -> None:
    from ceph_tpu.osd.daemon import MessengerShardBackend
    real = MessengerShardBackend.sub_write

    def broken(self, shard, txn, on_commit, log_entries=None,
               at_version=None, rollforward_to=None, trace=None,
               top=None):
        live = [s for s in range(len(self.acting))
                if self._osd_for(s) is not None]
        if log_entries and len(live) < len(self.acting) and \
                shard in live[-2:]:
            on_commit(shard)        # acked, never sent
            return
        return real(self, shard, txn, on_commit, log_entries,
                    at_version, rollforward_to, trace, top)
    MessengerShardBackend.sub_write = broken


FAULTS = {f.__name__: f for f in (recovery_decode_flip, recovery_skip,
                                  ack_below_min_size)}

if __name__ == "__main__":
    import argparse

    import run
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    known, rest = ap.parse_known_args()
    if "--rehearse" in rest:
        os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[known.fault]()
    print(f"faults_restart: fault {known.fault} planted", file=sys.stderr)
    # a control that crashes has failed: cli() says so and leaves
    run.cli(rest + ["--trace", "0"])
