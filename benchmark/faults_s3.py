#!/usr/bin/env python3
"""Faults planted underneath an S3 INGEST cell's timed path, and their
entry: one run of the cell with one of them, whose result line must
say "correct": false.  For the builder, on the chip, at the cell's own
size, and for the tests tiny on the CPU; never part of a benchmark
run.

    python3 benchmark/faults_s3.py --fault index_drop \\
        --workload <cell> --seed <n> --seconds <s>

- index_drop          THE CONTROL.  Breaks the `listing` guarantee
                      where the gateway records an object: one in 50
                      index writes is acknowledged without being
                      made.  The object's bytes are on all six shards
                      and the PUT is answered 200; only the listing
                      and the read-back (a GET looks the key up) can
                      see it.
- ack_before_index    breaks the `ack` guarantee: a PUT is answered
                      before its index entry is written — the entry
                      is written when the same connection's next PUT
                      arrives, so the last PUT of every connection is
                      never listed.
- etag_wrong          every 7th index entry records an ETag that is
                      not the md5 of the body: GET and the listing
                      carry it.
- index_replica_skew  breaks the index half of `integrity`: one OSD
                      applies the write that creates an index shard
                      object and acknowledges every later write to it
                      without applying it, so its copies stay behind
                      their peers'.
- parity_flip         faults.py's, unchanged: one byte of the first
                      parity shard of every fused launch is flipped
                      where the device's result reaches the host;
                      only the audit of the stores can see it.
"""

from __future__ import annotations

import os
import sys
import threading

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
sys.path.insert(0, HERE)

import faults  # noqa: E402


def index_drop(every: int = 50) -> None:
    from ceph_tpu.rgw.bucket_index import BucketIndex
    real = BucketIndex.add
    seen = [0]

    def broken(self, bucket, plane, key, meta, route=None, bmeta=None):
        seen[0] += 1
        if seen[0] % every == 0:
            return None
        return real(self, bucket, plane, key, meta, route, bmeta)
    BucketIndex.add = broken


def ack_before_index() -> None:
    from ceph_tpu.rgw.bucket_index import BucketIndex
    real = BucketIndex.add
    late = threading.local()

    def broken(self, *args, **kw):
        owed = getattr(late, "owed", None)
        late.owed = (self, args, kw)
        if owed is not None:
            real(owed[0], *owed[1], **owed[2])
    BucketIndex.add = broken


def etag_wrong() -> None:
    from ceph_tpu.rgw.bucket_index import BucketIndex
    real = BucketIndex.add
    seen = [0]

    def broken(self, bucket, plane, key, meta, route=None, bmeta=None):
        seen[0] += 1
        if seen[0] % 7 == 0 and "etag" in meta:
            meta = dict(meta, etag=meta["etag"][::-1])
        return real(self, bucket, plane, key, meta, route, bmeta)
    BucketIndex.add = broken


def index_replica_skew() -> None:
    from ceph_tpu.store.mem_store import MemStore
    real = MemStore.queue_transactions
    victim, created = [None], set()

    def index_name(op) -> str | None:
        oid = getattr(op, "oid", None)
        if oid is not None and oid.hobj.name.startswith("index."):
            return oid.hobj.name
        return None

    def broken(self, cid, txns):
        txns = list(txns)
        names = {index_name(op) for t in txns for op in t.ops} - {None}
        if names and victim[0] is None:
            victim[0] = self
        if names and victim[0] is self:
            for t in txns:
                t.ops = [op for op in t.ops
                         if index_name(op) not in created]
            created.update(names)
        return real(self, cid, txns)
    MemStore.queue_transactions = broken


FAULTS = {f.__name__: f for f in (
    index_drop, ack_before_index, etag_wrong, index_replica_skew,
    faults.parity_flip)}

if __name__ == "__main__":
    import argparse

    import run
    ap = argparse.ArgumentParser()
    ap.add_argument("--fault", required=True, choices=sorted(FAULTS))
    known, rest = ap.parse_known_args()
    if "--rehearse" in rest:
        os.environ["JAX_PLATFORMS"] = "cpu"
    FAULTS[known.fault]()
    print(f"faults_s3: fault {known.fault} planted", file=sys.stderr)
    # a control that crashes has failed: cli() says so and leaves
    run.cli(rest + ["--trace", "0"])
