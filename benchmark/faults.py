"""Faults planted underneath the timed path, to show that the
comparison that decides `correct` fails when it must.  Used by
control.py (on the chip, at a cell's own size) and by
tests/test_faults.py (on the CPU, tiny).  The benchmark's own runs
never load this file.

- parity_flip  THE CONTROL.  Breaks the configuration's `integrity`
               guarantee where the device's result reaches the host:
               one byte of the first parity shard of every launch run
               is flipped.  A healthy read returns data shards only,
               so the read-back passes; only the audit of the stores
               can see it.
- crc_flip     the device's crc word of the last shard is altered.
- data_flip    an answer altered where it is produced: every 5th
               object reaches the cluster with one byte changed.
- drop_shard   breaks the `ack` guarantee: one OSD acknowledges
               every 3rd shard write without applying it.
"""

from __future__ import annotations

import numpy as np


def _alter_device_result(alter) -> None:
    """Pass every run's (parity, crc words) of a fused launch through
    `alter` where the device's result reaches the host."""
    from ceph_tpu.ops import bitsliced as bs
    real = bs.gf_encode_extents_with_crc_finalize

    def broken(handle):
        out = []
        for parity, l, tail, body in real(handle):
            parity, l = alter(np.array(parity, copy=True),
                              np.array(l, copy=True))
            out.append((parity, l, tail, body))
        return out
    bs.gf_encode_extents_with_crc_finalize = broken


def parity_flip() -> None:
    def alter(parity, l):
        parity[0, 0] ^= 1
        return parity, l
    _alter_device_result(alter)


def crc_flip() -> None:
    def alter(parity, l):
        l[-1] ^= 1
        return parity, l
    _alter_device_result(alter)


def data_flip() -> None:
    from ceph_tpu.rados.client import IoCtx
    real = IoCtx.write_full
    seen = [0]

    def broken(self, name, data):
        seen[0] += 1
        if seen[0] % 5 == 0:
            data = bytearray(data)
            data[len(data) // 2] ^= 1
            data = bytes(data)
        return real(self, name, data)
    IoCtx.write_full = broken


def drop_shard() -> None:
    from ceph_tpu.store import object_store as os_
    from ceph_tpu.store.mem_store import MemStore
    real = MemStore.queue_transactions
    victim, seen = [None], [0]

    def broken(self, cid, txns):
        txns = list(txns)
        writes = any(isinstance(op, os_.OpWrite)
                     and not op.oid.hobj.name.startswith("__")
                     for t in txns for op in t.ops)
        if writes and victim[0] is None:
            victim[0] = self
        if writes and victim[0] is self:
            seen[0] += 1
            if seen[0] % 3 == 0:
                for t in txns:
                    t.ops = [op for op in t.ops
                             if op.oid.hobj.name.startswith("__")]
        return real(self, cid, txns)
    MemStore.queue_transactions = broken


FAULTS = {f.__name__: f for f in (parity_flip, crc_flip, data_flip,
                                  drop_shard)}
