"""The comparison that decides `correct`, shown to fail: drives a
whole run of the harness (the look for a chip skipped: --rehearse,
tiny sizes on the CPU) once sound and once with each fault of
faults.py planted underneath the timed path.

    python3 -m pytest benchmark/tests -q        (about 2 minutes)
"""

import argparse
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, os.path.dirname(BENCH))
sys.path.insert(0, BENCH)
os.environ["JAX_PLATFORMS"] = "cpu"

import faults  # noqa: E402
import run  # noqa: E402

CELL = "ec21_write4k"


def _run(seed):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=2.0,
                              trace=0, rehearse=True)
    return run.run(args)[1]


def test_sound_run_is_correct():
    res = _run(2147483999)
    assert res["correct"] is True
    assert res["failed"] == 0
    assert all(row["value"] <= row["limit"]
               for row in res["compared"].values())
    assert res["facts"]["checked"]["audited_shards"] > 0


@pytest.mark.parametrize("fault,number", [
    ("parity_flip", "audit_shard_bytes_wrong"),
    ("crc_flip", "audit_shard_crcs_wrong"),
    ("data_flip", "readback_differing"),
    ("drop_shard", None),
])
def test_fault_is_not_correct(fault, number, monkeypatch):
    from ceph_tpu.ops import bitsliced as bs
    from ceph_tpu.rados.client import IoCtx
    from ceph_tpu.store.mem_store import MemStore
    # planted by assignment in faults.py: put back after the test
    monkeypatch.setattr(bs, "gf_encode_extents_with_crc_finalize",
                        bs.gf_encode_extents_with_crc_finalize)
    monkeypatch.setattr(IoCtx, "write_full", IoCtx.write_full)
    monkeypatch.setattr(MemStore, "queue_transactions",
                        MemStore.queue_transactions)
    faults.FAULTS[fault]()
    res = _run(2147484000)
    assert res["correct"] is False
    failing = [k for k, row in res["compared"].items()
               if row["value"] > row["limit"]]
    assert failing
    if number is not None:
        assert number in failing
    if fault == "parity_flip":
        # a healthy read returns data shards only: the read-back
        # alone would have passed
        assert res["compared"]["readback_differing"]["value"] == 0
