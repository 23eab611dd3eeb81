"""The RBD-on-EC overwrite cell: a whole rehearsal run of the harness
(tiny sizes on the CPU, the look for a chip skipped) once sound — with
the program-counter metrics PR 28 added present — and once with each
fault of faults_overwrite.py planted underneath the timed path, which
must read "correct": false; and the new readers on hand-built counter
dumps, the dumps of a program without the counters among them.

    python3 -m pytest benchmark/tests/test_rbd_cell.py -q   (~3 minutes)
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

import faults_overwrite  # noqa: E402
import run  # noqa: E402

CELL = "rbd_ec42_randwrite4k"
NEW_COUNTER_METRICS = {
    "rmw_read_ms_mean", "rmw_read_bytes_per_user_byte",
    "rmw_cache_hit_share", "ec_plain_drain_share",
    "rollback_clone_bytes_per_user_byte",
    "chunk_crc_bytes_per_user_byte"}


def _run(seed, trace=0):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=2.0,
                              trace=trace, rehearse=True)
    return run.run(args)[1]


def test_sound_rehearsal_is_correct_and_reports_the_new_metrics():
    res = _run(2147485001, trace=1)
    assert res["correct"] is True
    assert res["failed"] == 0
    assert all(row["value"] <= row["limit"]
               for row in res["compared"].values())
    checked = res["facts"]["checked"]
    assert checked["audited_shards"] == 6 * checked["audited_objects"]
    assert checked["read_back"] == 2048 and checked["acked"] > 32
    values = {k: v["value"] for k, v in res["metrics"].items()}
    assert NEW_COUNTER_METRICS <= set(values)
    # a traced rehearsal prints no device metric
    assert "kernel_plain_roofline" not in values
    assert values["ec_plain_drain_share"] == 1.0
    assert values["ec_fused_drain_share"] == 0.0
    assert values["ec_probe_remote_share"] == 0.0
    assert values["compiles_in_window"] == 0
    # one 16 KiB stripe read back per 4 KiB write; the rehearsal's
    # shard objects are 16 KiB, and every shard clones and re-hashes
    # its whole object per overwrite: 6 x 16 KiB / 4 KiB.  Ops in
    # flight at a snapshot count on one side only, hence the room
    assert 3.0 <= values["rmw_read_bytes_per_user_byte"] <= 5.0
    assert 18.0 <= values["rollback_clone_bytes_per_user_byte"] <= 30.0
    assert values["chunk_crc_bytes_per_user_byte"] == pytest.approx(
        values["rollback_clone_bytes_per_user_byte"], rel=0.05)
    assert "stored_bytes_per_user_byte" not in values


@pytest.mark.parametrize("fault,numbers", [
    ("plain_parity_flip", {"audit_shard_bytes_wrong",
                           "audit_chunk_crcs_wrong"}),
    ("stale_rmw_read", {"readback_differing"}),
    ("chunk_crc_stale", {"audit_chunk_crcs_wrong"}),
    ("torn_block", {"readback_differing", "blocks_torn"}),
])
def test_fault_is_not_correct(fault, numbers, monkeypatch):
    from ceph_tpu.ec.plugins.ec_jax import ErasureCodeJax
    from ceph_tpu.osd import ec_util
    from ceph_tpu.osd.ec_backend import ECBackend
    from ceph_tpu.rados.client import IoCtx
    # planted by assignment in faults_overwrite.py: put back after
    monkeypatch.setattr(ErasureCodeJax, "encode_chunks_finalize",
                        ErasureCodeJax.encode_chunks_finalize)
    monkeypatch.setattr(ECBackend, "_rmw_read_complete",
                        ECBackend._rmw_read_complete)
    monkeypatch.setattr(ec_util, "refresh_chunk_crcs",
                        ec_util.refresh_chunk_crcs)
    monkeypatch.setattr(IoCtx, "write", IoCtx.write)
    faults_overwrite.FAULTS[fault]()
    res = _run(2147485002)
    assert res["correct"] is False
    failing = {k for k, row in res["compared"].items()
               if row["value"] > row["limit"]}
    assert numbers <= failing
    if fault in ("plain_parity_flip", "chunk_crc_stale"):
        # a healthy read returns data shards only, and no read checks
        # a crc: the read-back alone would have passed
        assert res["compared"]["readback_differing"]["value"] == 0
        assert res["compared"]["blocks_torn"]["value"] == 0
    if fault == "chunk_crc_stale":
        assert res["compared"]["audit_shard_bytes_wrong"]["value"] == 0
    if fault == "stale_rmw_read":
        # whole blocks of zeros are neither the prefill's nor a write
        assert res["compared"]["blocks_torn"]["value"] > 0


# -- the readers on hand-built dumps -----------------------------------------

RMW = run.load_module("metrics", "rmw")
UPKEEP = run.load_module("metrics", "overwrite_upkeep")
PLAIN = run.load_module("metrics", "plain_kernel")


def ctx(before, after, acked=10):
    def snap(t, osd_perf):
        return {"t": t, "osd_perf": osd_perf, "launch_queue": None,
                "compile": {}}
    ops = [(n, 100.0 + n, 101.0 + n, None) for n in range(acked)]
    return {"before": snap(100.0, before), "after": snap(200.0, after),
            "run": {"ops": ops}, "traffic": {"object_bytes": 4096}}


def hist(total, count):
    return {"sum": total, "count": count}


# two OSDs; between the dumps 10 client writes of 4 KiB: 10 pre-reads of
# a 16 KiB stripe in 0.5 s together, 4 KiB of them supplied by the
# extent cache, 10 drains all plain, 6 shards x 1 MiB cloned and hashed
BEFORE = [{"ec.1.0": {"ec_drain_submits": 5, "ec_plain_drains": 0,
                      "ec_rmw_reads": 0, "ec_rmw_read_bytes": 0,
                      "ec_rmw_cache_hit_bytes": 0,
                      "lat_ec_rmw_read": hist(0.0, 0)},
           "osd.0": {"ec_shard_clone_bytes": 0,
                     "ec_shard_chunk_crc_bytes": 0}},
          {"osd.1": {"ec_shard_clone_bytes": 100,
                     "ec_shard_chunk_crc_bytes": 100}}]
AFTER = [{"ec.1.0": {"ec_drain_submits": 15, "ec_plain_drains": 10,
                     "ec_rmw_reads": 10, "ec_rmw_read_bytes": 163840,
                     "ec_rmw_cache_hit_bytes": 4096,
                     "lat_ec_rmw_read": hist(0.5, 10)},
          "osd.0": {"ec_shard_clone_bytes": 40 << 20,
                    "ec_shard_chunk_crc_bytes": 30 << 20}},
         {"osd.1": {"ec_shard_clone_bytes": 100 + (20 << 20),
                    "ec_shard_chunk_crc_bytes": 100 + (30 << 20)}}]


def test_rmw_reader_on_recorded_dumps():
    got = RMW.read(ctx(BEFORE, AFTER))
    assert got == {
        "rmw_read_ms_mean": pytest.approx(50.0),
        "rmw_read_bytes_per_user_byte": pytest.approx(4.0),
        "rmw_cache_hit_share": pytest.approx(0.025),
        "ec_plain_drain_share": pytest.approx(1.0)}
    assert set(got) == set(RMW.METRICS)


def test_upkeep_reader_on_recorded_dumps():
    got = UPKEEP.read(ctx(BEFORE, AFTER))
    assert got == {
        "rollback_clone_bytes_per_user_byte": pytest.approx(1536.0),
        "chunk_crc_bytes_per_user_byte": pytest.approx(1536.0)}
    assert set(got) == set(UPKEEP.METRICS)


def test_readers_give_nothing_for_a_program_without_the_counters():
    """The parent of PR 28 under this PR's benchmark files (every
    cell's traced run): nothing reported, nothing raised."""
    old = [{"ec.1.0": {"ec_drain_submits": 5}, "osd.0": {"op": 3}}]
    new = [{"ec.1.0": {"ec_drain_submits": 15}, "osd.0": {"op": 13}}]
    assert RMW.read(ctx(old, new)) == {}
    assert UPKEEP.read(ctx(old, new)) == {}
    assert UPKEEP.read(ctx(BEFORE, AFTER, acked=0)) == {}


def test_plain_kernel_reader_on_a_recorded_slice():
    """1,000 launches of one 4 KiB-per-shard stripe in the slice: k *
    4096 B in each; the modules took 20 ms together."""
    trace = {"planes": 1, "busy_s": 0.02, "window_s": 5.0,
             "launch_queue_bytes": 1000 * 4 * 4096,
             "families": {"other": {"seconds": 0.021, "launches": 1010}},
             "device_ops": [
                 ["other:jit_gf_bitmatmul_pallas_w32", 0.020],
                 ["other:jit_squeeze", 0.001]]}
    c = {"trace": trace, "rehearsal": False,
         "device": {"kind": "TPU v5 lite"},
         "config": {"pool": {"profile": {"k": "4", "m": "2",
                                         "stripe_unit": "4096"}}}}
    got = PLAIN.read(c)
    moved = 1000 * 6 * 4096                     # k in, m out
    assert got == {
        "kernel_plain_roofline": pytest.approx(
            100.0 * (moved / 819e9) / 0.020),
        "kernel_plain_GBps": pytest.approx(1000 * 4 * 4096 / 0.020 / 1e9)}
    assert got["kernel_plain_roofline"] < 100.0
    assert set(got) == set(PLAIN.METRICS)
    # no plain module in the slice (every other cell), a rehearsal, an
    # untraced run: nothing
    trace["device_ops"] = [["fused_encode:jit__hier_acc_core", 0.02]]
    assert PLAIN.read(c) == {}
    assert PLAIN.read(dict(c, rehearsal=True)) == {}
    assert PLAIN.read(dict(c, trace=None)) == {}
