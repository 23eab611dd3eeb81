"""The degraded RBD-on-EC overwrite cell: a whole rehearsal run of the
harness (tiny sizes on the CPU, the look for a chip skipped) once sound
— with the program-counter metrics PR 32 added present — and once with
the control `decode_flip` and with `write_to_down_shard` planted
underneath the timed path, which must read "correct": false; and the
new readers on hand-built counter dumps, the dumps of a program without
the counters among them.

    python3 -m pytest benchmark/tests/test_degraded_cell.py -q  (~3 minutes)
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

import faults_degraded  # noqa: E402
import run  # noqa: E402

CELL = "rbd_ec42_down1_randwrite4k"
NEW_COUNTER_METRICS = {
    "degraded_pg_share", "rmw_reconstruct_share",
    "rmw_reconstruct_ms_mean", "subwrites_per_op",
    "decode_wait_ms_mean", "lq_decode_runs_per_launch"}


def _run(seed, trace=0):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=2.0,
                              trace=trace, rehearse=True)
    return run.run(args)[1]


def test_sound_rehearsal_is_correct_and_reports_the_new_metrics():
    res = _run(2147496101, trace=1)
    assert res["correct"] is True
    assert res["failed"] == 0
    assert all(row["value"] <= row["limit"]
               for row in res["compared"].values())
    assert {"audit_down_shard_changed", "audit_objects_undecodable",
            "recovered_shard_bytes_wrong",
            "osdmap_epochs_while_degraded"} <= set(res["compared"])
    checked = res["facts"]["checked"]
    # five live shards and the dead one of an object that had a shard
    # on the victim, six of one that had not; all six after recovery
    assert checked["audited_shards"] + checked["audited_down_shards"] \
        == 6 * checked["audited_objects"] == checked["recovered_shards"]
    assert checked["audited_down_shards"] > 0
    assert checked["read_back"] == 2048 and checked["acked"] > 32
    assert checked["recover_s"] > 0
    epochs = res["facts"]["osdmap_epoch"]
    assert epochs[0] == epochs[1]
    values = {k: v["value"] for k, v in res["metrics"].items()}
    assert NEW_COUNTER_METRICS <= set(values)
    # a traced rehearsal prints no device metric
    assert "kernel_bitmatmul_roofline" not in values
    assert "kernel_plain_roofline" not in values
    assert values["ec_plain_drain_share"] == 1.0
    assert values["ec_probe_remote_share"] == 0.0
    assert values["compiles_in_window"] == 0
    # the rehearsal's 8 PGs: those with the victim in their acting set
    assert 0.5 <= values["degraded_pg_share"] <= 1.0
    assert 0.2 <= values["rmw_reconstruct_share"] <= 0.8
    # 5 or 6 shard transactions a write; ops in flight at the first
    # snapshot count on one side only, hence the room below
    assert 4.0 <= values["subwrites_per_op"] <= 6.0
    assert values["lq_decode_runs_per_launch"] >= 1.0
    assert values["decode_wait_ms_mean"] > 0
    assert values["rmw_reconstruct_ms_mean"] \
        >= values["decode_wait_ms_mean"]


@pytest.mark.parametrize("fault,numbers", [
    ("decode_flip", {"readback_differing", "audit_shard_bytes_wrong"}),
    ("write_to_down_shard", {"audit_down_shard_changed"}),
])
def test_fault_is_not_correct(fault, numbers, monkeypatch):
    from ceph_tpu.ec.plugins.ec_jax import ErasureCodeJax
    from ceph_tpu.osd.daemon import MessengerShardBackend
    from ceph_tpu.tools.vstart import Cluster
    # planted by assignment in faults_degraded.py: put back after
    monkeypatch.setattr(ErasureCodeJax, "decode_chunks",
                        ErasureCodeJax.decode_chunks)
    monkeypatch.setattr(MessengerShardBackend, "sub_write",
                        MessengerShardBackend.sub_write)
    monkeypatch.setattr(Cluster, "kill_osd", Cluster.kill_osd)
    faults_degraded.FAULTS[fault]()
    res = _run(2147496102)
    assert res["correct"] is False
    failing = {k for k, row in res["compared"].items()
               if row["value"] > row["limit"]}
    assert numbers <= failing
    if fault == "write_to_down_shard":
        # every live shard, the read-back and the recovery are right:
        # only the look into the dead store sees it
        assert failing == {"audit_down_shard_changed"}


# -- the readers on hand-built dumps -----------------------------------------

DEGRADED = run.load_module("metrics", "degraded_path")
KERNEL = run.load_module("metrics", "bitmatmul_kernel")


def snap(t, osd_perf, queue=None):
    return {"t": t, "osd_perf": osd_perf, "launch_queue": queue,
            "compile": {}}


def ctx(before, after, acked=10, q0=None, q1=None):
    ops = [(n, 100.0 + n, 101.0 + n, None) for n in range(acked)]
    return {"before": snap(100.0, before, q0),
            "after": snap(200.0, after, q1),
            "run": {"ops": ops}, "traffic": {"object_bytes": 4096},
            "config": {"pool": {"pg_num": 4, "profile": {
                "k": "4", "m": "2", "stripe_unit": "4096"}}}}


def hist(total, count):
    return {"sum": total, "count": count}


# two OSDs lead four PGs, three of them with a hole; between the dumps
# 10 client writes: 10 pre-reads, 4 of them reconstructing in 0.6 s
# together of which 0.02 s on the decode ticket, 53 shard transactions
# sent and 7 skipped; 4 decode launches carried 5 submissions
BEFORE = [{"ec.1.0": {"ec_acting_holes": 1, "ec_rmw_reads": 0,
                      "ec_rmw_reconstructs": 0,
                      "ec_sub_writes_sent": 100,
                      "lat_ec_rmw_reconstruct": hist(0.0, 0),
                      "lat_ec_decode_wait": hist(0.0, 0)},
           "ec.1.1": {"ec_acting_holes": 0, "ec_rmw_reads": 2,
                      "ec_rmw_reconstructs": 0,
                      "ec_sub_writes_sent": 12},
           "ec_host_queue": {"ec_host_decode_launches": 1,
                             "ec_host_decode_runs": 1,
                             "ec_host_launch_in_bytes.plain_encode": 0,
                             "ec_host_launch_out_bytes.plain_encode": 0,
                             "ec_host_launch_in_bytes.decode": 0,
                             "ec_host_launch_out_bytes.decode": 0}},
          {"ec.1.2": {"ec_acting_holes": 1, "ec_rmw_reads": 0,
                      "ec_rmw_reconstructs": 0,
                      "ec_sub_writes_sent": 0},
           "ec.1.3": {"ec_acting_holes": 2, "ec_rmw_reads": 0,
                      "ec_rmw_reconstructs": 0,
                      "ec_sub_writes_sent": 0}}]
AFTER = [{"ec.1.0": {"ec_acting_holes": 1, "ec_rmw_reads": 4,
                     "ec_rmw_reconstructs": 3,
                     "ec_sub_writes_sent": 120,
                     "lat_ec_rmw_reconstruct": hist(0.45, 3),
                     "lat_ec_decode_wait": hist(0.015, 3)},
          "ec.1.1": {"ec_acting_holes": 0, "ec_rmw_reads": 5,
                     "ec_rmw_reconstructs": 0,
                     "ec_sub_writes_sent": 30},
          "ec_host_queue": {
              "ec_host_decode_launches": 5, "ec_host_decode_runs": 6,
              "ec_host_launch_in_bytes.plain_encode": 10 * 4 * 4096,
              "ec_host_launch_out_bytes.plain_encode": 10 * 2 * 4096,
              "ec_host_launch_in_bytes.decode": 4 * 4 * 4096,
              "ec_host_launch_out_bytes.decode": 4 * 2 * 4096}},
         {"ec.1.2": {"ec_acting_holes": 1, "ec_rmw_reads": 2,
                     "ec_rmw_reconstructs": 1,
                     "ec_sub_writes_sent": 10,
                     "lat_ec_rmw_reconstruct": hist(0.15, 1),
                     "lat_ec_decode_wait": hist(0.005, 1)},
          "ec.1.3": {"ec_acting_holes": 2, "ec_rmw_reads": 1,
                     "ec_rmw_reconstructs": 0,
                     "ec_sub_writes_sent": 5}}]


def test_degraded_reader_on_recorded_dumps():
    got = DEGRADED.read(ctx(BEFORE, AFTER))
    assert got == {
        "degraded_pg_share": pytest.approx(0.75),
        "rmw_reconstruct_share": pytest.approx(0.4),
        "rmw_reconstruct_ms_mean": pytest.approx(150.0),
        "decode_wait_ms_mean": pytest.approx(5.0),
        "subwrites_per_op": pytest.approx(5.3),
        "lq_decode_runs_per_launch": pytest.approx(1.25)}
    assert set(got) == set(DEGRADED.METRICS)


def test_readers_give_nothing_for_a_program_without_the_counters():
    """The parent of PR 32 under this PR's benchmark files (every
    cell's traced run): nothing reported, nothing raised."""
    old = [{"ec.1.0": {"ec_drain_submits": 5, "ec_rmw_reads": 0},
            "ec_host_queue": {"ec_host_decode_launches": 0}}]
    new = [{"ec.1.0": {"ec_drain_submits": 15, "ec_rmw_reads": 9},
            "ec_host_queue": {"ec_host_decode_launches": 4}}]
    assert DEGRADED.read(ctx(old, new)) == {}
    c = dict(ctx(old, new), rehearsal=False,
             device={"kind": "TPU v5 lite"},
             trace={"planes": 1, "launch_queue_bytes": 4096,
                    "device_ops": [
                        ["other:jit_gf_bitmatmul_pallas_w32", 0.02]]})
    assert KERNEL.read(c) == {}


def test_bitmatmul_kernel_reader_counts_work_per_kind():
    """The window launched 10 plain stripes (k x 4 KiB in, m x 4 KiB
    out) and 4 decodes (4 survivor rows in; the kernel rebuilt 2 rows
    each, the read lacked ONE: the benchmark's rule, not the program's
    count of what it computed, is the work); the queue's own byte
    count is k rows a plain submission and all k+m rows a decode's;
    half of it fell into the traced slice, where the modules took 2 ms
    together."""
    import roofline_bitmatmul
    import roofline_plain
    queued = 10 * 4 * 4096 + 4 * 6 * 4096
    c = dict(ctx(BEFORE, AFTER, q0={"coalesced_bytes": 1000},
                 q1={"coalesced_bytes": 1000 + queued}),
             rehearsal=False, device={"kind": "TPU v5 lite"},
             trace={"planes": 1, "launch_queue_bytes": queued // 2,
                    "device_ops": [
                        ["other:jit_gf_bitmatmul_pallas_w32", 0.002],
                        ["other:jit_squeeze", 0.001]]})
    got = KERNEL.read(c)
    b_in, b_out = 14 * 4 * 4096 // 2, (10 * 2 + 4 * 1) * 4096 // 2
    assert got == {
        "kernel_bitmatmul_roofline": pytest.approx(
            100.0 * ((b_in + b_out) / 819e9) / 0.002),
        "kernel_bitmatmul_GBps": pytest.approx(b_in / 0.002 / 1e9)}
    assert 0 < got["kernel_bitmatmul_roofline"] < 100.0
    assert set(got) == set(KERNEL.METRICS)
    # the per-kind work of a plain launch is roofline_plain's
    assert roofline_bitmatmul.bitmatmul_work(4, 2, 1, 4 * 4096, 0) \
        == roofline_plain.plain_encode_work(4, 2, 4096)
    # what a decode computed beyond the lost row does not raise it
    more = {"ec_host_launch_out_bytes.decode": 4 * 6 * 4096}
    wasteful = [dict(AFTER[0], ec_host_queue=dict(
        AFTER[0]["ec_host_queue"], **more))] + AFTER[1:]
    assert KERNEL.read(dict(c, after=dict(
        c["after"], osd_perf=wasteful))) == got
    # a rehearsal, an untraced run, no such module in the slice
    assert KERNEL.read(dict(c, rehearsal=True)) == {}
    assert KERNEL.read(dict(c, trace=None)) == {}
    c["trace"]["device_ops"] = [["fused_encode:jit__hier_acc_core", 1.0]]
    assert KERNEL.read(c) == {}
