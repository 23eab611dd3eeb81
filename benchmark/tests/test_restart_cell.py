"""The OSD-restart cell: a whole rehearsal run of the harness (tiny
sizes on the CPU, the look for a chip skipped) once sound — with the
recovery metrics read from the program's counters and spans — and
once with the control `recovery_decode_flip` and with each fault of
benchmark/faults_restart.py planted underneath the timed path, which
must read "correct": false; and the recovery reader on hand-built
dumps, those of a program without the counters among them.

    python3 -m pytest benchmark/tests/test_restart_cell.py -q  (~6 minutes)
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

import faults_restart  # noqa: E402
import run  # noqa: E402

CELL = "ec83_restart1_write4m"
RECOVERY_METRICS = {
    "recover_s", "recovery_shard_MBps", "recovery_scanned_per_recovered",
    "recover_reads_ms_mean", "recover_decode_wait_ms_mean",
    "recover_push_ms_mean", "peering_ms_mean"}


def _run(seed, trace=0, seconds=6.0):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=seconds,
                              trace=trace, rehearse=True)
    return run.run(args)[1]


def test_sound_rehearsal_is_correct_and_reports_recovery():
    res = _run(2147541001, trace=1)
    failing = {k: row for k, row in res["compared"].items()
               if row["value"] > row["limit"]}
    assert res["correct"] is True, failing
    assert res["failed"] == 0
    checked = res["facts"]["checked"]
    assert checked["acked_while_down_on_victim"] > 0
    assert checked["audited_from_down"] > 0
    assert checked["audited_shards"] == 11 * checked["audited_objects"]
    assert checked["read_back"] == checked["acked"]
    assert 0.9 < checked["stop_at_s"] < 1.5
    assert 2.9 < checked["restart_at_s"] < 3.5
    epochs = res["facts"]["osdmap_epoch"]
    assert epochs[1] >= epochs[0] + 2        # down, then up again
    values = {k: v["value"] for k, v in res["metrics"].items()}
    assert RECOVERY_METRICS <= set(values)
    assert values["compiles_in_window"] == 0
    assert 0 < values["recover_s"] < 60
    assert values["recovery_scanned_per_recovered"] >= 1.0
    for name in ("recover_reads_ms_mean", "recover_decode_wait_ms_mean",
                 "recover_push_ms_mean", "peering_ms_mean"):
        assert values[name] > 0


@pytest.mark.parametrize("fault,numbers", [
    ("recovery_decode_flip", {"active_clean_timeout",
                              "restarted_osd_shards_missing",
                              "audit_shards_missing"}),
    ("recovery_skip", {"restarted_osd_shards_missing",
                       "audit_shards_missing"}),
    ("ack_below_min_size", {"acked_while_down_live_shards_missing"}),
])
def test_fault_is_not_correct(fault, numbers, monkeypatch):
    from ceph_tpu.osd.daemon import MessengerShardBackend, OSDDaemon
    from ceph_tpu.osd.ec_backend import ECBackend
    from ceph_tpu.tools.vstart import Cluster
    # planted by assignment in faults_restart.py: put back after
    monkeypatch.setattr(ECBackend, "_push_recovered",
                        ECBackend._push_recovered)
    monkeypatch.setattr(OSDDaemon, "_recover_object",
                        OSDDaemon._recover_object)
    monkeypatch.setattr(MessengerShardBackend, "sub_write",
                        MessengerShardBackend.sub_write)
    monkeypatch.setattr(Cluster, "revive_osd", Cluster.revive_osd)
    faults_restart.FAULTS[fault]()
    res = _run(2147541002)
    assert res["correct"] is False
    failing = {k for k, row in res["compared"].items()
               if row["value"] > row["limit"]}
    assert numbers <= failing, failing


# -- the reader on hand-built dumps ------------------------------------------

RECOVERY = run.load_module("metrics", "recovery")


def hist(total, count):
    return {"sum": total, "count": count}


def dump(osd, scanned, recovered, peer, pushed, boot=0.0, clean=0.0,
         spans=None):
    out = {f"osd.{osd}": {"recovery_pushed_bytes": pushed},
           f"pg_ledger.osd.{osd}": {
               "pg_objects_scanned": scanned,
               "pg_objects_recovered": recovered,
               "lat_peering_total": peer,
               "osd_boot_at": boot, "pg_last_clean_at": clean}}
    if spans is not None:
        out["host_spans"] = spans
    return out


SPANS0 = {"ec.recover_reads_wall": 0.0, "ec.recover_reads_n": 0,
          "ec.recover_decode_wait_wall": 0.0,
          "ec.recover_decode_wait_n": 0,
          "ec.recover_push_wall": 0.0, "ec.recover_push_n": 0}
SPANS1 = {"ec.recover_reads_wall": 0.6, "ec.recover_reads_n": 4,
          "ec.recover_decode_wait_wall": 0.02,
          "ec.recover_decode_wait_n": 2,
          "ec.recover_push_wall": 1.0, "ec.recover_push_n": 2}
# osd.0 leads; osd.1 restarts at t=1000 (its counters start again)
BEFORE = [dump(0, 10, 0, hist(1.0, 10), 0, boot=900.0, clean=950.0,
               spans=SPANS0),
          dump(1, 5, 5, hist(0.5, 5), 4096, boot=900.0, clean=950.0)]
AFTER = [dump(0, 40, 6, hist(1.3, 12), 3 * 10 ** 6, boot=900.0,
              clean=1012.5, spans=SPANS1),
         dump(1, 20, 4, hist(0.4, 2), 10 ** 6, boot=1000.0,
              clean=1010.0)]


def ctx(before, after):
    return {"before": {"osd_perf": before},
            "verdict": {"recovered_snapshot": {"osd_perf": after}}}


def test_reader_reads_the_restart():
    got = RECOVERY.read(ctx(BEFORE, AFTER))
    # the restart at 1000, the last PG clean at 1012.5
    assert got["recover_s"] == pytest.approx(12.5)
    # 3 MB pushed by osd.0, 1 MB by the restarted osd.1 (from zero)
    assert got["recovery_shard_MBps"] == pytest.approx(4.0 / 12.5)
    # scanned 30 + 20, recovered 6 + 4
    assert got["recovery_scanned_per_recovered"] == pytest.approx(5.0)
    assert got["recover_reads_ms_mean"] == pytest.approx(150.0)
    assert got["recover_decode_wait_ms_mean"] == pytest.approx(10.0)
    assert got["recover_push_ms_mean"] == pytest.approx(500.0)
    # 0.3 s over 2 rounds on osd.0, 0.4 s over 2 on the new osd.1
    assert got["peering_ms_mean"] == pytest.approx(175.0)


def test_reader_gives_nothing_without_counters():
    # a program without the spans, the boot and clean gauges: only
    # what its ledger already counted
    strip = {"osd_boot_at", "pg_last_clean_at"}

    def old(dumps):
        return [{name: {k: v for k, v in sets.items() if k not in strip}
                 for name, sets in d.items() if name != "host_spans"}
                for d in dumps]
    got = RECOVERY.read(ctx(old(BEFORE), old(AFTER)))
    assert set(got) == {"recovery_scanned_per_recovered",
                        "peering_ms_mean"}
    # a cell without the third reading: nothing at all
    assert RECOVERY.read({"before": {"osd_perf": BEFORE},
                          "verdict": {}}) == {}
