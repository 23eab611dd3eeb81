"""The per-layer readers this round added (op_phases, host_spans,
transfers, compile_seconds) on hand-built snapshots: each returns the
hand-worked value, and nothing at all — no exception — on the dumps of
a program that has none of their counters.

    python3 -m pytest benchmark/tests/test_new_readers.py -q
"""

import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run  # noqa: E402

READERS = {name: run.load_module("metrics", name) for name in (
    "op_phases", "host_spans", "transfers", "compile_seconds")}


def hist(total, n):
    return {"sum": total, "count": n, "buckets": []}


def snapshot(t, osd_perf, launches=None, compile_=None):
    return {"t": t, "osd_perf": osd_perf,
            "launch_queue": None if launches is None
            else {"launches": launches},
            "compile": compile_ if compile_ is not None else {}}


def full_ctx():
    """Two OSDs; 4 client ops acked between the snapshots (one before,
    one failed); phases of 4 osd_ops on osd.0 and 8 sub-writes."""
    before = snapshot(
        100.0,
        [{"optracker.osd.0": {
            "lat_phase_osd_op_wire_in": hist(1.0, 10),
            "lat_phase_osd_op_queue_wait": hist(0.1, 10),
            "lat_phase_osd_op_prepare": hist(2.0, 10),
            "lat_phase_osd_op_encode": hist(0.5, 10),
            "lat_phase_osd_op_fanout_commit": hist(3.0, 10),
            "lat_total_osd_op": hist(5.6, 10),
            "lat_phase_ec_sub_write_apply": hist(0.2, 20)},
          "host_spans": {
              "process_cpu_s": 50.0,
              "msgr.reactor_cpu": 10.0,
              "msgr.dispatch.MPGStats_cpu": 0.25,
              "osd.op_prepare_cpu": 0.5, "osd.tick.heartbeat_cpu": 0.1,
              "ec.assemble_cpu": 0.3, "lq.launch_cpu": 0.1,
              "ec.h2d_cpu": 0.05, "ec.h2d_wall": 0.06,
              "ec.d2h_wait_cpu": 0.01, "ec.d2h_wait_wall": 0.5,
              "store.commit_cpu": 0.2},
          "ec_host_queue": {"ec_host_launch_bytes": 1000,
                            "ec_host_launch_padded_bytes": 1000,
                            "ec_h2d_bytes": 1200,
                            "ec_d2h_bytes": 600}},
         {"optracker.osd.1": {
             "lat_phase_ec_sub_write_apply": hist(0.1, 10)}}],
        launches=10, compile_={"misses": 3, "compile_s": 12.5})
    after = snapshot(
        200.0,
        [{"optracker.osd.0": {
            "lat_phase_osd_op_wire_in": hist(1.4, 14),
            "lat_phase_osd_op_queue_wait": hist(0.14, 14),
            "lat_phase_osd_op_prepare": hist(2.8, 14),
            "lat_phase_osd_op_encode": hist(0.9, 14),
            "lat_phase_osd_op_fanout_commit": hist(4.2, 14),
            "lat_total_osd_op": hist(8.04, 14),
            "lat_phase_ec_sub_write_apply": hist(0.28, 24)},
          "host_spans": {
              "process_cpu_s": 60.0,
              "msgr.reactor_cpu": 10.6,
              "msgr.dispatch.MPGStats_cpu": 0.45,
              "msgr.dispatch.MOSDECSubOpWrite_cpu": 0.2,     # new name
              "osd.op_prepare_cpu": 0.9, "osd.tick.heartbeat_cpu": 0.3,
              "ec.assemble_cpu": 0.5, "lq.launch_cpu": 0.3,
              "ec.h2d_cpu": 0.09, "ec.h2d_wall": 0.10,
              "ec.d2h_wait_cpu": 0.03, "ec.d2h_wait_wall": 0.9,
              "store.commit_cpu": 0.6},
          "ec_host_queue": {"ec_host_launch_bytes": 4000,
                            "ec_host_launch_padded_bytes": 5000,
                            "ec_h2d_bytes": 6000,
                            "ec_d2h_bytes": 2600}},
         {"optracker.osd.1": {
             "lat_phase_ec_sub_write_apply": hist(0.14, 14)}}],
        launches=14, compile_={"misses": 3, "compile_s": 12.5})
    ops = [(0, 90.0, 99.0, None),               # acked before
           (1, 100.0, 101.0, None), (2, 110.0, 112.0, None),
           (3, 120.0, 121.5, None), (4, 150.0, 151.5, None),
           (5, 160.0, 165.0, "boom")]           # failed
    return {"before": before, "after": after, "run": {"ops": ops}}


def bare_ctx():
    """A program without this round's counters (the parent commit):
    its dumps hold the old sets only."""
    perf = [{"optracker.osd.0": {"lat_total_osd_op": hist(1.0, 2)},
             "msgr_ledger": {"lat_msgr_dispatch": hist(1.0, 2)},
             "ec_host_queue": {"ec_host_launch_bytes": 10}}]
    return {"before": snapshot(1.0, perf, launches=1,
                               compile_={"misses": 0}),
            "after": snapshot(2.0, perf, launches=2,
                              compile_={"misses": 0}),
            "run": {"ops": [(0, 1.0, 1.5, None)]}}


# hand-worked from full_ctx(): deltas over the 4 ops / 4 launches
WANT = {
    "op_phases": {
        "op_wire_in_ms_mean": 100.0,            # 0.4 s / 4
        "op_queue_wait_ms_mean": 10.0,
        "op_prepare_ms_mean": 200.0,
        "op_encode_ms_mean": 100.0,
        "op_fanout_commit_ms_mean": 300.0,
        "subwrite_apply_ms_mean": 15.0,         # (0.08 + 0.04) s / 8
        # client mean (1 + 2 + 1.5 + 1.5) / 4 = 1500 ms; the primary's
        # op 2.44 s / 4 = 610 ms; 1500 - 100 - 610
        "client_outside_osd_ms_mean": 790.0},
    "host_spans": {
        "wire_cpu_ms_per_op": 250.0,            # (.6 + .2 + .2) / 4
        "osd_cpu_ms_per_op": 150.0,             # (.4 + .2) / 4
        "ec_cpu_ms_per_op": 115.0,              # (.2 + .2 + .04 + .02) / 4
        "store_cpu_ms_per_op": 100.0,
        "host_cpu_accounted_share": 0.246},     # 2.46 s of 10 s
    "transfers": {
        "h2d_ms_per_launch": 10.0,              # 0.04 s / 4
        "d2h_wait_ms_per_launch": 100.0,
        "lq_padded_byte_share": 0.25,           # 1 - 3000 / 4000
        "h2d_bytes_per_launch": 1200.0,         # 4800 / 4
        "d2h_bytes_per_launch": 500.0},
    "compile_seconds": {"compile_s_in_window": 0.0},
}


@pytest.mark.parametrize("reader", sorted(READERS))
def test_reader_gives_the_hand_worked_values(reader):
    got = READERS[reader].read(full_ctx())
    assert set(got) == set(WANT[reader])
    for name, want in WANT[reader].items():
        assert got[name] == pytest.approx(want, rel=1e-9, abs=1e-9), name
    assert set(got) == set(READERS[reader].METRICS)


@pytest.mark.parametrize("reader", sorted(READERS))
def test_reader_is_silent_without_its_counters(reader):
    assert READERS[reader].read(bare_ctx()) == {}


def test_the_four_phases_are_the_op_again():
    got = READERS["op_phases"].read(full_ctx())
    inside = sum(got[f"op_{p}_ms_mean"] for p in (
        "queue_wait", "prepare", "encode", "fanout_commit"))
    assert inside == pytest.approx(610.0)       # lat_total_osd_op's mean


def test_selfcheck_passes():
    done = subprocess.run(
        [sys.executable, os.path.join(BENCH, "selfcheck.py")],
        capture_output=True, text=True, timeout=300)
    assert done.returncode == 0, done.stdout[-2000:] + done.stderr[-2000:]
    assert "selfcheck: all ok" in done.stdout
