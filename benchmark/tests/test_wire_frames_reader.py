"""The wire-frames reader (benchmark/metrics/wire_frames.py) on
hand-built `perf dump`s: the two ratios are the hand-worked ones, and
on the dumps of a program without the counters (the parent commit)
both metrics are absent — not 0, and no exception.

    python3 -m pytest benchmark/tests/test_wire_frames_reader.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run  # noqa: E402

READER = run.load_module("metrics", "wire_frames")


def ctx(before, after):
    # the ledger's set is one object for the whole process: ONE OSD's
    # dump carries it
    def snap(t, ledger):
        return {"t": t, "osd_perf": [{"msgr_ledger": ledger}, {}],
                "launch_queue": None, "compile": {}}
    return {"before": snap(100.0, before), "after": snap(200.0, after),
            "run": {"ops": []}}


def ledger(frames, writes, acks, rode):
    return {"msgr_dispatches": 7, "msgr_frames_out": frames,
            "msgr_socket_writes": writes, "msgr_acks_out": acks,
            "msgr_acks_piggybacked": rode}


@pytest.mark.parametrize("before,after,writes,acks", [
    # 30,000 frames, each one call; 600 sessions' debts paid by timer
    ((1_000, 1_010, 10, 900), (31_000, 31_610, 610, 29_000),
     30_600 / 30_000, 600 / 30_000),
    # a frame written part by part and acked by a frame of its own
    ((0, 0, 0, 0), (10_000, 36_000, 10_000, 0), 3.6, 1.0),
    # every ack rode: writes are frames, no ack of its own
    ((500, 500, 0, 400), (4_500, 4_500, 0, 4_400), 1.0, 0.0),
], ids=["timer_acks_only", "parts_and_ack_frames", "all_acks_ride"])
def test_ratios_are_writes_and_own_acks_over_frames(before, after,
                                                    writes, acks):
    got = READER.read(ctx(ledger(*before), ledger(*after)))
    assert got == {"wire_writes_per_frame": pytest.approx(writes),
                   "wire_acks_per_frame": pytest.approx(acks)}
    assert set(got) == set(READER.METRICS)


@pytest.mark.parametrize("dump", [
    # the parent commit: the ledger's set without the four counters
    {"msgr_dispatches": 7, "lat_msgr_qwait": {"sum": 1.0, "count": 3}},
    # the counters are there and no frame left between the dumps
    ledger(4_500, 4_600, 100, 4_000),
], ids=["parent_commit", "no_frame_in_window"])
def test_metrics_are_absent_when_there_is_nothing_to_read(dump):
    assert READER.read(ctx(dump, dump)) == {}
