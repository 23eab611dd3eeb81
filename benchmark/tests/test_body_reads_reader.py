"""The body-reads reader (benchmark/metrics/body_reads.py) on
hand-built `perf dump`s: the ratio is the hand-worked one, and on the
dumps of a program without the counters (the parent commit), or of a
window in which no large body arrived, the metric is absent — not 0,
and no exception.

    python3 -m pytest benchmark/tests/test_body_reads_reader.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run  # noqa: E402

READER = run.load_module("metrics", "body_reads")


def ctx(before, after):
    # the ledger's set is one object for the whole process: ONE OSD's
    # dump carries it
    def snap(t, ledger):
        return {"t": t, "osd_perf": [{"msgr_ledger": ledger}, {}],
                "launch_queue": None, "compile": {}}
    return {"before": snap(100.0, before), "after": snap(200.0, after),
            "run": {"ops": []}}


def ledger(bodies, reads, nbytes, rx_reads):
    return {"msgr_dispatches": 7, "msgr_frames_out": 50_000,
            "msgr_rx_reads": rx_reads, "msgr_large_bodies": bodies,
            "msgr_large_body_reads": reads,
            "msgr_large_body_bytes": nbytes}


@pytest.mark.parametrize("before,after,want", [
    # 560 ops of k8m3 in the window: a 4 MiB body and ten 512 KiB
    # ones each; 1.9 reads a body
    ((1_100, 2_000, 1 << 32, 90_000),
     (1_100 + 6_160, 2_000 + 11_704, 1 << 34, 190_000), 1.9),
    # every body whole in the read that carried its header
    ((0, 0, 0, 0), (800, 800, 800 << 20, 5_000), 1.0),
    # what a 256 KiB-a-pass reader would have counted
    ((10, 170, 10 << 22, 400), (110, 1_870, 110 << 22, 4_000), 17.0),
], ids=["k8m3_write4m", "one_read_each", "sixteen_and_one"])
def test_ratio_is_landed_reads_over_bodies(before, after, want):
    got = READER.read(ctx(ledger(*before), ledger(*after)))
    assert got == {"wire_reads_per_large_body": pytest.approx(want)}
    assert set(got) == set(READER.METRICS)


@pytest.mark.parametrize("dump", [
    # the parent commit: the ledger's set without the counters
    {"msgr_dispatches": 7, "msgr_frames_out": 50_000,
     "msgr_socket_writes": 50_400},
    # the counters are there and no large body arrived between the
    # dumps (a 4 KiB cell: the prefill's bodies came before the window)
    ledger(4_500, 9_100, 4_500 << 22, 120_000),
], ids=["parent_commit", "no_large_body_in_window"])
def test_metric_is_absent_when_there_is_nothing_to_read(dump):
    assert READER.read(ctx(dump, dump)) == {}
