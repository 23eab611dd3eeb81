"""The constant-upload reader (benchmark/metrics/const_uploads.py) on
hand-built `perf dump`s: the share is the hand-worked one, and on the
dumps of a program without the counter (the parent commit) the metric
is absent — not 0, and no exception.

    python3 -m pytest benchmark/tests/test_const_upload_reader.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run  # noqa: E402

READER = run.load_module("metrics", "const_uploads")


def ctx(before, after):
    def snap(t, queue):
        return {"t": t, "osd_perf": [{"ec_host_queue": queue}, {}],
                "launch_queue": None, "compile": {}}
    return {"before": snap(100.0, before), "after": snap(200.0, after),
            "run": {"ops": []}}


@pytest.mark.parametrize("before,after,want", [
    # 100 launches of 8,192 staged bytes; two constants uploaded once
    ((1000, 0), (1000 + 819_200 + 532_480, 532_480), 532_480 / 1_351_680),
    # the steady state: nothing but the data goes up
    ((600_000, 532_480), (600_000 + 819_200, 532_480), 0.0),
    # every launch uploads its constants anew
    ((0, 0), (100 * 532_860, 100 * 524_668), 524_668 / 532_860),
], ids=["first_launches", "steady_state", "anew_every_launch"])
def test_share_is_constant_bytes_over_uploaded_bytes(before, after, want):
    def queue(pair):
        return {"ec_h2d_bytes": pair[0], "ec_h2d_const_bytes": pair[1]}
    got = READER.read(ctx(queue(before), queue(after)))
    assert got == {"lq_const_upload_share": pytest.approx(want)}
    assert set(got) == set(READER.METRICS)


@pytest.mark.parametrize("queue", [
    # the parent commit: uploads counted, constants not apart
    {"ec_h2d_bytes": 532_860},
    # the counter is there and no launch uploaded between the dumps
    {"ec_h2d_bytes": 532_860, "ec_h2d_const_bytes": 524_668},
], ids=["parent_commit", "no_launch_in_window"])
def test_metric_is_absent_when_there_is_nothing_to_read(queue):
    assert READER.read(ctx(queue, queue)) == {}
