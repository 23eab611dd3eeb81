"""The probe reader (benchmark/metrics/probe.py) on two hand-built
`perf dump`s: the share it reports is the hand-worked one, and on the
dumps of a program without the counters (the parent commit) the metric
is absent — not 0, and no exception.

    python3 -m pytest benchmark/tests/test_probe_reader.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run  # noqa: E402

PROBE = run.load_module("metrics", "probe")


def ctx(before, after):
    def snap(t, osd_perf):
        return {"t": t, "osd_perf": osd_perf, "launch_queue": None,
                "compile": {}}
    return {"before": snap(100.0, before), "after": snap(200.0, after),
            "run": {"ops": []}}


def ec_set(sweeps, remote, **more):
    return {"ec_drain_submits": 7, "ec_probe_sweeps": sweeps,
            "ec_probe_remote_sweeps": remote, **more}


# two OSDs, three PGs; between the dumps 40 + 8 + 2 = 50 probes, of
# which 0 + 3 + 2 = 5 went to the wire
BEFORE = [{"ec.1.0": ec_set(10, 10), "ec.1.1": ec_set(4, 1)},
          {"ec.1.2": ec_set(0, 0), "osd.1": {"op": 5}}]
AFTER = [{"ec.1.0": ec_set(50, 10, ec_probe_local_hits=12,
                           ec_probe_local_authoritative_misses=28),
          "ec.1.1": ec_set(12, 4, ec_probe_remote_reads=6)},
         {"ec.1.2": ec_set(2, 2, ec_probe_remote_reads=4),
          "osd.1": {"op": 9}}]


def test_share_is_remote_sweeps_over_sweeps():
    got = PROBE.read(ctx(BEFORE, AFTER))
    assert got == {"ec_probe_remote_share": pytest.approx(0.1)}
    assert set(got) == set(PROBE.METRICS)


@pytest.mark.parametrize("after,want", [
    ([{"ec.1.0": ec_set(30, 10)}, {}], 0.0),     # every probe local
    ([{"ec.1.0": ec_set(30, 30)}, {}], 1.0),     # every probe remote
], ids=["all_local", "all_remote"])
def test_share_at_its_ends(after, want):
    before = [{"ec.1.0": ec_set(10, 10)}, {}]
    assert PROBE.read(ctx(before, after)) == {
        "ec_probe_remote_share": pytest.approx(want)}


@pytest.mark.parametrize("dumps", [
    # the parent commit: ec sets without the probe counters
    [{"ec.1.0": {"ec_drain_submits": 9, "ec_fused_kernel_drains": 9}}],
    # no ec set at all (a replicated pool)
    [{"osd.0": {"op": 3}}],
    # the counters are there and no probe ran between the dumps
    [{"ec.1.0": ec_set(10, 10)}],
], ids=["parent_commit", "no_ec_set", "no_probe_in_window"])
def test_metric_is_absent_when_there_is_nothing_to_read(dumps):
    assert PROBE.read(ctx(dumps, dumps)) == {}
