"""The account-writes reader (benchmark/metrics/rgw_account.py) on
hand-built gateway dumps: three rewrites a PUT for a program that
reserves, applies and releases, one for a program whose stats call is
the only rewrite; on the dumps of a program without the counter (the
parent commit), and in a cell without a gateway, the metric is absent
— not 0, and no exception.

    python3 -m pytest benchmark/tests/test_rgw_account_reader.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run  # noqa: E402

READER = run.load_module("metrics", "rgw_account")


def ctx(before, after):
    return {"run": {"ops": [], "gateway_perf": {
        "before": {"t": 100.0, "rgw": before},
        "after": {"t": 200.0, "rgw": after}}}}


def rgw(puts, ops, writes=None):
    dump = {"rgw_req": puts + 40, "rgw_put": puts,
            "rgw_put_rados_ops": ops,
            "rgw_put_lat": {"sum": 1.9 * puts, "count": puts}}
    if writes is not None:
        dump["rgw_put_account_writes"] = writes
    return dump


@pytest.mark.parametrize("before,after,want", [
    # reserve, add_stats, release: every PUT rewrites the object thrice
    (rgw(64, 640, 192), rgw(911, 9_110, 2_733), 3.0),
    # no limit on the user: the stats call alone
    (rgw(64, 576, 64), rgw(1_264, 11_376, 1_264), 1.0),
    # a limit on the user: the gate reserves, the stats retire it
    (rgw(0, 0, 0), rgw(500, 4_500, 1_000), 2.0),
], ids=["parent_shaped", "unlimited_user", "limited_user"])
def test_rewrites_per_put(before, after, want):
    got = READER.read(ctx(before, after))
    assert got == {"rgw_account_writes_per_put": pytest.approx(want)}
    assert set(got) == set(READER.METRICS)


@pytest.mark.parametrize("context", [
    # the parent commit: the set without the counter
    ctx(rgw(64, 640), rgw(911, 9_110)),
    # the counter is there and no PUT was answered between the dumps
    ctx(rgw(64, 576, 64), rgw(64, 576, 64)),
    # a cell without a gateway
    {"run": {"ops": []}},
    # a gateway whose dump has no `rgw` set
    {"run": {"ops": [], "gateway_perf": {
        "before": {"t": 1.0}, "after": {"t": 2.0}}}},
], ids=["parent_commit", "no_put_in_window", "no_gateway", "no_rgw_set"])
def test_metric_is_absent_when_there_is_nothing_to_read(context):
    assert READER.read(context) == {}
