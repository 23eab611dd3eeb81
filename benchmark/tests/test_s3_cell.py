"""The S3 ingest cell: a whole rehearsal run of the harness (tiny
sizes on the CPU, the look for a chip skipped) — a well-formed result
line with every metric the cell lists except the device-trace ones,
the control of faults_s3.py reading "correct": false — the `rgw.py`
reader on hand-built dumps, the dumps of a program without the
counters among them, and the yardstick's own check.

    python3 -m pytest benchmark/tests/test_s3_cell.py -q   (~1 minute)

(Every fault of faults_s3.py, small, is in tests/test_s3_ingest.py.)
"""

import argparse
import json
import os
import subprocess
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
ROOT = os.path.dirname(BENCH)
sys.path.insert(0, ROOT)
sys.path.insert(0, BENCH)
os.environ["JAX_PLATFORMS"] = "cpu"

import faults_s3  # noqa: E402
import run  # noqa: E402

CELL = "s3_ec42_put64k"


def _run(seed, trace=0):
    args = argparse.Namespace(workload=CELL, seed=seed, seconds=2.0,
                              trace=trace, rehearse=True)
    return run.run(args)[1]


def test_rehearsal_prints_a_well_formed_line_with_the_cells_metrics():
    res = _run(2147487001, trace=1)
    json.loads(json.dumps(res))             # the line is plain JSON
    assert res["correct"] is True and res["failed"] == 0
    assert res["attempted"] == res["facts"]["checked"]["acked"] > 32
    assert all(row["value"] <= row["limit"] == 0
               for row in res["compared"].values())
    assert len(res["compared"]) == 17
    checked = res["facts"]["checked"]
    assert checked["audited_shards"] == 6 * checked["audited_objects"]
    assert checked["listed"] == checked["read_back"] == checked["acked"]
    assert checked["index_shard_objects"] == 4 * 11
    with open(os.path.join(ROOT, "BENCHMARK.json")) as f:
        manifest = json.load(f)
    listed = {m["name"] for m in manifest["per_layer"]
              if CELL in m.get("workloads", ())
              and m["source"] != "device_trace"}
    assert listed == set(res["metrics"])
    values = {k: v["value"] for k, v in res["metrics"].items()}
    assert values["compiles_in_window"] == 0
    assert values["rgw_rados_ops_per_put"] == 10.0
    assert 0.85 < values["rgw_index_ops_share"] < 0.95
    assert values["ec_probe_remote_share"] == 0.0
    # k4m2 stores 1.5 bytes a byte; the index, three times over, a
    # little on top
    assert 1.5 < values["stored_bytes_per_user_byte"] < 1.6
    split = sum(values[k] for k in (
        "rgw_frontend_ms_mean", "rgw_data_write_ms_mean",
        "rgw_index_ms_per_put", "rgw_account_ms_per_put"))
    assert 0.9 * values["rgw_put_ms_mean"] < split \
        <= values["rgw_put_ms_mean"]
    e2e = res["facts"]["end_to_end"]
    assert e2e["write_MBps"] > 0 and e2e["write_p95_ms"] > 0
    assert e2e["setup_s"] > 0 and "client_sign_ms_mean" in e2e


def test_the_control_is_not_correct(monkeypatch):
    from ceph_tpu.rgw.bucket_index import BucketIndex
    # planted by assignment in faults_s3.py: put back after the test
    monkeypatch.setattr(BucketIndex, "add", BucketIndex.add)
    faults_s3.FAULTS["index_drop"]()
    res = _run(2147487002)
    assert res["correct"] is False
    assert res["compared"]["listing_keys_missing"]["value"] > 0
    assert res["compared"]["audit_shard_bytes_wrong"]["value"] == 0
    assert res["failed"] == 0           # every PUT was answered 200


# -- the reader on hand-built dumps ------------------------------------------

RGW = run.load_module("metrics", "rgw")


def hist(total, count):
    return {"sum": total, "count": count, "buckets": []}


def ctx(before, after, osd_ops=(100, 1000), acked=10):
    def snap(t, ops):
        return {"t": t, "osd_perf": [{"osd.0": {"op": ops}}],
                "launch_queue": None, "compile": {}}
    ops = [(n, 100.0 + n, 101.5 + n, None) for n in range(acked)]
    return {"before": snap(100.0, osd_ops[0]),
            "after": snap(200.0, osd_ops[1]),
            "config": {"gateway": {"meta_pool": {"name": ".rgw.meta"}}},
            "run": {"ops": ops, "gateway_perf": {
                "before": dict(before, t=100.0),
                "after": dict(after, t=200.0)}}}


# between the dumps 10 PUTs of 1.2 s each: 0.1 frontend, 0.2 data, 0.5
# index, 0.3 accounting; 100 RADOS ops of theirs, 90 of them (and 10 of
# a maintenance sweep's) to the index pool; the OSDs received 900 ops
BEFORE = {"rgw": {"rgw_put": 5, "rgw_put_rados_ops": 50,
                  "rgw_rados_ops..rgw.meta": 45,
                  "rgw_rados_ops..rgw.data": 5,
                  "rgw_put_lat": hist(6.0, 5),
                  "rgw_put_frontend_lat": hist(0.5, 5),
                  "rgw_put_data_lat": hist(1.0, 5),
                  "rgw_put_index_lat": hist(2.5, 5),
                  "rgw_put_account_lat": hist(1.5, 5)}}
AFTER = {"rgw": {"rgw_put": 15, "rgw_put_rados_ops": 150,
                 "rgw_rados_ops..rgw.meta": 145,
                 "rgw_rados_ops..rgw.data": 15,
                 "rgw_put_lat": hist(18.0, 15),
                 "rgw_put_frontend_lat": hist(1.5, 15),
                 "rgw_put_data_lat": hist(3.0, 15),
                 "rgw_put_index_lat": hist(7.5, 15),
                 "rgw_put_account_lat": hist(4.5, 15)}}


def test_rgw_reader_on_recorded_dumps():
    got = RGW.read(ctx(BEFORE, AFTER))
    assert got == {
        "rgw_put_ms_mean": pytest.approx(1200.0),
        "rgw_frontend_ms_mean": pytest.approx(100.0),
        "rgw_data_write_ms_mean": pytest.approx(200.0),
        "rgw_index_ms_per_put": pytest.approx(500.0),
        "rgw_account_ms_per_put": pytest.approx(300.0),
        "rgw_rados_ops_per_put": pytest.approx(10.0),
        "rgw_index_ops_share": pytest.approx(100 / 900),
        "client_outside_rgw_ms_mean": pytest.approx(300.0)}
    assert set(got) == set(RGW.METRICS)


def test_rgw_reader_gives_nothing_without_a_gateway_or_its_counters():
    """Every other cell's traced run, and the parent of PR 34 under
    this PR's benchmark files: nothing reported, nothing raised."""
    assert RGW.read({"run": {"ops": []}}) == {}
    assert RGW.read(ctx({}, {})) == {}
    assert RGW.read(ctx({"objecter": {}}, {"objecter": {}})) == {}
    # no PUT between the dumps
    assert RGW.read(ctx(BEFORE, BEFORE)) == {}
    # the OSDs' op counter absent: the share is left out, not guessed
    got = RGW.read(ctx(BEFORE, AFTER, osd_ops=(0, 0)))
    assert "rgw_index_ops_share" not in got and len(got) == 7


def test_selfcheck_still_passes():
    out = subprocess.run(
        [sys.executable, os.path.join(BENCH, "selfcheck.py")],
        capture_output=True, text=True, timeout=300, cwd=ROOT)
    assert out.returncode == 0, out.stdout[-2000:] + out.stderr[-2000:]
    assert "selfcheck: all ok" in out.stdout
