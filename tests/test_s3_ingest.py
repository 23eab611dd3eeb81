"""S3 small-object ingest through the gateway onto an erasure-coded
data pool with a sharded, replicated bucket index — the deployment
`s3_ec42_osd8` of the benchmark, small: 4 OSDs, EC k=2 m=1, index
replicated twice, 4 buckets x 3 shards, 8 concurrent signed PUTs of
8 KiB over HTTP.

Sound, it is compared with the benchmark's plain reference
(benchmark/references/s3_bucket_ec.py) by the benchmark's own
generator; the gateway's `rgw` counters move by the exact count a
scripted sequence implies; a PUT's spans form the documented tree and
its trace id reaches the OSDs; and each fault of
benchmark/faults_s3.py reads not correct."""

import copy
import errno
import json
import os
import sys
import threading
import time

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
BENCH = os.path.join(ROOT, "benchmark")
# after everything else: the benchmark's flat module names (deploy,
# stats, ...) must shadow nothing a later test of this worker imports
sys.path.append(BENCH)

import deploy  # noqa: E402
import faults_s3  # noqa: E402
from run import load_module  # noqa: E402

from ceph_tpu.common import spans  # noqa: E402
from ceph_tpu.rados import RadosClient  # noqa: E402
from ceph_tpu.rados.client import RadosError  # noqa: E402
from ceph_tpu.rgw.gateway import S3Gateway  # noqa: E402
from ceph_tpu.rgw.store import _part_oid  # noqa: E402

GEN = load_module("generators", "s3_closed_loop_put")
REF = load_module("references", "s3_bucket_ec")
READER = load_module("metrics", "rgw")
SIZE = 8192


def small_config() -> dict:
    cfg = deploy.rehearsal_of(deploy.load_json("configs", "s3_ec42_osd8"))
    cfg["deployment"].update(osds=4, conf={"rgw_bucket_index_shards": 3})
    cfg["pool"].update(pg_num=8, profile=dict(
        cfg["pool"]["profile"], k="2", m="1"))
    cfg["gateway"]["meta_pool"].update(size=2, pg_num=4)
    return cfg


def small_traffic() -> dict:
    trf = deploy.rehearsal_of(deploy.load_json("traffic", "s3_put64k_w32"))
    trf.update(object_bytes=SIZE, writers=8, warmup_ops=8, ramp_s=0.2,
               stagger_s=0.1, counter_lead_s=0.1, payload_pool=8,
               list_page=5)
    return trf


def mini_run(seed: int, seconds: float = 1.5) -> dict:
    """What benchmark/run.py does with a cell, without its device
    look-up and prewarm: boot, drive, verify, stop."""
    cfg, trf = small_config(), small_traffic()
    dep = deploy.Deployment(cfg)
    snaps = {}
    try:
        dep.start()
        state = GEN.make_payloads(trf, seed)
        run = GEN.drive(dep, trf, state, seconds,
                        before_window=lambda: snaps.__setitem__(
                            "before", dep.snapshot()))
        snaps["after"] = dep.snapshot()
        verdict = GEN.verify(dep, trf, state, run, seed, REF)
    finally:
        dep.stop()
    return {"config": cfg, "traffic": trf, "run": run,
            "verdict": verdict, **snaps}


@pytest.fixture(scope="module")
def sound():
    return mini_run(2147486001)


def test_acked_puts_read_back_equal_with_etag_and_length(sound):
    v = sound["verdict"]
    assert v["failed"] == 0 and v["checked"]["acked"] > 16
    assert v["checked"]["read_back"] == v["checked"]["acked"]
    for key in ("put_errors", "put_etag_wrong", "readback_unreadable",
                "readback_differing", "readback_etag_wrong",
                "readback_length_wrong"):
        assert v["compared"][key] == [0, 0], key


def test_paginated_listing_equals_the_reference(sound):
    v = sound["verdict"]
    # pages of 5 over 4 buckets: the continuation path was walked
    assert v["checked"]["listed"] == v["checked"]["acked"] > 4 * 5
    for key in ("listing_keys_missing", "listing_keys_unexpected",
                "listing_keys_doubled", "listing_entries_wrong",
                "listing_buckets_misordered"):
        assert v["compared"][key] == [0, 0], key


def test_shards_and_crcs_equal_the_reference_encoding(sound):
    v = sound["verdict"]
    assert v["checked"]["audited_shards"] == \
        3 * v["checked"]["audited_objects"] > 0
    for key in ("audit_shards_missing", "audit_shard_bytes_wrong",
                "audit_shard_crcs_wrong"):
        assert v["compared"][key] == [0, 0], key


def test_index_shards_equal_on_their_replicas(sound):
    v = sound["verdict"]
    assert v["checked"]["index_shard_objects"] == 4 * 3
    for key in ("index_shard_objects_absent", "index_replicas_missing",
                "index_replicas_differing"):
        assert v["compared"][key] == [0, 0], key
    assert v["correct"] is True
    # 1.5 x for k2m1, and the index documents twice on top
    assert 1.5 < v["stored_bytes"] / v["acked_bytes"] < 1.7


def test_reader_on_the_runs_own_dumps(sound):
    got = READER.read(sound)
    assert set(got) == set(READER.METRICS)
    # the bucket row once (authorization, handed on to the store), the
    # key's index entry once, quota gate (the user has no limit: it
    # reserves nothing and no release follows), write, index add, stats
    assert got["rgw_rados_ops_per_put"] == 6.0
    split = sum(got[k] for k in (
        "rgw_frontend_ms_mean", "rgw_data_write_ms_mean",
        "rgw_index_ms_per_put", "rgw_account_ms_per_put"))
    assert 0.8 * got["rgw_put_ms_mean"] < split <= got["rgw_put_ms_mean"]
    # five of a PUT's six ops go to the replicated pool
    assert 0.78 < got["rgw_index_ops_share"] < 0.88
    assert got["client_outside_rgw_ms_mean"] > 0
    # a cell without a gateway, a program without the counters
    assert READER.read({"run": {"ops": []}}) == {}
    bare = copy.deepcopy(sound)
    for side in ("before", "after"):
        del bare["run"]["gateway_perf"][side]["rgw"]
    assert READER.read(bare) == {}


@pytest.mark.parametrize("fault,numbers", [
    ("index_drop", {"listing_keys_missing", "readback_unreadable"}),
    ("ack_before_index", {"listing_keys_missing"}),
    ("etag_wrong", {"readback_etag_wrong", "listing_entries_wrong"}),
    ("index_replica_skew", {"index_replicas_differing"}),
    ("parity_flip", {"audit_shard_bytes_wrong"}),
])
def test_fault_is_not_correct(fault, numbers, monkeypatch):
    from ceph_tpu.ops import bitsliced as bs
    from ceph_tpu.rgw.bucket_index import BucketIndex
    from ceph_tpu.store.mem_store import MemStore
    # planted by assignment in faults_s3.py: put back after the test
    monkeypatch.setattr(BucketIndex, "add", BucketIndex.add)
    monkeypatch.setattr(MemStore, "queue_transactions",
                        MemStore.queue_transactions)
    monkeypatch.setattr(bs, "gf_encode_extents_with_crc_finalize",
                        bs.gf_encode_extents_with_crc_finalize)
    if fault == "index_drop":
        # one in 50 at the cell's size; a run this small may not make 50
        faults_s3.index_drop(every=10)
    else:
        faults_s3.FAULTS[fault]()
    v = mini_run(2147486002)["verdict"]
    assert v["correct"] is False
    failing = {k for k, (val, lim) in v["compared"].items() if val > lim}
    assert numbers <= failing
    if fault == "parity_flip":
        # a healthy GET returns data shards only: the audit alone sees it
        assert failing == {"audit_shard_bytes_wrong"}
    if fault == "index_replica_skew":
        assert v["compared"]["audit_shard_bytes_wrong"][0] == 0


# -- a live gateway for scripted sequences -----------------------------------

@pytest.fixture(scope="module")
def live():
    cfg = small_config()
    dep = deploy.Deployment(cfg)
    dep.start()
    meta = cfg["gateway"]["meta_pool"]
    dep.client.create_pool(meta["name"], meta["type"], size=meta["size"],
                           pg_num=meta["pg_num"])
    dep.cluster.wait_active_clean(timeout=120.0)
    spec = cfg["gateway"]
    gw = S3Gateway(dep.cluster.client(), (spec["host"], 0),
                   creds={spec["access_key"]: spec["secret_key"]})
    conn = GEN.S3Connection(tuple(gw.addr), spec)
    try:
        yield {"dep": dep, "gw": gw, "conn": conn, "spec": spec}
    finally:
        conn.close()
        gw.shutdown()
        dep.stop()


def test_create_bucket_takes_its_shard_count_from_the_configuration(live):
    status, _, _ = live["conn"].request("PUT", "/confbucket")
    assert status == 200
    stats = live["gw"].store.bucket_stats("confbucket")
    assert stats["shards"] == 3 and len(stats["shard_fill"]) == 3
    assert live["gw"].store.conf.get("rgw_bucket_index_shards") == 3


def test_the_schema_default_is_still_one_shard(live):
    """A gateway on a client that carries no configuration."""
    client = RadosClient(live["dep"].cluster.mon_addrs).connect()
    try:
        assert client.conf.get("rgw_bucket_index_shards") == 1
        from ceph_tpu.rgw.store import RGWStore
        st = RGWStore(client)
        st.create_bucket("plainbucket")
        assert st.bucket_stats("plainbucket")["shards"] == 1
    finally:
        client.shutdown()


def _rgw(live) -> dict:
    return live["gw"].perf_dump()["rgw"]


def test_rgw_counters_move_by_the_exact_counts(live):
    conn, spec = live["conn"], live["spec"]
    assert conn.request("PUT", "/counted")[0] == 200
    before = _rgw(live)
    n = 5
    for i in range(n):
        assert conn.request("PUT", f"/counted/k{i}",
                            body=bytes([i]) * SIZE)[0] == 200
    after = _rgw(live)
    meta, data = spec["meta_pool"]["name"], live["dep"].pool

    def delta(key):
        return after.get(key, 0) - before.get(key, 0)

    assert delta("rgw_req") == n and delta("rgw_failed") == 0
    assert delta("rgw_put") == n
    assert delta("rgw_put_bytes") == n * SIZE
    assert delta("rgw_put_rados_ops") == 6 * n
    assert delta(f"rgw_rados_ops.{meta}") == 5 * n
    assert delta(f"rgw_rados_ops.{data}") == n
    assert delta("rgw_put_bucket_row_reads") == n
    # the user has no limit: every PUT passes the gate, none reserves,
    # and the account object is rewritten once a PUT, by the stats
    assert delta("rgw_quota_gates") == n
    assert delta("rgw_quota_reservations") == 0
    assert delta("rgw_put_account_writes") == n
    for key in ("rgw_put_lat", "rgw_put_frontend_lat", "rgw_put_data_lat",
                "rgw_put_index_lat", "rgw_put_account_lat"):
        assert after[key]["count"] - before[key]["count"] == n, key
        assert after[key]["sum"] > before[key]["sum"], key
    split = sum(after[k]["sum"] - before[k]["sum"] for k in (
        "rgw_put_frontend_lat", "rgw_put_data_lat", "rgw_put_index_lat",
        "rgw_put_account_lat"))
    assert split <= after["rgw_put_lat"]["sum"] - \
        before["rgw_put_lat"]["sum"]
    # the objecter's set rides the same dump
    assert live["gw"].perf_dump()["objecter"]["op_reply"] > 0


def test_failed_and_other_requests_are_counted_apart(live):
    conn = live["conn"]
    before = _rgw(live)
    assert conn.request("GET", "/counted/absent")[0] == 404
    assert conn.request("PUT", "/nosuchbucket/k", body=b"x")[0] == 404
    assert conn.request("GET", "/counted/k0")[0] == 200
    after = _rgw(live)
    assert after["rgw_req"] - before["rgw_req"] == 3
    assert after["rgw_failed"] - before["rgw_failed"] == 2
    assert after["rgw_put"] == before["rgw_put"]
    assert after["rgw_put_lat"]["count"] == before["rgw_put_lat"]["count"]


def test_counters_are_exact_under_concurrent_puts(live):
    spec = live["spec"]
    before = _rgw(live)
    errors = []

    def worker(w: int) -> None:
        conn = GEN.S3Connection(tuple(live["gw"].addr), spec)
        try:
            for i in range(4):
                status, _, _ = conn.request(
                    "PUT", f"/counted/w{w}-{i}", body=bytes([w]) * SIZE)
                if status != 200:
                    errors.append(status)
        finally:
            conn.close()

    threads = [threading.Thread(target=worker, args=(w,))
               for w in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join(timeout=60)
    assert not errors and not any(t.is_alive() for t in threads)
    after = _rgw(live)
    assert after["rgw_put"] - before["rgw_put"] == 32
    assert after["rgw_put_rados_ops"] - before["rgw_put_rados_ops"] == 192
    assert after["rgw_put_bucket_row_reads"] - \
        before["rgw_put_bucket_row_reads"] == 32
    assert after["rgw_put_account_writes"] - \
        before["rgw_put_account_writes"] == 32
    assert after["rgw_quota_gates"] - before["rgw_quota_gates"] == 32
    assert after["rgw_quota_reservations"] == \
        before["rgw_quota_reservations"]
    assert after["rgw_put_bytes"] - before["rgw_put_bytes"] == 32 * SIZE
    rows = GEN.list_bucket(live["conn"], "counted", 7)
    assert len(rows) == len({k for k, _, _ in rows}) == 5 + 32


def test_a_limited_users_put_is_nine_ops_and_two_account_writes(live):
    """The other branch of the rule: with a limit set the gate
    reserves, and the stats call takes the reservation back."""
    conn, store = live["conn"], live["gw"].store
    user = live["spec"]["access_key"]
    store.set_user_quota(user, max_objects=1_000_000)
    try:
        before = _rgw(live)
        n = 3
        for i in range(n):
            assert conn.request("PUT", f"/counted/lim{i}",
                                body=bytes([i]) * SIZE)[0] == 200
        after = _rgw(live)
        assert "pending" not in store.get_user_header(user)
    finally:
        store.set_user_quota(user)
    for key, want in (("rgw_put", n), ("rgw_put_rados_ops", 6 * n),
                      ("rgw_put_account_writes", 2 * n),
                      ("rgw_quota_gates", n),
                      ("rgw_quota_reservations", n)):
        assert after[key] - before[key] == want, key


# -- one bucket row and one index entry a PUT --------------------------------

def _index_gets(live, bucket: str) -> int:
    """Look-ups of the bucket's index shards (every plane) so far."""
    return sum(c["get"] for c in
               live["gw"].store.index.perf_dump(bucket).values())


def _multipart(store, bucket: str, key: str) -> list[str]:
    """Complete a two-part upload of `key`: -> its part objects."""
    upload_id = store.init_multipart(bucket, key)
    parts = [(num, store.upload_part(bucket, key, upload_id, num,
                                     bytes([num]) * SIZE))
             for num in (1, 2)]
    store.complete_multipart(bucket, key, upload_id, parts)
    return [_part_oid(bucket, upload_id, num) for num, _ in parts]


def _absent(io, oid: str) -> bool:
    try:
        io.stat(oid)
    except RadosError as e:
        assert e.errno == errno.ENOENT
        return True
    return False


@pytest.mark.parametrize("overwrite", [False, True],
                         ids=["fresh_key", "overwrite"])
def test_a_put_reads_its_bucket_row_once_and_its_index_entry_once(
        live, overwrite):
    """The row the authorization read is the one `put_object` uses,
    and an entry found absent is not looked up again."""
    conn, spec = live["conn"], live["spec"]
    bucket = "overwritten" if overwrite else "fresh"
    assert conn.request("PUT", f"/{bucket}")[0] == 200
    n = 3
    if overwrite:
        for i in range(n):
            assert conn.request("PUT", f"/{bucket}/k{i}",
                                body=b"o" * SIZE)[0] == 200
    before, gets = _rgw(live), _index_gets(live, bucket)
    for i in range(n):
        # a size of its own: the overwrite's stats call goes out too
        assert conn.request("PUT", f"/{bucket}/k{i}",
                            body=bytes([i]) * (SIZE // 2))[0] == 200
    after = _rgw(live)
    meta, data = spec["meta_pool"]["name"], live["dep"].pool
    assert after["rgw_put"] - before["rgw_put"] == n
    assert after["rgw_put_bucket_row_reads"] - \
        before["rgw_put_bucket_row_reads"] == n
    assert _index_gets(live, bucket) - gets == n
    assert after["rgw_put_rados_ops"] - before["rgw_put_rados_ops"] == 6 * n
    assert after[f"rgw_rados_ops.{meta}"] - \
        before[f"rgw_rados_ops.{meta}"] == 5 * n
    assert after[f"rgw_rados_ops.{data}"] - \
        before[f"rgw_rados_ops.{data}"] == n
    rows = GEN.list_bucket(conn, bucket, 7)
    assert [(k, size) for k, size, _ in rows] == \
        [(f"k{i}", SIZE // 2) for i in range(n)]


def test_an_overwrite_of_a_multipart_object_removes_its_parts(live):
    conn, store = live["conn"], live["gw"].store
    assert conn.request("PUT", "/mpover")[0] == 200
    parts = _multipart(store, "mpover", "big")
    assert not any(_absent(store.data, oid) for oid in parts)
    before = _rgw(live)
    assert conn.request("PUT", "/mpover/big", body=b"p" * SIZE)[0] == 200
    after = _rgw(live)
    assert after["rgw_put_bucket_row_reads"] - \
        before["rgw_put_bucket_row_reads"] == 1
    assert all(_absent(store.data, oid) for oid in parts)
    status, _, body = conn.request("GET", "/mpover/big")
    assert (status, body) == (200, b"p" * SIZE)


def test_a_put_on_a_suspended_bucket_replaces_the_null_rows_manifest(live):
    conn, store = live["conn"], live["gw"].store
    assert conn.request("PUT", "/suspended")[0] == 200
    store.set_versioning("suspended", "Suspended")
    parts = _multipart(store, "suspended", "doc")
    assert store._version_row("suspended", "doc", "null")["multipart"]
    before = _rgw(live)
    status, headers, _ = conn.request("PUT", "/suspended/doc",
                                      body=b"n" * SIZE)
    assert status == 200
    after = _rgw(live)
    assert after["rgw_put_bucket_row_reads"] - \
        before["rgw_put_bucket_row_reads"] == 1
    row = store._version_row("suspended", "doc", "null")
    assert "multipart" not in row and row["null_data"] is True
    assert (row["size"], f'"{row["etag"]}"') == (SIZE, headers["ETag"])
    assert all(_absent(store.data, oid) for oid in parts)
    body, _ = store.get_object_version("suspended", "doc", "null")
    assert bytes(body) == b"n" * SIZE


def test_a_put_to_a_deleted_bucket_is_404_after_one_op(live, monkeypatch):
    conn, gw = live["conn"], live["gw"]
    assert conn.request("PUT", "/gonebucket")[0] == 200
    assert conn.request("DELETE", "/gonebucket")[0] == 204
    seen = []
    real = gw.account

    def spy(req, status, put_bytes):
        seen.append((status, req.ops, req.bucket_row_reads))
        return real(req, status, put_bytes)

    monkeypatch.setattr(gw, "account", spy)
    status, _, body = conn.request("PUT", "/gonebucket/k",
                                   body=b"x" * SIZE)
    assert status == 404 and b"NoSuchBucket" in body
    assert seen == [(404, 1, 1)]


def test_span_tree_of_one_put(live, monkeypatch):
    seen = []
    real_end = spans.Span.end

    def recording_end(self, *exc):
        if self.wall_ns is None and self.on \
                and self.name.startswith("rgw."):
            parent = getattr(self, "parent", None)
            seen.append((self.name, parent.name if parent else None))
        return real_end(self, *exc)

    monkeypatch.setattr(spans.Span, "end", recording_end)
    monkeypatch.setattr(spans.Span, "__exit__", recording_end)
    n0 = spans.table().get("rgw.put", (0, 0, 0))[2]
    assert live["conn"].request("PUT", "/counted/spanned",
                                body=b"s" * SIZE)[0] == 200
    # the span closes after the reply has left: give it a moment
    deadline = time.monotonic() + 5.0
    while ("rgw.put", None) not in seen and time.monotonic() < deadline:
        time.sleep(0.01)
    assert ("rgw.put", None) in seen
    assert spans.table()["rgw.put"][2] == n0 + 1
    children = [name for name, parent in seen if parent == "rgw.put"]
    assert children.count("rgw.auth") == 1
    assert children.count("rgw.data_write") == 1
    # the bucket row (authorization), the key's entry, the index add
    assert children.count("rgw.index") == 3
    assert children.count("rgw.account") == 2
    assert all(parent == "rgw.put" for name, parent in seen
               if name != "rgw.put")


def test_a_puts_trace_id_reaches_the_osds(live):
    status, headers, _ = live["conn"].request(
        "PUT", "/counted/traced", body=b"t" * SIZE)
    assert status == 200
    trace_id = headers["x-amz-request-id"]
    ops = [op for osd in live["dep"].cluster.osds
           for op in osd.op_tracker.get_historic(trace_id)]
    # the ring keeps the newest 20 ops per OSD: the PUT's last ops are
    # certainly still there, each a span under the request's
    assert ops
    client_ops = [op for op in ops if op.op_type == "osd_op"]
    assert client_ops and len(
        {op.trace.span_id for op in client_ops}) == len(client_ops)
    assert len({op.trace.parent_span for op in client_ops}) == 1
    # the data write's sub-writes hang under ITS span, one level down
    spans_of_client_ops = {op.trace.span_id for op in client_ops}
    assert all(op.trace.parent_span in spans_of_client_ops
               for op in ops if op.op_type == "ec_sub_write")
    dumped = json.dumps([osd.op_tracker.dump_historic_ops()
                         for osd in live["dep"].cluster.osds])
    assert trace_id in dumped
