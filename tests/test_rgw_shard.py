"""Sharded bucket index subsystem: hash routing, merge-sorted
listing, online dynamic resharding, cls-atomic quota reservations.

Reference analogs: cls_rgw bucket index shards
(rgw_bucket_shard_index routing), RGWReshard/rgw_reshard.cc (dual
write + copy + cutover, dynamic resharding thresholds), and
radosgw-admin `bucket reshard` / `bucket limit check`.
"""

import json
import threading

import pytest

from ceph_tpu.rgw.bucket_index import shard_of
from ceph_tpu.rgw.store import RGWError, RGWStore
from ceph_tpu.tools.vstart import Cluster


@pytest.fixture(scope="module")
def cluster():
    with Cluster(n_osds=3) as c:
        yield c


@pytest.fixture(scope="module")
def st(cluster):
    return RGWStore(cluster.client())


def _keys(st, bucket, **kw):
    entries, _cps, _tr, _nm = st.list_objects(bucket, max_keys=100000,
                                              **kw)
    return [k for k, _m in entries]


# -- routing + layout ---------------------------------------------------


def test_shard_of_stable_and_spread():
    # stable: pure function of the key bytes (md5) — any drift would
    # misroute every existing bucket's entries
    assert shard_of("hello", 8) == shard_of("hello", 8)
    assert shard_of("hello", 1) == 0
    hits = {shard_of(f"key-{i}", 8) for i in range(256)}
    assert hits == set(range(8))    # every shard takes load


def test_legacy_layout_untouched(st):
    """shards=1 buckets keep the exact pre-shard oid so old data and
    direct index.<bucket> pokes (lifecycle tests) still resolve."""
    st.create_bucket("legacy1")
    st.put_object("legacy1", "a", b"x")
    raw = st._cls(st.meta, "index.legacy1", "dir_get", {"key": "a"})
    assert json.loads(raw.decode())["size"] == 1


def test_sharded_bucket_crud(st):
    st.create_bucket("sh4", shards=4)
    for i in range(40):
        st.put_object("sh4", f"k{i:03d}", b"v" * (i + 1))
    assert st.index.count("sh4") == 40
    # entries really spread over the 4 shard objects
    fill = st.index.shard_counts("sh4")
    assert len(fill) == 4 and sum(fill.values()) == 40
    assert all("g1" in oid for oid in fill)
    assert max(fill.values()) < 40
    body, meta = st.get_object("sh4", "k007")
    assert bytes(body) == b"v" * 8 and meta["size"] == 8
    st.delete_object("sh4", "k007")
    with pytest.raises(RGWError):
        st.head_object("sh4", "k007")
    assert st.index.count("sh4") == 39


def test_delete_bucket_reaps_all_shards(st, cluster):
    st.create_bucket("shdel", shards=4)
    st.put_object("shdel", "x", b"1")
    st.delete_object("shdel", "x")
    st.delete_bucket("shdel")
    from ceph_tpu.rados.client import RadosError
    for i in range(4):
        with pytest.raises(RadosError):
            st.meta.stat(f"index.shdel.g1.{i}")


# -- merge-sorted listing edges -----------------------------------------


@pytest.fixture(scope="module")
def listbkt(st):
    """8-shard bucket with folder structure spanning shards."""
    st.create_bucket("mlist", shards=8)
    keys = ([f"docs/{i:02d}.txt" for i in range(10)] +
            [f"logs/day{i}/x.log" for i in range(5)] +
            [f"top{i:02d}" for i in range(15)])
    for k in keys:
        st.put_object("mlist", k, b".")
    return sorted(keys)


def test_merged_flat_listing_sorted(st, listbkt):
    assert _keys(st, "mlist") == listbkt


def test_merged_pagination_mid_shard(st, listbkt):
    """Pages of 7 with resume tokens must re-assemble the exact key
    sequence — resume points land mid-shard and the per-shard cursors
    must not skip or repeat around them."""
    got, resume, rounds = [], "", 0
    while True:
        entries, _cps, trunc, nm = st.list_objects(
            "mlist", max_keys=7, resume=resume)
        got.extend(k for k, _m in entries)
        rounds += 1
        # truncation invariant: every non-final page says truncated
        assert trunc == (len(got) < len(listbkt))
        if not trunc:
            break
        resume = nm
    assert got == listbkt
    assert rounds == -(-len(listbkt) // 7)


def test_merged_marker_exclusive(st, listbkt):
    after = listbkt[4]
    assert _keys(st, "mlist", marker=after) == listbkt[5:]


def test_merged_delimiter_rollup_spans_shards(st, listbkt):
    """docs/ and logs/ roll up to one CommonPrefix each even though
    their members hash across all 8 shards."""
    entries, cps, trunc, _nm = st.list_objects(
        "mlist", delimiter="/", max_keys=1000)
    assert cps == ["docs/", "logs/"]
    assert [k for k, _m in entries] == \
        [k for k in listbkt if "/" not in k]
    assert not trunc


def test_merged_delimiter_paginated(st, listbkt):
    """max_keys budget counts folders + keys, and the resume point
    after a folder is its prefix successor (one probe per folder)."""
    entries, cps, trunc, nm = st.list_objects(
        "mlist", delimiter="/", max_keys=3)
    assert cps == ["docs/", "logs/"]
    assert len(entries) == 1 and trunc
    entries2, cps2, trunc2, _ = st.list_objects(
        "mlist", delimiter="/", max_keys=1000, resume=nm)
    assert cps2 == []
    rest = [k for k in listbkt if "/" not in k]
    assert [k for k, _m in entries] + [k for k, _m in entries2] == rest
    assert not trunc2


def test_versioned_listing_newest_first_across_shards(st):
    st.create_bucket("mvers", shards=4)
    st.set_versioning("mvers", "Enabled")
    for k in ("va", "vb", "vc"):
        for gen in range(3):
            st.put_object("mvers", k, f"{k}-{gen}".encode())
    rows = st.list_versions("mvers")
    assert [r["key"] for r in rows] == ["va"] * 3 + ["vb"] * 3 + \
        ["vc"] * 3
    for k in ("va", "vb", "vc"):
        krows = [r for r in rows if r["key"] == k]
        assert krows[0]["is_latest"] and not any(
            r["is_latest"] for r in krows[1:])
        # newest-first within the key: latest row is generation 2
        body, _m = st.get_object_version(
            "mvers", k, krows[0]["version_id"])
        assert bytes(body) == f"{k}-2".encode()


def test_versioned_pagination_truncation(st):
    rows_all = st.list_versions("mvers")
    rows_page = st.list_versions("mvers", max_keys=4)
    assert rows_page == rows_all[:4]


# -- online resharding ---------------------------------------------------


def test_reshard_grow_preserves_keys(st):
    st.create_bucket("grow", shards=1)
    keys = {f"g{i:03d}" for i in range(60)}
    for k in keys:
        st.put_object("grow", k, k.encode())
    out = st.reshard_bucket("grow", 4)
    assert out["shards"] == 4 and out["gen"] == 1
    assert out["reshard"] is None          # marker cleared at cutover
    assert set(_keys(st, "grow")) == keys  # zero lost/dup/misrouted
    assert st.index.count("grow") == 60
    for k in sorted(keys)[:5]:
        assert bytes(st.get_object("grow", k)[0]) == k.encode()
    # old single-object index reaped
    from ceph_tpu.rados.client import RadosError
    with pytest.raises(RadosError):
        st.meta.stat("index.grow")


def test_reshard_shrink(st):
    assert st.reshard_bucket("grow", 2)["shards"] == 2
    assert st.index.count("grow") == 60


def test_reshard_under_concurrent_puts(st):
    """Writers keep mutating while the reshard copies: dual-write +
    tombstones must yield exactly the final key set, nothing lost,
    resurrected, or misrouted."""
    st.create_bucket("churn", shards=1)
    for i in range(50):
        st.put_object("churn", f"pre{i:03d}", b"0")
    stop = threading.Event()
    added, deleted = [], []

    def writer(wid):
        i = 0
        while not stop.is_set():
            k = f"live{wid}-{i:03d}"
            st.put_object("churn", k, b"1")
            added.append(k)
            if i % 3 == 2:
                st.delete_object("churn", k)
                deleted.append(k)
            i += 1

    threads = [threading.Thread(target=writer, args=(w,))
               for w in range(2)]
    for t in threads:
        t.start()
    try:
        out = st.reshard_bucket("churn", 4)
    finally:
        stop.set()
        for t in threads:
            t.join()
    assert out["shards"] == 4
    expect = ({f"pre{i:03d}" for i in range(50)} |
              set(added)) - set(deleted)
    assert set(_keys(st, "churn")) == expect
    # routing audit: every key sits in exactly the shard its hash says
    for k in sorted(expect):
        oid = f"index.churn.g1.{shard_of(k, 4)}"
        raw = st._cls(st.meta, oid, "dir_get", {"key": k})
        assert json.loads(raw.decode()) is not None


def test_reshard_interrupted_resumes(st):
    """A reshard that dies after entering dual-write (daemon kill)
    leaves a durable marker; the next sweep resumes and converges —
    including writes that happened while no copier was running."""
    st.create_bucket("crash", shards=1)
    for i in range(30):
        st.put_object("crash", f"c{i:03d}", b"x")
    st.resharder.start("crash", 4)        # dies before run(): marker only
    bmeta = st._bucket_meta("crash")
    assert bmeta["reshard"]["state"] == "dual"
    # writes during the outage dual-write old+new
    st.put_object("crash", "during-outage", b"y")
    st.delete_object("crash", "c001")
    # revived daemon's maintenance sweep picks the marker up
    stats = st.reshard_sweep()
    assert stats["resumed"] == 1
    assert (st._bucket_meta("crash") or {}).get("reshard") is None
    expect = {f"c{i:03d}" for i in range(30)} - {"c001"} | \
        {"during-outage"}
    assert set(_keys(st, "crash")) == expect
    assert st.reshard_status("crash")["shards"] == 4


def test_reshard_autoscale_trigger(st, monkeypatch):
    """Entry count past shards*rgw_max_objs_per_shard triggers the
    sweep's pow2 scale-up, capped by rgw_reshard_max_shards."""
    monkeypatch.setitem(st.conf._layers["override"],
                        "rgw_max_objs_per_shard", 10)
    st.create_bucket("auto", shards=1)
    for i in range(35):
        st.put_object("auto", f"a{i:03d}", b"z")
    stats = st.reshard_sweep()
    # other module buckets may cross the lowered threshold too; "auto"
    # must be among the resharded
    assert stats["started"] >= 1
    status = st.reshard_status("auto")
    assert status["shards"] == 4           # next_pow2(ceil(35/10))
    assert st.index.count("auto") == 35
    # everything under threshold now: a second sweep is a no-op
    assert st.reshard_sweep()["started"] == 0


def test_bucket_stats_and_limit_check(st):
    stats = st.bucket_stats("sh4")
    assert stats["shards"] == 4 and stats["objects"] == 39
    assert len(stats["shard_fill"]) == 4
    assert sum(stats["shard_fill"].values()) == 39
    perf = stats["perf"]
    assert sum(c["put"] for c in perf.values()) >= 40
    rows = st.bucket_limit_check()
    row = next(r for r in rows if r["bucket"] == "sh4")
    assert row["status"] == "OK" and row["objects"] == 39


# -- cls-atomic quota reservations (cross-process window closed) --------


def test_quota_gate_cross_store_no_overshoot(cluster):
    """Two RGWStore instances (= two gateway processes) racing the
    last quota slots: the cls_user reservation serializes admission
    on the user object, so the combined committed total can never
    exceed the quota — the old process-local pending pot could not
    guarantee this."""
    st1 = RGWStore(cluster.client())
    st2 = RGWStore(cluster.client())
    st1.create_bucket("qb", owner="alice")
    st1.set_user_quota("alice", max_objects=10)
    ok, denied = [], []

    def put(store, wid):
        for i in range(10):
            try:
                store.put_object("qb", f"q{wid}-{i}", b"d")
                ok.append(1)
            except RGWError as e:
                assert e.code == "QuotaExceeded"
                denied.append(1)

    ts = [threading.Thread(target=put, args=(s, w))
          for w, s in enumerate((st1, st2))]
    for t in ts:
        t.start()
    for t in ts:
        t.join()
    hdr = st1.get_user_header("alice")
    assert hdr["totals"]["objects"] == len(ok) <= 10
    assert len(ok) + len(denied) == 20
    # deletes free quota; a new put admits again
    st2.delete_object("qb", next(
        k for k in _keys(st1, "qb")))
    st1.put_object("qb", "q-refill", b"d")


def test_quota_negative_delta_always_admits(cluster):
    st1 = RGWStore(cluster.client())
    st1.create_bucket("qshrink", owner="bob")
    st1.put_object("qshrink", "big", b"x" * 1000)
    st1.set_user_quota("bob", max_bytes=1000)
    # shrinking overwrite admits even though totals are AT the limit
    st1.put_object("qshrink", "big", b"x" * 10)
    hdr = st1.get_user_header("bob")
    assert hdr["totals"]["bytes"] == 10
