"""cls_user-backed account stats + quota enforcement and the
cls_log-backed usage log (reference src/cls/user, src/cls/log,
rgw_quota.cc, rgw_usage.cc)."""

import errno
import json
import time

import pytest

from ceph_tpu.cls import ClsError, cls_user
from ceph_tpu.rgw.store import RGWError, RGWStore
from ceph_tpu.tools.vstart import Cluster


@pytest.fixture(scope="module")
def store():
    with Cluster(n_osds=3) as c:
        yield RGWStore(c.client(), usage_log=True)


def test_user_stats_track_current_view(store):
    store.create_bucket("acct", owner="alice")
    store.put_object("acct", "a", b"x" * 100,
                     extra={"owner": "alice"})
    store.put_object("acct", "b", b"y" * 50, extra={"owner": "alice"})
    hdr = store.get_user_header("alice")
    assert hdr["totals"] == {"objects": 2, "bytes": 150}
    # overwrite: object count stays, bytes reflect the new size
    store.put_object("acct", "a", b"z" * 10, extra={"owner": "alice"})
    hdr = store.get_user_header("alice")
    assert hdr["totals"] == {"objects": 2, "bytes": 60}
    store.delete_object("acct", "a")
    hdr = store.get_user_header("alice")
    assert hdr["totals"] == {"objects": 1, "bytes": 50}


def test_quota_enforced(store):
    store.create_bucket("qb", owner="bob")
    store.set_user_quota("bob", max_objects=2, max_bytes=1000)
    store.put_object("qb", "one", b"a" * 100, extra={"owner": "bob"})
    store.put_object("qb", "two", b"b" * 100, extra={"owner": "bob"})
    # object quota: third object refused
    with pytest.raises(RGWError) as ei:
        store.put_object("qb", "three", b"c", extra={"owner": "bob"})
    assert ei.value.code == "QuotaExceeded"
    # overwrite stays within object count: allowed
    store.put_object("qb", "one", b"a" * 200, extra={"owner": "bob"})
    # byte quota: growing past 1000 refused
    with pytest.raises(RGWError) as ei:
        store.put_object("qb", "two", b"b" * 2000,
                         extra={"owner": "bob"})
    assert ei.value.code == "QuotaExceeded"
    # delete frees quota
    store.delete_object("qb", "one")
    store.put_object("qb", "three", b"c", extra={"owner": "bob"})


def test_multipart_counts_against_quota(store):
    store.create_bucket("mpq", owner="carol")
    store.set_user_quota("carol", max_bytes=100_000)
    uid = store.init_multipart("mpq", "big")
    store.upload_part("mpq", "big", uid, 1, b"A" * 70000)
    store.upload_part("mpq", "big", uid, 2, b"B" * 40000)
    parts = [(n, m["etag"]) for n, m in store.list_parts("mpq", "big",
                                                         uid)]
    with pytest.raises(RGWError) as ei:       # 110000 > 100000
        store.complete_multipart("mpq", "big", uid, parts,
                                 extra={"owner": "carol"})
    assert ei.value.code == "QuotaExceeded"
    store.set_user_quota("carol", max_bytes=-1)
    store.complete_multipart("mpq", "big", uid, parts,
                             extra={"owner": "carol"})
    hdr = store.get_user_header("carol")
    assert hdr["totals"]["bytes"] == 110000


def test_usage_log_records_and_trims(store):
    store.create_bucket("ub", owner="dave")
    store.put_object("ub", "k1", b"data", extra={"owner": "dave"})
    store.delete_object("ub", "k1")
    out = store.get_usage()
    ops = [(e["user"], e["op"]) for _k, _ts, e in out["entries"]
           if e["bucket"] == "ub"]
    assert ("dave", "put_obj") in ops
    assert ("dave", "delete_obj") in ops
    # trim everything so far; the log drains
    last_ts = max(ts for _k, ts, _e in out["entries"])
    store.trim_usage(last_ts + 1.0)
    left = [e for _k, _ts, e in store.get_usage()["entries"]
            if e["bucket"] == "ub"]
    assert left == []


def test_cross_owner_overwrite_moves_charge(store):
    """B overwriting A's object must release A's charge and charge B —
    not leave A paying for bytes that no longer exist."""
    store.create_bucket("xo", owner="ann")
    store.put_object("xo", "doc", b"a" * 1000, extra={"owner": "ann"})
    assert store.get_user_header("ann")["totals"] == \
        {"objects": 1, "bytes": 1000}
    store.put_object("xo", "doc", b"b" * 10, extra={"owner": "ben"})
    assert store.get_user_header("ann")["totals"] == \
        {"objects": 0, "bytes": 0}
    assert store.get_user_header("ben")["totals"] == \
        {"objects": 1, "bytes": 10}


def test_version_surgery_adjusts_current_view(store):
    """Deleting the CURRENT version releases its quota charge (and a
    promoted predecessor re-charges at its own size)."""
    store.create_bucket("vs", owner="zoe")
    store.set_versioning("vs", "Enabled")
    store.put_object("vs", "k", b"1" * 100, extra={"owner": "zoe"})
    store.put_object("vs", "k", b"2" * 300, extra={"owner": "zoe"})
    assert store.get_user_header("zoe")["totals"]["bytes"] == 300
    cur_vid = store.head_object("vs", "k")["version_id"]
    store.delete_object_version("vs", "k", cur_vid)
    # predecessor (100 bytes) promoted to current
    assert store.get_user_header("zoe")["totals"] == \
        {"objects": 1, "bytes": 100}
    vid2 = store.head_object("vs", "k")["version_id"]
    store.delete_object_version("vs", "k", vid2)
    assert store.get_user_header("zoe")["totals"] == \
        {"objects": 0, "bytes": 0}


def test_failed_delete_logs_nothing(store):
    """A 404 delete on a Suspended bucket must not feed the usage log
    or the stats (failed ops leave no ledger entries)."""
    store.create_bucket("sus", owner="flo")
    store.set_versioning("sus", "Suspended")
    before = len(store.get_usage(max_entries=10000)["entries"])
    with pytest.raises(RGWError):
        store.delete_object("sus", "never-existed")
    after = len(store.get_usage(max_entries=10000)["entries"])
    assert after == before
    assert store.get_user_header("flo")["totals"] == \
        {"objects": 0, "bytes": 0}


def test_bucket_delete_drops_stats_row(store):
    store.create_bucket("gone", owner="erin")
    store.put_object("gone", "x", b"1", extra={"owner": "erin"})
    assert store.get_user_header("erin")["buckets"].get("gone")
    store.delete_object("gone", "x")
    store.delete_bucket("gone")
    assert "gone" not in store.get_user_header("erin")["buckets"]


# -- a reservation exists only where a limit does; the stats retire it ------
# (cls_user on a fake context that counts the object's rewrites)


class CountingCtx:
    """What a class method needs of its context: the object's body,
    and a count of the rewrites it staged."""

    def __init__(self, doc: dict | None = None):
        self.body = json.dumps(doc).encode() if doc is not None else b""
        self.writes = 0

    def read(self) -> bytes:
        return self.body

    def write_full(self, data: bytes) -> None:
        self.body = bytes(data)
        self.writes += 1

    def call(self, method: str, **req) -> dict:
        out = getattr(cls_user, method)(self, json.dumps(req).encode())
        return json.loads(out.decode()) if out else {}

    @property
    def doc(self) -> dict:
        return json.loads(self.body.decode())


def _limited(**quota) -> CountingCtx:
    """An account object whose quota was set, its rewrites counted
    from here."""
    ctx = CountingCtx()
    ctx.call("set_quota", **quota)
    ctx.writes = 0
    return ctx


@pytest.mark.parametrize("quota", [None, {"max_objects": -1,
                                          "max_bytes": -1}],
                         ids=["fresh_object", "limits_unset"])
def test_unlimited_reserve_returns_no_token_and_stages_no_write(quota):
    ctx = CountingCtx() if quota is None else _limited(**quota)
    assert ctx.call("reserve", objects=1, bytes=65536, ttl=30.0) == \
        {"token": ""}
    assert ctx.writes == 0


def test_unlimited_put_sequences_rewrite_the_object_once_each():
    ctx = CountingCtx()
    n = 7
    for _ in range(n):
        token = ctx.call("reserve", objects=1, bytes=100, ttl=30.0)["token"]
        assert token == ""
        # the store sends the stats with no token and no release
        ctx.call("add_stats", bucket="b", objects=1, bytes=100)
    assert ctx.writes == n
    assert "pending" not in ctx.doc
    assert ctx.call("get_header")["totals"] == \
        {"objects": n, "bytes": 100 * n}


def test_unlimited_reserve_purges_stale_reservations_in_one_write():
    old = time.time() - 3600.0
    ctx = CountingCtx({
        "buckets": {}, "quota": {"max_objects": -1, "max_bytes": -1},
        "pending": {"t1": {"objects": 1, "bytes": 5, "ts": old},
                    "t2": {"objects": 1, "bytes": 5, "ts": old}}})
    assert ctx.call("reserve", objects=1, bytes=1, ttl=30.0) == \
        {"token": ""}
    assert ctx.writes == 1 and "pending" not in ctx.doc
    assert ctx.call("reserve", objects=1, bytes=1, ttl=30.0) == \
        {"token": ""}
    assert ctx.writes == 1


def test_unlimited_reserve_leaves_live_reservations_to_their_owners():
    """A limit taken away while a reserved write is in flight: the
    entry is neither consulted nor rewritten away; its own stats call
    retires it."""
    ctx = _limited(max_objects=10)
    token = ctx.call("reserve", objects=1, bytes=1, ttl=30.0)["token"]
    ctx.call("set_quota", max_objects=-1)
    ctx.writes = 0
    assert ctx.call("reserve", objects=1, bytes=1, ttl=30.0) == \
        {"token": ""}
    assert ctx.writes == 0 and list(ctx.doc["pending"]) == [token]
    ctx.call("add_stats", bucket="b", objects=1, bytes=1, token=token)
    assert ctx.writes == 1 and "pending" not in ctx.doc


@pytest.mark.parametrize("quota", [{"max_objects": 10},
                                   {"max_bytes": 10_000},
                                   {"max_objects": 10,
                                    "max_bytes": 10_000}],
                         ids=["objects", "bytes", "both"])
def test_limited_reservation_is_retired_by_the_stats_in_one_write(quota):
    ctx = _limited(**quota)
    token = ctx.call("reserve", objects=1, bytes=100, ttl=30.0)["token"]
    assert token and ctx.writes == 1
    assert ctx.doc["pending"][token]["bytes"] == 100
    ctx.call("add_stats", bucket="b", objects=1, bytes=100, token=token)
    assert ctx.writes == 2
    doc = ctx.doc
    assert "pending" not in doc
    assert doc["buckets"]["b"] == {"objects": 1, "bytes": 100}
    # the release of a consumed token stages nothing
    ctx.call("release", token=token)
    assert ctx.writes == 2


def test_stats_with_a_token_leave_other_reservations_alone():
    ctx = _limited(max_objects=10)
    mine = ctx.call("reserve", objects=1, bytes=1, ttl=30.0)["token"]
    theirs = ctx.call("reserve", objects=1, bytes=1, ttl=30.0)["token"]
    ctx.call("add_stats", bucket="b", objects=1, bytes=1, token=mine)
    assert list(ctx.doc["pending"]) == [theirs]
    # stats without a token (a delete, a bucket removal) touch none
    ctx.call("add_stats", bucket="b", objects=-1, bytes=-1)
    assert list(ctx.doc["pending"]) == [theirs]
    # an unknown token (TTL-expired) is no error and the delta lands
    ctx.call("add_stats", bucket="b", objects=1, bytes=7, token="gone")
    assert ctx.doc["buckets"]["b"] == {"objects": 1, "bytes": 7}
    ctx.call("release", token=theirs)
    assert "pending" not in ctx.doc


@pytest.mark.parametrize("quota,first,second,message", [
    ({"max_objects": 2}, (1, 10), (2, 10), "object quota"),
    ({"max_bytes": 100}, (1, 60), (1, 60), "byte quota"),
], ids=["objects", "bytes"])
def test_edquot_counts_totals_plus_live_reservations(quota, first,
                                                     second, message):
    ctx = _limited(**quota)
    token = ctx.call("reserve", objects=first[0], bytes=first[1],
                     ttl=30.0)["token"]
    writes = ctx.writes
    with pytest.raises(ClsError) as ei:
        ctx.call("reserve", objects=second[0], bytes=second[1], ttl=30.0)
    assert ei.value.errno == errno.EDQUOT and message in str(ei.value)
    assert ctx.writes == writes        # a denial rewrites nothing
    # landed, the same growth still counts — once, not twice
    ctx.call("add_stats", bucket="b", objects=first[0], bytes=first[1],
             token=token)
    with pytest.raises(ClsError):
        ctx.call("reserve", objects=second[0], bytes=second[1], ttl=30.0)
    assert ctx.call("reserve", objects=1, bytes=40, ttl=30.0)["token"]


# -- the same rule through the store, on a live cluster ---------------------

@pytest.fixture(scope="module")
def plain(store):
    """A second gateway's store on the same cluster, usage log off as
    by default: its ops are the PUT's alone."""
    return RGWStore(store.client)


def _tallied(store, fn):
    """Run `fn` as one request: -> (RADOS ops, account rewrites)."""
    req = store.begin_request(time.perf_counter())
    try:
        fn()
    finally:
        store.end_request()
    return req.ops, req.account_writes


def _gates(store) -> tuple[int, int]:
    dump = store.perf.dump()
    return dump["rgw_quota_gates"], dump["rgw_quota_reservations"]


def test_limited_users_put_costs_two_account_rewrites(plain):
    plain.create_bucket("lim", owner="lena")
    plain.set_user_quota("lena", max_objects=100, max_bytes=1 << 20)
    g0, r0 = _gates(plain)
    ops, writes = _tallied(plain, lambda: plain.put_object(
        "lim", "k", b"x" * 100))
    # the gateway's six less its authorization read plus the store's
    # own read of the bucket row: bucket row, index look-up, reserve,
    # write, index add, stats (+ the token)
    assert (ops, writes) == (6, 2)
    assert _gates(plain) == (g0 + 1, r0 + 1)
    hdr = plain.get_user_header("lena")
    assert "pending" not in hdr
    assert hdr["totals"] == {"objects": 1, "bytes": 100}
    # an unlimited user's: the same ops, the gate rewrites nothing
    plain.create_bucket("unl", owner="uma")
    ops, writes = _tallied(plain, lambda: plain.put_object(
        "unl", "k", b"x" * 100))
    assert (ops, writes) == (6, 1)
    assert _gates(plain) == (g0 + 2, r0 + 1)
    assert "pending" not in plain.get_user_header("uma")


def test_same_size_overwrite_releases_what_no_stats_took(plain):
    plain.create_bucket("same", owner="sam")
    plain.set_user_quota("sam", max_bytes=1000)
    plain.put_object("same", "k", b"a" * 100)
    # zero delta: no stats call goes out, so the release does; the
    # bucket row and the index entry are read once each, as for a
    # fresh key
    ops, writes = _tallied(plain, lambda: plain.put_object(
        "same", "k", b"b" * 100))
    assert (ops, writes) == (6, 2)
    hdr = plain.get_user_header("sam")
    assert "pending" not in hdr
    assert hdr["totals"] == {"objects": 1, "bytes": 100}


def test_put_that_fails_after_the_gate_leaves_no_reservation(
        store, monkeypatch):
    store.create_bucket("dies", owner="dee")
    store.set_user_quota("dee", max_objects=5)

    def refuse(*_a, **_kw):
        raise OSError("the data pool is away")

    monkeypatch.setattr(store.data, "write_full", refuse)
    with pytest.raises(OSError):
        store.put_object("dies", "k", b"x")
    monkeypatch.undo()
    hdr = store.get_user_header("dee")
    assert "pending" not in hdr
    assert hdr["totals"] == {"objects": 0, "bytes": 0}
    # and the slot it held is free again
    for i in range(5):
        store.put_object("dies", f"k{i}", b"x")
    with pytest.raises(RGWError):
        store.put_object("dies", "k5", b"x")
    assert "pending" not in store.get_user_header("dee")


def test_cross_owner_overwrite_retires_the_new_owners_reservation(store):
    store.create_bucket("xq", owner="olga")
    store.put_object("xq", "doc", b"a" * 500, extra={"owner": "olga"})
    store.set_user_quota("olga", max_bytes=10_000)
    store.set_user_quota("nina", max_bytes=10_000)
    _, writes = _tallied(store, lambda: store.put_object(
        "xq", "doc", b"b" * 20, extra={"owner": "nina"}))
    # reserve and stats on nina's object, the old charge off olga's
    assert writes == 3
    for user, totals in (("olga", {"objects": 0, "bytes": 0}),
                         ("nina", {"objects": 1, "bytes": 20})):
        hdr = store.get_user_header(user)
        assert "pending" not in hdr and hdr["totals"] == totals


def test_multipart_complete_retires_its_reservation(store):
    store.create_bucket("mpr", owner="mia")
    store.set_user_quota("mia", max_bytes=100_000)
    uid = store.init_multipart("mpr", "big")
    store.upload_part("mpr", "big", uid, 1, b"A" * 7000)
    parts = [(n, m["etag"]) for n, m in store.list_parts("mpr", "big",
                                                         uid)]
    g0, r0 = _gates(store)
    store.complete_multipart("mpr", "big", uid, parts)
    assert _gates(store) == (g0 + 1, r0 + 1)
    hdr = store.get_user_header("mia")
    assert "pending" not in hdr and hdr["totals"]["bytes"] == 7000


def test_set_quota_after_unlimited_puts_decides_from_landed_totals(store):
    store.create_bucket("late", owner="lou")
    for i in range(3):
        store.put_object("late", f"k{i}", b"x" * 10)
    assert "pending" not in store.get_user_header("lou")
    store.set_user_quota("lou", max_objects=4)
    store.put_object("late", "k3", b"x" * 10)           # 4 of 4
    with pytest.raises(RGWError) as ei:
        store.put_object("late", "k4", b"x" * 10)
    assert ei.value.code == "QuotaExceeded"
    # a limit set below current usage denies growth and nothing else
    store.set_user_quota("lou", max_objects=2)
    store.put_object("late", "k0", b"y" * 5)            # shrinking
    store.delete_object("late", "k1")
    hdr = store.get_user_header("lou")
    assert "pending" not in hdr
    assert hdr["totals"] == {"objects": 3, "bytes": 25}
