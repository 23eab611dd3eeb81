"""The primary's per-object lock under the op tracker (ISSUE 36,
docs/TRACING.md "Phases"): two writers contending for one object —
`lat_obj_lock_wait` takes the loser's wait, `lat_obj_lock_hold` both
holds, the timeline gains the event `obj_lock_acquired`, and the op's
phases are what they were without it."""

import threading
import time

import pytest

from ceph_tpu.common.tracked_op import PHASE_ANCHORS, TrackedOp

HOLD_S = 0.3


@pytest.fixture(scope="module")
def cluster():
    from ceph_tpu.tools.vstart import Cluster
    with Cluster(n_osds=3) as c:
        yield c


def _primary(cluster, pool_name, oid_name):
    osdmap = cluster.osds[0].osdmap
    pool = osdmap.lookup_pool(pool_name)
    pgid = osdmap.object_to_pg(pool.id, oid_name)
    return cluster.osds[osdmap.pg_to_up_acting_osds(pgid)[3]]


def _lock_hists(osd):
    d = osd.op_tracker.perf.dump()
    return {k: (d[k]["count"], d[k]["sum"]) if k in d else (0, 0.0)
            for k in ("lat_obj_lock_wait", "lat_obj_lock_hold")}


def test_two_writers_on_one_object_split_into_wait_and_hold(cluster):
    client = cluster.client()
    client.create_pool("lockpool", "replicated", pg_num=4)
    io = client.open_ioctx("lockpool")
    io.write_full("contended", b"0" * 512)       # warm: pool, PG, conn
    osd = _primary(cluster, "lockpool", "contended")
    before = _lock_hists(osd)
    hist0 = len(osd.op_tracker.dump_historic_ops()["ops"])

    # the first op to take the lock keeps it HOLD_S longer
    real, first = osd._do_client_op, threading.Event()

    def slow_once(conn, msg, t0):
        if not first.is_set():
            first.set()
            time.sleep(HOLD_S)
        real(conn, msg, t0)

    osd._do_client_op = slow_once
    try:
        writers = [threading.Thread(
            target=io.write_full, args=("contended", bytes([i]) * 512))
            for i in (1, 2)]
        writers[0].start()
        assert first.wait(10)
        writers[1].start()
        for w in writers:
            w.join(30)
            assert not w.is_alive()
    finally:
        osd._do_client_op = real

    after = _lock_hists(osd)
    n_wait, s_wait = (after["lat_obj_lock_wait"][i]
                      - before["lat_obj_lock_wait"][i] for i in (0, 1))
    n_hold, s_hold = (after["lat_obj_lock_hold"][i]
                      - before["lat_obj_lock_hold"][i] for i in (0, 1))
    # one sample an op that took the lock, in each histogram
    assert (n_wait, n_hold) == (2, 2)
    # the winner held the lock HOLD_S; the loser arrived a little
    # after it and waited out the rest, then held it for its own op
    assert s_hold >= HOLD_S
    assert 0.5 * HOLD_S <= s_wait <= s_hold
    assert s_hold - HOLD_S < HOLD_S              # two ordinary holds

    ops = [t for t in list(osd.op_tracker._history)[hist0:]
           if t.op_type == "osd_op" and "contended" in t.desc]
    assert len(ops) == 2
    assert "obj_lock_acquired" not in {
        anchor for _, anchor in PHASE_ANCHORS["osd_op"]}
    for top in ops:
        names = [name for _, name in top.events]
        assert names.count("obj_lock_acquired") == 1
        assert names.index("dequeued") < \
            names.index("obj_lock_acquired")
        # the same timeline without the event: the same phases
        bare = TrackedOp(None, top.op_type, top.desc, top.trace)
        bare.initiated_at = top.initiated_at
        bare.completed_at = top.completed_at
        bare.events = [e for e in top.events
                       if e[1] != "obj_lock_acquired"]
        assert bare.phase_durations() == top.phase_durations()
        assert sum(dt for _, dt in top.phase_durations()) == \
            pytest.approx(top.completed_at - min(
                top.initiated_at, top.events[0][0]))
    # the loser's wait lies on its timeline, before the event
    waits = sorted(dict((n, dt) for n, dt in top.stage_durations())
                   ["obj_lock_acquired"] for top in ops)
    assert waits[1] >= 0.5 * HOLD_S > waits[0]


def test_untracked_op_takes_the_lock_without_a_sample(cluster):
    client = cluster.client()
    io = client.open_ioctx("lockpool")
    osd = _primary(cluster, "lockpool", "quiet")
    osd.op_tracker.enabled = False
    try:
        before = _lock_hists(osd)
        io.write_full("quiet", b"q" * 512)
        assert io.read("quiet", 512) == b"q" * 512
        assert _lock_hists(osd) == before
    finally:
        osd.op_tracker.enabled = True
