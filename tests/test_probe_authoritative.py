"""The primary's own shard answers object metadata for a PG that is
clean for its interval (docs/PIPELINE.md "Authoritative local shard"):
a create, an overwrite and a read of a missing object send no
`MOSDECSubOpRead`; in every state that casts doubt on the PG the probe
fan-out remains and finds what the peers hold; holders refuse the
sub-writes of a primary whose interval they have left.

Reference analogs: ECBackend::get_hash_info (the primary's own shard,
never a peer) and PG::can_discard_replica_op (replicas drop sub-ops
from before same_interval_since).
"""

import errno
import threading
import time

import numpy as np
import pytest

from ceph_tpu.ec import ErasureCodeError, ErasureCodePluginRegistry
from ceph_tpu.msg import messages as M
from ceph_tpu.osd.ec_backend import ECBackend, LocalShardBackend
from ceph_tpu.osd.ec_transaction import PGTransaction, shard_oid
from ceph_tpu.osd.ec_util import StripeInfo
from ceph_tpu.osd.pg_log import LogEntry, entry_to_wire
from ceph_tpu.osd.types import eversion_t, hobject_t, pg_t, spg_t
from ceph_tpu.rados.client import RadosError
from ceph_tpu.store import MemStore
from ceph_tpu.store.object_store import Transaction
from ceph_tpu.tools.vstart import Cluster

PROBE_COUNTERS = ("ec_probe_sweeps", "ec_probe_local_hits",
                  "ec_probe_local_authoritative_misses",
                  "ec_probe_remote_sweeps", "ec_probe_remote_reads")


def payload(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).integers(
        0, 256, n, dtype=np.uint8).tobytes()


class Led:
    """One EC PG (pool of pg_num 1, k2m1 on 4 OSDs) and its primary."""

    def __init__(self, cluster, io):
        self.cluster, self.io = cluster, io
        self.pgid = pg_t(io.pool_id, 0)

    @property
    def acting(self):
        return self.cluster.mon.osdmap.pg_to_up_acting_osds(self.pgid)[1]

    @property
    def osd(self):
        primary = self.cluster.mon.osdmap.pg_to_up_acting_osds(
            self.pgid)[3]
        return self.cluster.osds[primary]

    @property
    def state(self):
        return self.osd._get_pg(self.pgid)

    def hobj(self, name):
        return hobject_t(pool=self.io.pool_id, name=name)

    def clean(self, name="x") -> bool:
        st = self.state
        return self.osd._pg_clean_for_interval(
            self.pgid, st.backend.shards, self.hobj(name))

    def counters(self) -> dict:
        dump = self.state.backend.perf.dump()
        return {k: dump[k] for k in PROBE_COUNTERS}

    def subop_reads(self) -> int:
        """MOSDECSubOpRead frames every OSD has served so far."""
        return sum(o.perf.dump()["subop_r"]
                   for o in self.cluster.osds if o is not None)

    def recover(self) -> None:
        """One full recovery pass of the PG, as a retry would run it."""
        osd = self.osd
        osd._pgs_needing_recovery.add(self.pgid)
        osd._recover_ec_pg(self.pgid, list(self.acting), set())

    def drop_primary_shard(self, name) -> None:
        """The primary comes to lack an object its peers hold."""
        osd = self.osd
        shard = list(self.acting).index(osd.osd_id)
        txn = Transaction()
        txn.remove(shard_oid(self.hobj(name), shard))
        osd.store.queue_transactions(spg_t(self.pgid, shard), [txn])

    def rebuild_primary_shard(self, name) -> None:
        osd, be = self.osd, self.state.backend
        shard = list(self.acting).index(osd.osd_id)
        oid = self.hobj(name)
        be.recover_shard(oid, [shard], osd._make_recovery_push(
            self.pgid, list(self.acting), oid))


@pytest.fixture(scope="module")
def cluster():
    with Cluster(n_osds=4) as c:
        yield c


@pytest.fixture(scope="module")
def led(cluster):
    client = cluster.client()
    client.set_ec_profile("probe21", {
        "plugin": "jerasure", "k": "2", "m": "1",
        "stripe_unit": "1024"})
    client.create_pool("probe_ec", "erasure",
                       erasure_code_profile="probe21", pg_num=1)
    io = client.open_ioctx("probe_ec")
    cluster.wait_active_clean(timeout=60)
    pg = Led(cluster, io)
    assert pg.clean()
    return pg


def delta(before: dict, after: dict) -> dict:
    return {k: after[k] - before[k] for k in before}


# -- the clean PG: no metadata frame ------------------------------------------

def test_create_on_clean_pg_sends_no_sub_read(led):
    c0, r0 = led.counters(), led.subop_reads()
    led.io.write_full("fresh", payload(1, 5000))
    d = delta(c0, led.counters())
    assert led.subop_reads() == r0
    assert d["ec_probe_local_authoritative_misses"] >= 1
    assert d["ec_probe_remote_sweeps"] == 0
    assert d["ec_probe_remote_reads"] == 0
    assert d["ec_probe_sweeps"] == \
        d["ec_probe_local_hits"] + d["ec_probe_local_authoritative_misses"]
    assert led.io.read("fresh", 5000) == payload(1, 5000)


def test_overwrite_answers_from_the_local_hit(led):
    led.io.write_full("twice", payload(2, 3000))
    c0, r0 = led.counters(), led.subop_reads()
    led.io.write_full("twice", payload(3, 4000))
    d = delta(c0, led.counters())
    assert led.subop_reads() == r0
    assert d["ec_probe_local_hits"] >= 1
    assert d["ec_probe_local_authoritative_misses"] == 0
    assert d["ec_probe_remote_sweeps"] == 0
    assert led.io.read("twice", 4000) == payload(3, 4000)


@pytest.mark.parametrize("op", ["read", "stat"])
def test_missing_object_on_clean_pg_is_enoent_without_the_wire(led, op):
    c0, r0 = led.counters(), led.subop_reads()
    with pytest.raises(RadosError) as e:
        if op == "read":
            led.io.read("never_written", 10)
        else:
            led.io.stat("never_written")
    assert e.value.errno == errno.ENOENT
    d = delta(c0, led.counters())
    assert led.subop_reads() == r0
    assert d["ec_probe_remote_sweeps"] == 0


def test_append_and_partial_write_on_clean_pg_need_no_sub_read(led):
    """RMW metadata (the size an append lands at) is a local hit."""
    a, b = payload(4, 2048), payload(5, 700)
    led.io.write_full("grow", a)
    c0 = led.counters()
    led.io.append("grow", b)
    led.io.write("grow", b"Z" * 100, 10)
    d = delta(c0, led.counters())
    assert d["ec_probe_remote_sweeps"] == 0
    want = bytearray(a + b)
    want[10:110] = b"Z" * 100
    assert led.io.read("grow", len(want)) == bytes(want)


# -- every doubt keeps the fan-out --------------------------------------------

def _fresh_pg_state(pg):
    """(a) a primary whose recovery pass has not finished: the PGState
    of a new interval — peered by its first op, never yet clean."""
    pg.osd.pgs.pop(pg.pgid)


def _needs_recovery(pg):
    pg.osd._pgs_needing_recovery.add(pg.pgid)          # (b)


def _undersized(pg):
    pg.osd._pgs_undersized.add(pg.pgid)                # (c)


def _hole_written_around(pg):
    pg.state.backend.shards.degraded_shards.add(1)     # (c)
    pg.osd._pg_unclean(pg.pgid)


def _split_settling(pg):
    """(d) the PG is a split child whose parent may still hold it."""
    pg.osd._split_ancestry[pg.pgid] = pg_t(pg.pgid.pool, 7)


def _merge_settling(pg):
    """(d) dying merge children may still hold the PG's objects."""
    pool = pg.osd.osdmap.pools[pg.pgid.pool]
    pool.pg_num_max = pool.pg_num * 2


def _interval_change(pg):
    """(e) what _adopt_map does when the acting set changed."""
    st = pg.state
    st.needs_peer = True
    st.unclean()


def _recovery_pass_failed(pg):
    pg.osd._pg_unclean(pg.pgid)


def _restore(pg):
    osd = pg.osd
    osd._pgs_undersized.discard(pg.pgid)
    osd._split_ancestry.pop(pg.pgid, None)
    pool = osd.osdmap.pools[pg.pgid.pool]
    pool.pg_num_max = 0
    for o in osd.osdmap.osds.values():
        o.up = True
    pg.recover()
    assert pg.clean()


UNSAFE = {
    "a_new_primary_before_its_pass": _fresh_pg_state,
    "b_needs_recovery": _needs_recovery,
    "c_undersized": _undersized,
    "c_hole_written_around": _hole_written_around,
    "d_split_settling": _split_settling,
    "d_merge_settling": _merge_settling,
    "e_interval_change_rearms_peering": _interval_change,
    "recovery_pass_failed": _recovery_pass_failed,
}


@pytest.mark.parametrize("case", sorted(UNSAFE))
def test_fan_out_remains_in_every_unsafe_state(led, case):
    """Peers hold objects the primary lacks; in each state the probe
    still goes to them, so a following append lands after the old
    bytes and a partial write keeps them."""
    old = payload(hash(case) & 0xffff, 3000)
    tail, patch = payload(7, 500), b"P" * 64
    led.io.write_full(f"{case}.app", old)
    led.io.write_full(f"{case}.part", old)
    assert led.clean()
    try:
        led.drop_primary_shard(f"{case}.app")
        led.drop_primary_shard(f"{case}.part")
        UNSAFE[case](led)
        assert not led.clean()
        c0, r0 = led.counters(), led.subop_reads()
        led.io.append(f"{case}.app", tail)
        led.io.write(f"{case}.part", patch, 100)
        d = delta(c0, led.counters())
        assert led.subop_reads() > r0
        assert d["ec_probe_remote_sweeps"] >= 2
        assert d["ec_probe_local_authoritative_misses"] == 0
        # the primary's shard of both objects is still a hole: rebuild
        # it from the peers, whose bytes are what the ops preserved
        led.rebuild_primary_shard(f"{case}.app")
        led.rebuild_primary_shard(f"{case}.part")
        assert led.io.read(f"{case}.app", 3500) == old + tail
        want = bytearray(old)
        want[100:164] = patch
        assert led.io.read(f"{case}.part", 3000) == bytes(want)
    finally:
        _restore(led)


def test_acting_member_the_map_marks_down_casts_doubt(led):
    """(c) every acting member must be placed and up in the map the
    primary holds, whatever the acting list still says."""
    assert led.clean()
    peer = next(o for o in led.acting if o != led.osd.osd_id)
    led.osd.osdmap.osds[peer].up = False
    try:
        assert not led.clean()
    finally:
        led.osd.osdmap.osds[peer].up = True
    assert led.clean()


def test_clean_is_reached_again_only_by_a_recovery_pass(led):
    """(e) re-arming peering clears the fact; peering alone does not
    restore it, a finished recovery pass does."""
    assert led.clean()
    _interval_change(led)
    assert not led.clean()
    led.io.write_full("after_repeer", payload(8, 100))   # re-peers
    assert not led.state.needs_peer and not led.clean()
    led.recover()
    assert led.clean()
    c0 = led.counters()
    led.io.write_full("after_pass", payload(9, 100))
    assert delta(c0, led.counters())["ec_probe_remote_sweeps"] == 0


def test_a_pass_that_started_before_the_doubt_does_not_vouch(led):
    """clean_gen is the interval_gen the pass STARTED under."""
    assert led.clean()
    st = led.state
    started_under = st.interval_gen
    led.osd._pg_unclean(led.pgid)
    st.clean_gen = started_under        # what the stale pass would set
    assert not led.clean()
    _restore(led)


def test_pass_with_an_unreachable_acting_member_does_not_vouch(led):
    osd = led.osd
    led.osd._pg_unclean(led.pgid)
    osd._pgs_needing_recovery.add(led.pgid)
    peer = next(o for o in led.acting if o != osd.osd_id)
    osd._recover_ec_pg(led.pgid, list(led.acting), {peer})
    assert not led.clean()
    _restore(led)


def test_scrub_error_casts_doubt_and_repair_gets_the_real_hinfo(led):
    """A shard the primary lost on a clean PG: scrub's repair reads the
    hinfo from the peers (repair probes never trust a local miss), and
    the PG stops vouching until a recovery pass."""
    data = payload(10, 6000)
    led.io.write_full("rotted", data)
    assert led.clean()
    led.drop_primary_shard("rotted")
    out = led.osd._asok_scrub({"deep": True, "repair": True})
    rep = out[str(led.pgid)]
    assert rep["errors"] == [] and rep["repaired"] >= 1
    assert not led.clean()
    assert led.io.read("rotted", 6000) == data
    _restore(led)
    assert led.io.read("rotted", 6000) == data


# -- a real interval change, end to end ---------------------------------------

def test_new_primary_is_not_clean_until_its_pass_has_run():
    """(a) for real: the primary is marked out with recovery held
    back; the OSD that leads next has no finished pass, so its probes
    go to the wire; its pass pulls what the old holders have, and from
    then on it answers alone."""
    with Cluster(n_osds=4) as c:
        client = c.client()
        client.set_ec_profile("p21", {"plugin": "jerasure", "k": "2",
                                      "m": "1", "stripe_unit": "1024"})
        client.create_pool("mv", "erasure", erasure_code_profile="p21",
                           pg_num=1)
        io = client.open_ioctx("mv")
        c.wait_active_clean(timeout=60)
        pg = Led(c, io)
        old = payload(11, 3000)
        io.write_full("kept", old)
        first = pg.osd.osd_id
        for o in c.osds:
            o.recovery_enabled = False
        r, _ = client.mon_command({"prefix": "osd out", "id": first})
        assert r == 0
        deadline = time.time() + 30
        while time.time() < deadline and (
                first in pg.acting or
                pg.osd.osdmap.epoch < c.mon.osdmap.epoch):
            time.sleep(0.1)
        assert first not in pg.acting and pg.osd.osd_id != first
        r0 = pg.subop_reads()
        io.write_full("during", payload(12, 2000))
        assert not pg.clean()
        assert pg.subop_reads() > r0
        now = pg.counters()
        assert now["ec_probe_remote_sweeps"] >= 1
        assert now["ec_probe_local_authoritative_misses"] == 0
        for o in c.osds:
            o.recovery_enabled = True
        pg.recover()
        c.wait_active_clean(timeout=60)
        assert pg.clean()
        assert io.read("kept", 3000) == old
        assert io.read("during", 2000) == payload(12, 2000)
        c0, r0 = pg.counters(), pg.subop_reads()
        io.write_full("new_here", payload(13, 100))
        assert pg.subop_reads() == r0
        assert delta(c0, pg.counters())[
            "ec_probe_local_authoritative_misses"] >= 1


# -- the holders' fence -------------------------------------------------------

def _ghost_sub_write(pg, sender, target_osd, shard, name, epoch, tid):
    """A client write's sub-op for `shard`, sent by `sender`; returns
    the holder's reply."""
    oid = pg.hobj(name)
    spg = spg_t(pg.pgid, shard)
    txn = Transaction()
    txn.write(shard_oid(oid, shard), 0,
              np.frombuffer(b"ghost" * 10, dtype=np.uint8))
    version = eversion_t(epoch, 10_000 + tid)
    box, ev = {}, threading.Event()
    sender.raw_write_waiters[(spg, tid)] = \
        lambda m: (box.update(msg=m), ev.set())
    sender.conn_to_osd(target_osd).send_message(M.MOSDECSubOpWrite(
        spg, tid, version, txn,
        log_entries=[entry_to_wire(LogEntry(version, oid))]))
    assert ev.wait(10)
    return box["msg"]


def test_holder_refuses_sub_write_of_an_interval_it_has_left(led):
    """After the primary's activation a shard holder refuses (ESTALE,
    nothing applied) a versioned sub-write from another OSD stamped
    before that activation; the activating primary's own pass."""
    osd = led.osd
    acting = list(led.acting)
    outsider = next(o for o in led.cluster.osds
                    if o.osd_id not in acting)
    shard = next(s for s, o in enumerate(acting) if o != osd.osd_id)
    holder = led.cluster.osds[acting[shard]]
    spg = spg_t(led.pgid, shard)
    les = holder._shard_log(spg).info.last_epoch_started
    assert les > 0 and holder._activated_by[spg] == osd.osd_id
    reply = _ghost_sub_write(led, outsider, holder.osd_id, shard,
                             "ghost", les - 1, 900_001)
    assert reply.result == -errno.ESTALE
    assert holder.stat_shard(spg, led.hobj("ghost"), False).result != 0
    # the same stamp from the OSD that activated the shard is served
    reply = _ghost_sub_write(led, osd, holder.osd_id, shard,
                             "ghost2", les - 1, 900_002)
    assert reply.result == 0
    assert holder.stat_shard(spg, led.hobj("ghost2"), False).result == 0
    # clean up the half-written ghost and the log entries it left
    txn = Transaction()
    txn.remove(shard_oid(led.hobj("ghost2"), shard))
    holder.store.queue_transactions(spg, [txn])


def test_refused_sub_write_fails_the_op_with_eagain():
    """The primary's side of the fence: a shard that refuses fails the
    op (never acks it as durable) and carries EAGAIN to the reply."""
    class Refusing(LocalShardBackend):
        def sub_write(self, shard, txn, on_commit, **kw):
            if shard == 1:
                on_commit(shard, ErasureCodeError(errno.EAGAIN, "left"))
                return
            super().sub_write(shard, txn, on_commit, **kw)

    codec = ErasureCodePluginRegistry.instance().factory(
        "jerasure", {"k": "2", "m": "1"})
    store = MemStore()
    store.mount()
    be = ECBackend(codec, StripeInfo(128, 64),
                   Refusing(store, pg_t(1, 0), 3))
    txn = PGTransaction()
    txn.write(hobject_t(pool=1, name="o"), 0,
              np.zeros(128, dtype=np.uint8))
    done = []
    op = be.submit_transaction(txn, eversion_t(1, 1),
                               lambda: done.append(1))
    assert done and op.state == "failed"
    assert getattr(op.error, "errno", None) == errno.EAGAIN
    assert be.perf.dump()["ec_drain_errors"] == 1
