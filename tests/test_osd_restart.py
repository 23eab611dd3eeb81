"""One OSD restarted under writes on a k=8 m=3 pool (deployment
`ec83_rados12_restart1`): stopped and marked down between batches of
writes, started again on the store it had, recovered while writers go
on; every acknowledged object read back and every shard of it — the
restarted OSD's included — compared with the benchmark's plain
reference (benchmark/references/ec_cauchy_crc32c.py: numpy GF(2^8)
Cauchy encode, google_crc32c; nothing of ceph_tpu), bytes and the k+m
crcs of every shard's hinfo.  Also: the grouped recovery decode is
one launch a capped slice of a geometry group, and the prewarm plan
covers the widths it launches for a 4 MiB object at k=8."""

import importlib.util
import os
import threading
import time

import numpy as np
import pytest

from ceph_tpu.common.util import next_pow2
from ceph_tpu.ec import ErasureCodePluginRegistry
from ceph_tpu.msg import messages as msgs
from ceph_tpu.osd.ec_backend import ECBackend, LocalShardBackend
from ceph_tpu.osd.ec_transaction import PGTransaction, shard_oid
from ceph_tpu.osd.ec_util import HINFO_KEY, HashInfo, StripeInfo
from ceph_tpu.osd.types import eversion_t, hobject_t, pg_t, spg_t
from ceph_tpu.ops import prewarm
from ceph_tpu.parallel.launch_queue import (RECOVERY_DECODE_MAX_OBJECTS,
                                            ECLaunchQueue,
                                            recovery_decode_cap,
                                            recovery_decode_widths)
from ceph_tpu.store import MemStore
from ceph_tpu.store.object_store import Transaction
from ceph_tpu.tools.vstart import Cluster

K, M, SU = 8, 3, 4096
PROFILE = {"plugin": "jax", "technique": "cauchy", "k": str(K),
           "m": str(M), "stripe_unit": str(SU)}
OBJ = 2 * K * SU                # two stripes: 8 KiB a shard
VICTIM = 1


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "references",
        "ec_cauchy_crc32c.py")
    spec = importlib.util.spec_from_file_location("ec_cauchy_crc32c",
                                                  path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def _payload(n: int) -> bytes:
    return np.random.default_rng([41, n]).bytes(OBJ)


def _shard(cluster, pool_id, name, shard, osd_id):
    """(bytes, hinfo) of one shard object as it lies in the store."""
    store = cluster.osds[osd_id].store
    for cid in store.list_collections():
        if cid.pgid.pool != pool_id or cid.shard != shard:
            continue
        for goid in store.list_objects(cid):
            if goid.hobj.name == name and goid.generation == \
                    shard_oid(goid.hobj, shard).generation:
                raw = store.getattrs(cid, goid).get(HINFO_KEY)
                return store.read(cid, goid), \
                    HashInfo.decode(raw) if raw is not None else None
    return None, None


def test_restart_under_writes_keeps_every_acked_object():
    with Cluster(n_osds=12, heartbeat_interval=0.5) as c:
        client = c.client()
        client.set_ec_profile("k8m3", dict(PROFILE))
        client.create_pool("ec", "erasure", erasure_code_profile="k8m3",
                           pg_num=8)
        c.wait_active_clean(timeout=120)
        pool_id = c.mon.osdmap.lookup_pool("ec").id
        acked: dict[int, float] = {}
        lock = threading.Lock()
        counter = iter(range(10 ** 6))
        slowest = [0.0]

        def write_some(count: int, writers: int = 4) -> None:
            def writer():
                io = client.open_ioctx("ec")
                for _ in range(count):
                    with lock:
                        n = next(counter)
                    t0 = time.perf_counter()
                    io.write_full(f"obj{n}", _payload(n))
                    t1 = time.perf_counter()
                    with lock:
                        acked[n] = t1
                        slowest[0] = max(slowest[0], t1 - t0)
            threads = [threading.Thread(target=writer)
                       for _ in range(writers)]
            for t in threads:
                t.start()
            for t in threads:
                t.join()

        write_some(4)                               # healthy
        # writes in flight when the OSD stops
        during = threading.Thread(target=write_some, args=(6,))
        during.start()
        time.sleep(0.05)
        c.kill_osd(VICTIM)
        c.mark_osd_down(VICTIM)
        during.join()
        t_down = time.perf_counter()
        write_some(4)                               # while it is down
        t_up = time.perf_counter()
        # writers keep going through the restart and the recovery
        after = threading.Thread(target=write_some, args=(6,))
        after.start()
        c.revive_osd(VICTIM)
        after.join()
        c.wait_active_clean(timeout=120)

        # a write in flight at the stop or the restart is answered
        # once the map says so, not after an attempt's timeout
        assert slowest[0] < 10.0
        io = client.open_ioctx("ec")
        for n in acked:
            assert io.read(f"obj{n}", OBJ) == _payload(n), n
        osdmap = c.mon.osdmap
        rebuilt = 0
        for n, t_ack in acked.items():
            name = f"obj{n}"
            want, crcs = REF.expected_shards(_payload(n), K, M, SU)
            pgid = osdmap.object_to_pg(pool_id, name)
            acting = osdmap.pg_to_up_acting_osds(pgid)[1]
            assert len(acting) == K + M
            for shard, osd_id in enumerate(acting):
                data, hinfo = _shard(c, pool_id, name, shard, osd_id)
                assert data is not None, (name, shard, osd_id)
                np.testing.assert_array_equal(data, want[shard])
                assert hinfo is not None and \
                    list(hinfo.cumulative_shard_hashes) == crcs, \
                    (name, shard, osd_id)
                if osd_id == VICTIM and t_down <= t_ack <= t_up:
                    rebuilt += 1
        # objects acked while the OSD was down, rebuilt on it
        assert rebuilt > 0
        launches = sum(
            sets.get("ec_recovery_decode_launches", 0)
            for osd in c.osds for name, sets in osd.cct.perf.dump().items()
            if name.startswith("ec."))
        assert 0 < launches <= rebuilt


# -- the grouped recovery decode ---------------------------------------------

@pytest.mark.parametrize("objects,chunk", [(5, 4096), (6, 32768)])
def test_recovery_decode_is_one_launch_a_capped_slice(objects, chunk):
    """Every object of a PG lost the same shard: the group decodes in
    one launch while its objects' shards fit under the cap (4 KiB
    shards: 64 KiB), and in one launch per RECOVERY_DECODE_MAX_OBJECTS
    objects above it (32 KiB shards: 128 KiB) — each rebuilt shard
    equal to the reference's encoding."""
    codec = ErasureCodePluginRegistry.instance().factory(
        "jax", {k: str(v) for k, v in PROFILE.items()})
    store = MemStore()
    store.mount()
    shards = LocalShardBackend(store, pg_t(1, 0), K + M)
    queue = ECLaunchQueue(window_us=0)
    try:
        be = ECBackend(codec, StripeInfo(K * chunk, chunk), shards,
                       launch_queue=queue)
        size = K * chunk
        oids = []
        for n in range(objects):
            txn = PGTransaction()
            oid = hobject_t(pool=1, name=f"o{n}")
            txn.write(oid, 0, np.frombuffer(_payload_of(n, size),
                                            dtype=np.uint8))
            done = []
            be.submit_transaction(txn, eversion_t(1, n + 1),
                                  lambda: done.append(1))
            assert done
            oids.append(oid)
        lost = 3
        for oid in oids:
            t = Transaction()
            t.remove(shard_oid(oid, lost))
            store.queue_transactions(shards.cids[lost], [t])
        pushed = {}
        res = be.recover_shards_batch(
            [(oid, [lost]) for oid in oids],
            lambda oid: lambda s, data, hinfo: pushed.__setitem__(
                (oid.name, s), np.asarray(data).copy()))
        assert all(err is None for err in res.values()), res
        per_launch = recovery_decode_cap(chunk) // chunk
        want_launches = -(-objects // per_launch)
        perf = be.perf.dump()
        assert perf["ec_recovery_decode_launches"] == want_launches
        assert queue.status()["decode_launches"] == want_launches
        assert perf["ec_recovery_decode_bytes"] == sum(
            K * len(part) * chunk
            for part in _slices(objects, per_launch))
        for n, oid in enumerate(oids):
            want, _ = REF.expected_shards(_payload_of(n, size), K, M,
                                          chunk)
            np.testing.assert_array_equal(pushed[(oid.name, lost)],
                                          want[lost])
    finally:
        queue.close()


def _payload_of(n: int, size: int) -> bytes:
    return np.random.default_rng([43, n]).bytes(size)


def _slices(objects: int, per: int) -> list[list[int]]:
    return [list(range(i, min(i + per, objects)))
            for i in range(0, objects, per)]


# -- prewarm -----------------------------------------------------------------

def test_prewarm_plans_the_recovery_decode_of_a_4mib_object():
    """A 4 MiB object at k=8 has 512 KiB shards: its recovery launches
    512 KiB, 1 MiB and 2 MiB (1, 2, 3 or 4 objects, pow2-padded), one
    erased shard — and a plan of those widths seeds the bucket of
    every such launch, whichever shard is lost."""
    shard = (4 << 20) // K
    widths = recovery_decode_widths(shard)
    assert widths == [shard, 2 * shard, 4 * shard]
    assert {next_pow2(j * shard)
            for j in range(1, RECOVERY_DECODE_MAX_OBJECTS + 1)} \
        == set(widths)
    codec = ErasureCodePluginRegistry.instance().factory(
        "jax", {k: str(v) for k, v in PROFILE.items()})
    plan = prewarm.PrewarmPlan(codec, run_shapes=[], plain_widths=[],
                               decode_widths=widths,
                               decode_erasures=[(0,)])
    planned = set(plan.planned_buckets())
    for w in widths:
        for lost in range(K + M):
            # the launch queue's bucket of a decode rebuilding `lost`
            assert f"d:e{lost}:w{w}" in planned
    # below the cap the widths are the default plan's
    small = recovery_decode_widths(4096)
    assert set(small) <= {2048 << j for j in range(6)}


def test_host_perf_sets_survive_their_owners_restart():
    """The host-level perf sets (device profiler, wire ledger, spans
    table, launch queue) are exported by exactly one daemon; when that
    daemon restarts they are exported again, by exactly one daemon —
    the next built, or the next to wire the launch queue — instead of
    vanishing from the host's `perf dump`."""
    def owners(cluster, name):
        return [osd.osd_id for osd in cluster.osds
                if name in osd.cct.perf.dump()]

    with Cluster(n_osds=4, heartbeat_interval=0.5) as c:
        client = c.client()
        client.set_ec_profile("k2m1", {"plugin": "jax", "k": "2",
                                       "m": "1", "stripe_unit": "4096"})
        client.create_pool("ec", "erasure", erasure_code_profile="k2m1",
                           pg_num=4)
        c.wait_active_clean(timeout=60)
        io = client.open_ioctx("ec")
        io.write_full("before", b"x" * 8192)
        sets = ("host_spans", "ec_host_queue")
        before = {name: owners(c, name) for name in sets}
        assert all(len(ids) == 1 for ids in before.values()), before
        for victim in sorted({ids[0] for ids in before.values()}):
            c.kill_osd(victim)
            c.mark_osd_down(victim)
            c.revive_osd(victim)
            c.wait_active_clean(timeout=60)
        io.write_full("after", b"y" * 8192)
        assert io.read("after", 8192) == b"y" * 8192
        after = {name: owners(c, name) for name in sets}
        assert all(len(ids) == 1 for ids in after.values()), after


def test_a_late_round_of_an_older_interval_leaves_the_shard_to_the_new():
    """An interim primary's peering round, begun before the map that
    gives a restarted OSD its PGs back, can still reach a shard after
    the returning primary peered it.  Its log query must not fence the
    new primary's writes (they would be refused until the next map
    change: the client's ops fail), and its rollback must not undo
    what the newer round left on the shard."""
    with Cluster(n_osds=4, heartbeat_interval=0.5) as c:
        client = c.client()
        client.set_ec_profile("k2m1", {"plugin": "jax", "k": "2",
                                       "m": "1", "stripe_unit": "4096"})
        client.create_pool("ec", "erasure", erasure_code_profile="k2m1",
                           pg_num=4)
        c.wait_active_clean(timeout=60)
        io = client.open_ioctx("ec")
        io.write_full("obj", b"x" * 8192)          # peers its PG
        osdmap = c.mon.osdmap
        pgid = osdmap.object_to_pg(osdmap.lookup_pool("ec").id, "obj")
        _, acting, _, primary = osdmap.pg_to_up_acting_osds(pgid)
        shard = next(s for s, o in enumerate(acting) if o != primary)
        holder, spg = acting[shard], spg_t(pgid, shard)
        late = next(o.osd_id for o in c.osds
                    if o.osd_id not in (primary, holder))
        older = osdmap.epoch - 1
        head = c.osds[holder]._shard_log(spg).info.last_update
        assert c.osds[late]._peer_rpc(holder, spg, msgs.MPGLogQuery,
                                      epoch=older) is not None
        assert c.osds[late]._peer_rpc(holder, spg, msgs.MPGLogRollback,
                                      v=eversion_t(), epoch=older
                                      ).removed == []
        assert c.osds[holder]._shard_log(spg).info.last_update == head
        t0 = time.perf_counter()
        io.write_full("obj", b"y" * 8192)
        assert time.perf_counter() - t0 < 2.0      # no EAGAIN storm
        assert io.read("obj", 8192) == b"y" * 8192
        # a round of the current interval still takes the shard over
        assert c.osds[late]._peer_rpc(holder, spg, msgs.MPGLogQuery,
                                      epoch=osdmap.epoch) is not None
        assert c.osds[holder]._peering_by[spg] == (late, osdmap.epoch)


def test_a_round_whose_pg_moved_away_while_it_waited_queries_no_shard():
    """A peering round that waits for the ops of the ending interval
    can outlive its OSD's lead: the map moves the PG's primary away
    meanwhile (an interim primary when the restarted OSD comes back).
    It must end there, not read the new interval's acting set and
    epoch and ask the shards for their logs as if it led it — that
    fences the new primary's writes at the new primary's own epoch."""
    with Cluster(n_osds=4, heartbeat_interval=0.5) as c:
        client = c.client()
        client.set_ec_profile("k2m1", {"plugin": "jax", "k": "2",
                                       "m": "1", "stripe_unit": "4096"})
        client.create_pool("ec", "erasure", erasure_code_profile="k2m1",
                           pg_num=4)
        c.wait_active_clean(timeout=60)
        io = client.open_ioctx("ec")
        io.write_full("obj", b"x" * 8192)          # peers its PG
        osdmap = c.mon.osdmap
        pgid = osdmap.object_to_pg(osdmap.lookup_pool("ec").id, "obj")
        _, acting, _, primary = osdmap.pg_to_up_acting_osds(pgid)
        spare = next(o.osd_id for o in c.osds if o.osd_id not in acting)
        lead = c.osds[primary]
        shards = [(c.osds[osd], spg_t(pgid, s))
                  for s, osd in enumerate(acting) if osd != primary]
        before = [daemon._peering_by.get(spg) for daemon, spg in shards]
        state = lead.pgs[pgid]
        go = threading.Event()
        state.backend.wait_idle = lambda timeout: go.wait(30)
        result = []
        waiting = threading.Thread(
            target=lambda: result.append(lead._peer_pg(pgid, state)))
        waiting.start()
        r, _ = client.mon_command({"prefix": "osd pg-upmap-items",
                                   "pgid": [pgid.pool, pgid.seed],
                                   "pairs": [[primary, spare]]})
        assert r == 0
        deadline = time.monotonic() + 10
        while pgid in lead.pgs and time.monotonic() < deadline:
            time.sleep(0.01)
        assert pgid not in lead.pgs                # the lead moved
        go.set()
        waiting.join(30)
        assert result == [False]
        # it asked no shard: each is held as the first round left it,
        # or by the new primary's own round
        now = [daemon._peering_by.get(spg) for daemon, spg in shards]
        assert all(held == first or held[0] == spare
                   for held, first in zip(now, before)), (now, before)
        t0 = time.perf_counter()
        io.write_full("obj", b"y" * 8192)
        assert time.perf_counter() - t0 < 5.0
        assert io.read("obj", 8192) == b"y" * 8192
        assert all(daemon._peering_by[spg][0] == spare
                   for daemon, spg in shards if daemon.osd_id != spare)
