"""RBD overwrites on an erasure-coded data pool while one OSD is down
and not yet out (deployment `rbd_ec42_osd8_down1`), against the
benchmark's plain reference (benchmark/references/rbd_image_ec_down1.py:
numpy GF(2^8) Cauchy encode and decode, google_crc32c; nothing of
ceph_tpu).  For each of the six shard positions of one object's PG
lost in turn: 4 KiB overwrites through `Image.write`, several in
flight on one stripe and on one object; the image read back through
the degraded cluster; what the live stores hold and what the dead one
kept; the counters of docs/PIPELINE.md "Overwrites on a degraded PG" at the
values the op mix implies; and after revive and active+clean all six
shards again.  And the program's decode against the reference's for
every survivor set of size k."""

import importlib.util
import itertools
import os
import threading

import numpy as np
import pytest

from ceph_tpu.ec import ErasureCodePluginRegistry
from ceph_tpu.ec.interface import Profile
from ceph_tpu.osd.ec_util import CHUNK_CRC_KEY, HINFO_KEY, HashInfo
from ceph_tpu.osd.types import NO_GEN
from ceph_tpu.rbd import RBD, Image
from ceph_tpu.rbd.image import _data
from ceph_tpu.tools.vstart import Cluster

K, M, SU = 4, 2, 4096
ORDER = 16                      # 64 KiB objects: four k4m2 stripes
OBJECTS = 4
BLOCK = 4096
PROFILE = {"plugin": "jax", "technique": "cauchy", "k": str(K),
           "m": str(M), "stripe_unit": str(SU)}


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "references",
        "rbd_image_ec_down1.py")
    spec = importlib.util.spec_from_file_location(
        "rbd_image_ec_down1", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


class Deployment:
    """8 OSDs, a k4m2 data pool and a replicated metadata pool, one
    prefilled image on one exclusive handle, and the model of it."""

    def __init__(self, cluster: Cluster):
        self.c = cluster
        self.client = cluster.client()
        self.client.set_ec_profile("k4m2", dict(PROFILE))
        self.client.create_pool("ecdata", "erasure",
                                erasure_code_profile="k4m2", pg_num=4)
        self.client.create_pool("meta", "replicated", size=3, pg_num=4)
        cluster.wait_active_clean(timeout=120)
        meta = self.client.open_ioctx("meta")
        size = OBJECTS << ORDER
        RBD(meta).create("img", size, order=ORDER, data_pool="ecdata")
        self.image = Image(meta, "img", exclusive=True)
        self.model = REF.ImageModel(size, ORDER)
        self.writes = 0
        for n in range(OBJECTS):
            data = np.random.default_rng([11, n]).bytes(1 << ORDER)
            self.image.write(n << ORDER, data)
            self.model.fill(n << ORDER, data)
        self.pool_id = self.client.objecter.osdmap.lookup_pool(
            "ecdata").id

    def pg_and_acting(self, n: int):
        osdmap = self.c.mon.osdmap
        pgid = osdmap.object_to_pg(self.pool_id, _data("img", n))
        return pgid, list(osdmap.pg_to_up_acting_osds(pgid)[1])

    def shard(self, n: int, shard: int, osd_id: int):
        """(bytes, attrs) of one shard object as it lies in a store
        (a dead daemon's store is still there to be read)."""
        store = self.c.osds[osd_id].store
        pgid, _ = self.pg_and_acting(n)
        name = _data("img", n)
        for cid in store.list_collections():
            if cid.pgid != pgid or cid.shard != shard:
                continue
            for g in store.list_objects(cid):
                if g.hobj.name == name and not g.hobj.snap \
                        and g.generation == NO_GEN:
                    return store.read(cid, g), store.getattrs(cid, g)
        return None

    def counters(self, pgid) -> dict:
        """Sums over all OSDs of the PG's `ec.<pgid>` set (a PG whose
        primary died has one on the old primary and one on the new)."""
        out: dict = {}
        for osd in self.c.osds:
            for key, val in osd.cct.perf.dump().get(
                    f"ec.{pgid}", {}).items():
                if isinstance(val, dict):
                    val = val.get("count", 0)
                out[key] = out.get(key, 0) + val
        return out

    def overwrite(self, blocks: list[int], writers: int = 8) -> None:
        """Every block once, `writers` writes in flight."""
        todo = iter(blocks)
        lock = threading.Lock()
        errors = []

        def writer() -> None:
            while True:
                with lock:
                    b = next(todo, None)
                    if b is None:
                        return
                    self.writes += 1
                    seq = self.writes
                data = b.to_bytes(8, "little") \
                    + np.random.default_rng([12, seq]).bytes(BLOCK - 8)
                try:
                    self.image.write(b * BLOCK, data)
                except Exception as e:  # noqa: BLE001 — reported
                    errors.append(repr(e))
                    return
                with lock:
                    self.model.overlay(b * BLOCK, data)

        threads = [threading.Thread(target=writer)
                   for _ in range(writers)]
        for t in threads:
            t.start()
        for t in threads:
            t.join(timeout=120)
        assert not any(t.is_alive() for t in threads), \
            "an overwrite hung on the degraded cluster"
        assert not errors, errors

    def assert_reads_back(self) -> None:
        got = np.frombuffer(self.image.read(0, self.model.size),
                            dtype=np.uint8)
        assert np.array_equal(got, self.model.bytes)

    def assert_shards(self, n: int, model_bytes, shards_of: dict,
                      what: str) -> None:
        """Shards {position: osd} of object n hold the reference's
        encoding of `model_bytes`, with the crc of their own bytes."""
        want, want_crcs = REF.expected_shards(model_bytes, K, M, SU)
        for shard, osd_id in shards_of.items():
            hit = self.shard(n, shard, osd_id)
            assert hit is not None, f"{what}: object {n} shard {shard}"
            data, attrs = hit
            assert np.array_equal(data, want[shard]), \
                f"{what}: object {n} shard {shard} bytes"
            hinfo = HashInfo.decode(attrs[HINFO_KEY])
            if CHUNK_CRC_KEY in attrs:
                carried = int.from_bytes(attrs[CHUNK_CRC_KEY], "little")
            else:
                carried = hinfo.cumulative_shard_hashes[shard]
            assert carried == want_crcs[shard], \
                f"{what}: object {n} shard {shard} crc"
            assert hinfo.logical_size == len(model_bytes)


@pytest.fixture(scope="module")
def dep():
    with Cluster(n_osds=8) as c:
        d = Deployment(c)
        yield d
        d.image.close()


# the blocks one case writes: a whole stripe of object 0 at once (four
# writes in flight on ONE stripe), more of object 0 (one object), and
# a block of every other object
def _case_blocks(case: int) -> list[int]:
    per_obj = (1 << ORDER) // BLOCK
    stripe = (case % 4) * 4
    mine = [stripe, stripe + 1, stripe + 2, stripe + 3,
            (stripe + 5) % per_obj, (stripe + 10) % per_obj]
    return mine + [n * per_obj + (case * 2 + n) % per_obj
                   for n in range(1, OBJECTS)]


@pytest.mark.parametrize("lost", range(K + M))
def test_overwrites_with_one_shard_position_lost(dep, lost):
    pgid0, acting0 = dep.pg_and_acting(0)
    victim = acting0[lost]
    acting = {n: dep.pg_and_acting(n)[1] for n in range(OBJECTS)}
    at_kill = dep.model.bytes.copy()
    dep.c.kill_osd(victim)
    dep.c.mark_osd_down(victim)
    dep.c.wait_active(timeout=60)
    before = dep.counters(pgid0)
    blocks = _case_blocks(lost)
    dep.overwrite(blocks)
    after = dep.counters(pgid0)
    dep.assert_reads_back()

    def delta(key: str) -> int:
        return after.get(key, 0) - before.get(key, 0)

    # object 0's PG lost shard `lost`: its six overwrites reconstruct
    # where that is a data shard and never where it is parity
    ops = sum(1 for b in blocks
              if dep.pg_and_acting(b * BLOCK >> ORDER)[0] == pgid0)
    assert delta("ec_rmw_reads") == ops
    # a pre-read asks all k data shards: with one of them lost every
    # one reconstructs, from ONE parity shard; with parity lost none
    want = ops if lost < K else 0
    assert delta("ec_rmw_reconstructs") == want
    assert delta("ec_rmw_parity_reads") == want
    assert delta("ec_reconstruct_reads") >= want   # + the read-back's
    assert delta("ec_sub_writes_sent") == ops * (K + M - 1)
    assert delta("ec_sub_writes_skipped_down") == ops

    # the live shards follow the model, the dead store kept what it
    # had at the kill
    osize = 1 << ORDER
    for n in range(OBJECTS):
        live = {s: o for s, o in enumerate(acting[n]) if o != victim}
        dead = {s: o for s, o in enumerate(acting[n]) if o == victim}
        dep.assert_shards(n, dep.model.object(n), live, "live")
        dep.assert_shards(n, at_kill[n * osize:(n + 1) * osize], dead,
                          "dead")
        # any k live shards give the object (the reference's decode)
        for use in itertools.islice(
                itertools.combinations(sorted(live), K), 3):
            got = REF.object_from_shards(
                {s: dep.shard(n, s, live[s])[0] for s in use},
                K, M, SU, osize)
            assert np.array_equal(got, dep.model.object(n))

    dep.c.revive_osd(victim)
    dep.c.wait_active_clean(timeout=120)
    for n in range(OBJECTS):
        dep.assert_shards(n, dep.model.object(n),
                          dict(enumerate(acting[n])), "recovered")
    dep.assert_reads_back()


@pytest.mark.parametrize(
    "survivors", list(itertools.combinations(range(K + M), K)),
    ids=lambda s: "".join(map(str, s)))
def test_decode_chunks_equals_the_reference_decode(survivors):
    codec = ErasureCodePluginRegistry.instance().factory(
        "jax", Profile(dict(PROFILE)))
    rng = np.random.default_rng([13, *survivors])
    obj = rng.integers(0, 256, 8 * K * SU, dtype=np.uint8)
    full, _ = REF.expected_shards(obj, K, M, SU)
    erased = [s for s in range(K + M) if s not in survivors]
    dense = full.copy()
    dense[erased] = 0
    got = np.asarray(codec.decode_chunks(dense, erased))
    assert np.array_equal(got, full)
    want = REF.decode_data({s: full[s] for s in survivors}, K, M)
    assert np.array_equal(got[:K], want)
    assert np.array_equal(want, full[:K])


@pytest.mark.parametrize("how", ["write_full", "overwrite"])
def test_a_shard_that_missed_writes_is_rebuilt_on_revive(how):
    """An object that exists on all six shards is rewritten while one
    data shard's holder is down.  The holder comes back with the OLD
    shard object: peering must count it missing (the shard's log ends
    before the write) and recovery must rebuild it — before this was
    so, the revived OSD served the old bytes to every healthy read."""
    with Cluster(n_osds=6) as c:
        client = c.client()
        client.set_ec_profile("k4m2", dict(PROFILE))
        client.create_pool("ec", "erasure",
                           erasure_code_profile="k4m2", pg_num=1)
        c.wait_active_clean(timeout=120)
        io = client.open_ioctx("ec")
        old = np.random.default_rng(21).bytes(64 * 1024)
        new = np.random.default_rng(22).bytes(64 * 1024)
        io.write_full("obj", old)
        pgid = c.mon.osdmap.object_to_pg(io.pool_id, "obj")
        acting = list(c.mon.osdmap.pg_to_up_acting_osds(pgid)[1])
        victim = acting[1]
        c.kill_osd(victim)
        c.mark_osd_down(victim)
        if how == "write_full":
            io.write_full("obj", new)
        else:
            # shard 1's chunk of the first stripe, and parity
            io.write("obj", new[SU:2 * SU], SU)
            new = old[:SU] + new[SU:2 * SU] + old[2 * SU:]
        assert io.read("obj") == new
        c.revive_osd(victim)
        c.wait_active_clean(timeout=120)
        assert io.read("obj") == new
        want, _ = REF.expected_shards(new, K, M, SU)
        store = c.osds[victim].store
        cid = next(cid for cid in store.list_collections()
                   if cid.pgid == pgid and cid.shard == 1)
        head = next(g for g in store.list_objects(cid)
                    if g.hobj.name == "obj" and g.generation == NO_GEN)
        assert np.array_equal(store.read(cid, head), want[1])


def test_a_partial_write_the_victim_held_is_undone_on_revive():
    """The holder of a data shard dies with an overwrite applied that
    no other shard ever saw (its generation kept, its entry logged):
    the survivors never had it, go on writing ANOTHER object, and never
    touch this one again.  On revive the entry is in nobody's log but
    the victim's: it must be rolled back there — the head is the
    reference's shard again, the kept generation is gone — and not
    served to the healthy read that follows."""
    from ceph_tpu.osd.pg_log import (LogEntry, LogOp, RollbackInfo,
                                     entry_to_wire)
    from ceph_tpu.osd.types import (eversion_t, ghobject_t, hobject_t,
                                    spg_t)
    from ceph_tpu.store.object_store import Transaction
    with Cluster(n_osds=6) as c:
        client = c.client()
        client.set_ec_profile("k4m2", dict(PROFILE))
        client.create_pool("ec", "erasure",
                           erasure_code_profile="k4m2", pg_num=1)
        c.wait_active_clean(timeout=120)
        io = client.open_ioctx("ec")
        rng = np.random.default_rng(31)
        old, other = rng.bytes(64 * 1024), rng.bytes(64 * 1024)
        io.write_full("obj", old)
        pgid = c.mon.osdmap.object_to_pg(io.pool_id, "obj")
        acting = list(c.mon.osdmap.pg_to_up_acting_osds(pgid)[1])
        victim = c.osds[acting[1]]
        spg = spg_t(pgid, 1)
        hobj = hobject_t(pool=pgid.pool, name="obj")
        goid = ghobject_t(hobj, shard=1)
        at = victim._shard_log(spg).info.last_update
        torn = eversion_t(at.epoch, at.version + 1)
        txn = Transaction()
        txn.clone(goid, ghobject_t(hobj, torn.version, 1))
        txn.write(goid, 0, np.frombuffer(rng.bytes(SU), dtype=np.uint8))
        victim.apply_sub_write(spg, txn, [entry_to_wire(LogEntry(
            torn, hobj, LogOp.MODIFY, RollbackInfo(
                kept_generation=torn.version, extents=[(0, SU)])))],
            torn, None)
        want, _ = REF.expected_shards(old, K, M, SU)
        assert not np.array_equal(victim.store.read(spg, goid), want[1])
        c.kill_osd(victim.osd_id)
        c.mark_osd_down(victim.osd_id)
        io.write_full("other", other)
        io.write("other", other[:SU], SU)
        assert io.read("obj") == old
        c.revive_osd(victim.osd_id)
        c.wait_active_clean(timeout=120)
        store = c.osds[victim.osd_id].store
        mine = {(g.hobj.name, g.generation)
                for g in store.list_objects(spg)}
        assert ("obj", NO_GEN) in mine
        assert not [g for name, g in mine
                    if name == "obj" and g != NO_GEN]
        assert np.array_equal(store.read(spg, goid), want[1])
        assert io.read("obj") == old
        other = other[:SU] + other[:SU] + other[2 * SU:]
        assert io.read("other") == other
        want, _ = REF.expected_shards(other, K, M, SU)
        assert np.array_equal(store.read(
            spg, ghobject_t(hobject_t(pool=pgid.pool, name="other"),
                            shard=1)), want[1])


def test_tracing_of_reconstructing_overwrites(dep):
    """Five overwrites of object 0, one at a time, while the holder
    of its data shard 2 is down: every span, histogram, counter and
    event the degraded pre-read adds, at the count that implies."""
    from ceph_tpu.common import spans
    from ceph_tpu.parallel.launch_queue import ECLaunchQueue
    pgid0, acting0 = dep.pg_and_acting(0)
    victim = acting0[2]
    degraded = {f"ec.{dep.pg_and_acting(n)[0]}" for n in range(OBJECTS)
                if victim in dep.pg_and_acting(n)[1]}
    dep.c.kill_osd(victim)
    dep.c.mark_osd_down(victim)
    dep.c.wait_active(timeout=60)
    primary = dep.c.osds[dep.c.mon.osdmap.pg_to_up_acting_osds(
        pgid0)[3]]
    queue = ECLaunchQueue.host_get()

    def snapshot() -> dict:
        out = {f"{name}_n": row[2] for name, row in
               spans.table().items()}
        out.update(dep.counters(pgid0))
        out.update({k: v for k, v in queue.perf.dump().items()
                    if not isinstance(v, dict)})
        return out

    before = snapshot()
    n_ops = 5
    for i in range(n_ops):
        dep.overwrite([(3 + i) % 16], writers=1)
    after = snapshot()

    def delta(key: str) -> int:
        return after.get(key, 0) - before.get(key, 0)

    for key in ("ec.rmw_parity_read_n", "ec.reconstruct_n",
                "ec.decode_wait_n", "ec_rmw_reconstructs",
                "ec_rmw_parity_reads", "ec_reconstruct_reads",
                "lat_ec_rmw_reconstruct", "lat_ec_decode_wait",
                "ec_host_decode_launches", "ec_host_decode_runs",
                "ec_sub_writes_skipped_down"):
        assert delta(key) == n_ops, key
    assert delta("ec_sub_writes_sent") == n_ops * (K + M - 1)
    # the rows launched per kind, unpadded: a decode reads the k
    # survivor rows of one 4 KiB-a-shard stripe and writes the lost
    # shard and the parity shard it did not read; a plain launch k in,
    # m out
    assert delta("ec_host_launch_in_bytes.decode") == n_ops * K * SU
    assert delta("ec_host_launch_out_bytes.decode") == n_ops * 2 * SU
    assert delta("ec_host_launch_in_bytes.plain_encode") \
        == n_ops * K * SU
    assert delta("ec_host_launch_out_bytes.plain_encode") \
        == n_ops * M * SU
    # the event lies inside `prepare`, and the phases after `wire_in`
    # still add up to the op
    seen = 0
    for op in primary.op_tracker.dump_historic_ops()["ops"]:
        if op["type"] != "osd_op":
            continue
        names = [e["event"] for e in op["events"]]
        if "ec_rmw_reconstruct" not in names:
            continue
        assert names.index("dequeued") \
            < names.index("ec_rmw_reconstruct") \
            < names.index("ec_encode_launch")
        top = next(t for t in primary.op_tracker.get_historic(
            op["trace_id"]) if t.op_type == "osd_op")
        phases = dict(top.phase_durations())
        assert set(phases) == {"wire_in", "queue_wait", "prepare",
                               "encode", "fanout_commit"}
        assert sum(v for k, v in phases.items() if k != "wire_in") \
            == pytest.approx(top.duration(), abs=1e-6)
        seen += 1
    assert seen >= n_ops
    # the share of the pool's PGs that serve degraded, from `perf dump`
    holes = {}
    for osd in dep.c.osds:
        if osd.osd_id == victim:
            continue
        for name, vals in osd.cct.perf.dump().items():
            if name.startswith(f"ec.{pgid0.pool}."):
                holes[name] = vals["ec_acting_holes"]
    assert {name for name, h in holes.items() if h > 0} >= degraded
    assert holes[f"ec.{pgid0}"] == 1
    dep.c.revive_osd(victim)
    dep.c.wait_active_clean(timeout=120)
    dep.assert_reads_back()
