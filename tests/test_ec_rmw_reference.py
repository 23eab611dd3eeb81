"""The EC backend's read-modify-write half against the benchmark's
plain reference (benchmark/references/rbd_image_ec.py: numpy GF(2^8)
Cauchy encode stripe by stripe, google_crc32c; nothing of ceph_tpu):
seeded overwrites through the cluster path — objecter, messenger,
primary, ECBackend pre-read / overlay / plain parity launch,
generations, shard-side chunk_crc — and then what the OSDs' stores
hold: shard bytes, the crc each shard carries, sizes, the generations
left behind; with the counters of docs/PIPELINE.md "Overwrites" read
at exact values."""

import importlib.util
import os
import threading

import numpy as np
import pytest

from ceph_tpu.ec import ErasureCodePluginRegistry
from ceph_tpu.ec.interface import Profile
from ceph_tpu.osd.ec_backend import ECBackend, LocalShardBackend
from ceph_tpu.osd.ec_transaction import PGTransaction
from ceph_tpu.osd.ec_util import (CHUNK_CRC_KEY, HINFO_KEY, HashInfo,
                                  StripeInfo)
from ceph_tpu.osd.types import NO_GEN, eversion_t, hobject_t, pg_t
from ceph_tpu.rbd import RBD, Image
from ceph_tpu.store import MemStore
from ceph_tpu.tools.vstart import Cluster

SU = 4096
GEOMETRIES = {"k4m2": (4, 2), "k2m1": (2, 1)}


def _reference():
    path = os.path.join(os.path.dirname(os.path.dirname(
        os.path.abspath(__file__))), "benchmark", "references",
        "rbd_image_ec.py")
    spec = importlib.util.spec_from_file_location("rbd_image_ec", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


REF = _reference()


def payload(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).bytes(n)


@pytest.fixture(scope="module")
def cluster():
    with Cluster(n_osds=6) as c:
        client = c.client()
        for name, (k, m) in GEOMETRIES.items():
            client.set_ec_profile(name, {
                "plugin": "jax", "technique": "cauchy", "k": str(k),
                "m": str(m), "stripe_unit": str(SU)})
            client.create_pool(name, "erasure",
                               erasure_code_profile=name, pg_num=1)
        client.create_pool("meta", "replicated", size=3, pg_num=2)
        c.wait_active_clean(timeout=120)
        yield c, client


class Pool:
    """One single-PG EC pool: its client handle, its stores, and the
    sums of its counters over every OSD."""

    def __init__(self, cluster, name: str):
        self.c, self.client = cluster
        self.name = name
        self.k, self.m = GEOMETRIES[name]
        self.io = self.client.open_ioctx(name)
        self.pgid = pg_t(self.io.pool_id, 0)

    def shards(self, oid: str) -> dict:
        """{shard: (store, cid, head ghobject, [generation ghobjects],
        shard log)} of object `oid`, as the acting set holds it."""
        acting = self.c.mon.osdmap.pg_to_up_acting_osds(self.pgid)[1]
        out = {}
        for shard, osd_id in enumerate(acting):
            osd = self.c.osds[osd_id]
            cid = next(cid for cid in osd.store.list_collections()
                       if cid.pgid == self.pgid and cid.shard == shard)
            mine = [g for g in osd.store.list_objects(cid)
                    if g.hobj.name == oid and not g.hobj.snap]
            out[shard] = (osd.store, cid,
                          next(g for g in mine if g.generation == NO_GEN),
                          [g for g in mine if g.generation != NO_GEN],
                          osd.shard_logs[cid])
        return out

    def counters(self) -> dict:
        """Sums over all OSDs: the PG's `ec.<pgid>` set (it lives on
        the primary) and the shard-side counters of every `osd.N`."""
        out: dict = {}
        for osd in self.c.osds:
            for set_name, vals in osd.cct.perf.dump().items():
                if set_name == f"ec.{self.pgid}" or \
                        set_name == f"osd.{osd.osd_id}":
                    for key, val in vals.items():
                        if isinstance(val, dict):
                            val = val.get("count", 0)
                        out[key] = out.get(key, 0) + val
        return out

    def assert_matches_reference(self, oid: str, model: bytes) -> None:
        want, want_crcs = REF.expected_shards(model, self.k, self.m, SU)
        for shard, (store, cid, head, _, _) in self.shards(oid).items():
            assert np.array_equal(store.read(cid, head), want[shard]), \
                f"shard {shard} bytes"
            attrs = store.getattrs(cid, head)
            assert int.from_bytes(attrs[CHUNK_CRC_KEY], "little") \
                == want_crcs[shard], f"shard {shard} chunk_crc"
            hinfo = HashInfo.decode(attrs[HINFO_KEY])
            assert hinfo.logical_size == len(model)
            assert hinfo.total_chunk_size == want.shape[1]
            assert hinfo.invalidated


def stripes_read(off: int, n: int, width: int) -> set[int]:
    """The stripes a write inside an object must read back: its head
    and tail stripes where it covers them only partly."""
    out = set()
    if off % width:
        out.add(off // width)
    if (off + n) % width:
        out.add((off + n) // width)
    return out


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_seeded_overwrites_match_the_reference(cluster, geometry):
    pool = Pool(cluster, geometry)
    k, m = pool.k, pool.m
    width = k * SU
    nstripes = 8
    oid = f"obj_{geometry}"
    model = bytearray(payload(1, nstripes * width))
    pool.io.write_full(oid, bytes(model))
    rng = np.random.default_rng([7, k])
    writes = []
    for _ in range(6):          # one chunk of one stripe: fio's 4 KiB
        writes.append((int(rng.integers(0, nstripes * k)) * SU, SU))
    for _ in range(6):          # 512 B inside a chunk
        writes.append((int(rng.integers(0, nstripes * width // 512))
                       * 512, 512))
    for _ in range(4):          # straddling a stripe boundary
        s = int(rng.integers(1, nstripes))
        writes.append((s * width - int(rng.integers(1, SU)),
                       int(rng.integers(SU, 2 * SU))))
    writes.append((2 * width, width))       # a whole stripe: no read
    # head in one stripe, tail two stripes on: two separate pre-reads
    # inside one written extent
    writes.append((width // 2, 2 * width))
    before = pool.counters()
    for i, (off, n) in enumerate(writes):
        data = payload(100 + i, n)
        pool.io.write(oid, data, offset=off)
        model[off:off + n] = data
    after = pool.counters()
    delta = {key: after[key] - before.get(key, 0) for key in after}

    assert pool.io.read(oid, len(model)) == bytes(model)
    pool.assert_matches_reference(oid, bytes(model))

    n_writes, n_shards = len(writes), k + m
    shard_bytes = nstripes * SU
    read_sets = [stripes_read(off, n, width) for off, n in writes]
    assert delta["ec_rmw_read_bytes"] == \
        sum(len(s) for s in read_sets) * width
    # adjacent stripes are read as one extent, separate ones as two
    assert delta["ec_rmw_reads"] == sum(
        0 if not s else 1 if max(s) - min(s) <= 1 else 2
        for s in read_sets)
    assert delta["lat_ec_rmw_read"] == sum(1 for s in read_sets if s)
    assert delta["ec_rmw_cache_hit_bytes"] == 0
    assert delta["ec_plain_drains"] == n_writes
    assert delta["ec_drain_submits"] == n_writes
    assert delta["ec_fused_kernel_drains"] == 0
    # the rollback generation is O(object): every overwrite clones
    # the WHOLE shard object on each of the k+m shards
    assert delta["ec_shard_clone_bytes"] == \
        n_writes * n_shards * shard_bytes
    # chunk_crc is O(write): each shard hashes the chunk bytes it
    # wrote (one stripe unit per stripe the write touches) and patches
    # its attr from them.  None of these writes forces a whole
    # re-hash: all lie inside the object, none changes its size, and
    # the first one has no attr yet but seeds the patch from the
    # write_full's hinfo (valid, and as long as the shard object)
    stripes_written = sum(-(-(off + n) // width) - off // width
                          for off, n in writes)
    assert delta["ec_shard_chunk_crc_bytes"] == \
        stripes_written * n_shards * SU
    assert delta["ec_shard_chunk_crc_patches"] == n_writes * n_shards
    assert delta["ec_shard_chunk_crc_rehashes"] == 0
    # each sub-write tells its shard that the write before it is
    # rolled forward: that write's generation goes, the last one stays
    assert delta["ec_shard_generations_trimmed"] == \
        (n_writes - 1) * n_shards
    for shard, (store, cid, _, gens, slog) in pool.shards(oid).items():
        bound = slog.log.rollforward_to.version
        assert len(gens) == 1, f"shard {shard}: {gens}"
        assert gens[0].generation > bound


@pytest.mark.parametrize("geometry", sorted(GEOMETRIES))
def test_two_in_flight_on_one_stripe(cluster, geometry):
    """Writers of different chunks (and of the two halves of one
    chunk) of ONE stripe released together, stripe after stripe: both
    land whole, and the shards are the reference's."""
    pool = Pool(cluster, geometry)
    k = pool.k
    width = k * SU
    nstripes = 6
    oid = f"race_{geometry}"
    model = bytearray(payload(2, nstripes * width))
    pool.io.write_full(oid, bytes(model))
    ios = [pool.client.open_ioctx(pool.name) for _ in range(k + 1)]
    errors = []
    for stripe in range(nstripes):
        # one writer a chunk, and a second one in the last chunk's
        # other half
        jobs = [(stripe * width + c * SU, SU // 2 if c == k - 1 else SU)
                for c in range(k)]
        jobs.append((stripe * width + (k - 1) * SU + SU // 2, SU // 2))
        datas = [payload(1000 * stripe + j, n)
                 for j, (_, n) in enumerate(jobs)]
        start = threading.Barrier(len(jobs))

        def writer(j: int) -> None:
            start.wait()
            try:
                ios[j].write(oid, datas[j], offset=jobs[j][0])
            except Exception as e:  # noqa: BLE001 — reported below
                errors.append(repr(e))

        threads = [threading.Thread(target=writer, args=(j,))
                   for j in range(len(jobs))]
        for t in threads:
            t.start()
        for t in threads:
            t.join()
        for (off, n), data in zip(jobs, datas):
            model[off:off + n] = data
    assert not errors
    assert pool.io.read(oid, len(model)) == bytes(model)
    pool.assert_matches_reference(oid, bytes(model))


def test_image_model_to_object_bytes_to_shards_to_stores(cluster):
    """The pieces tied together at a small size: the reference's image
    model (prefill + overwrites) gives each data object's bytes, its
    encoding gives the shards, and the stores hold exactly those."""
    c, client = cluster
    pool = Pool(cluster, "k4m2")
    meta = client.open_ioctx("meta")
    order, size = 16, 256 << 10             # 4 objects of 64 KiB
    RBD(meta).create("tied", size, order=order, data_pool="k4m2")
    model = REF.ImageModel(size, order)
    with Image(meta, "tied", exclusive=True) as img:
        for n in range(model.objects):
            data = payload(50 + n, model.object_bytes)
            img.write(n * model.object_bytes, data)
            model.fill(n * model.object_bytes, data)
        rng = np.random.default_rng(11)
        for i, block in enumerate(rng.permutation(size // SU)[:24]):
            data = payload(500 + i, SU)
            img.write(int(block) * SU, data)
            model.overlay(int(block) * SU, data)
        assert img.read(0, size) == model.bytes.tobytes()
    assert model.overwritten            # and, at 24 of 64 blocks, all
    for n in range(model.objects):
        oid = f"rbd_data.tied.{n:016x}"
        if n in model.overwritten:
            pool.assert_matches_reference(oid, model.object(n).tobytes())
        else:
            # only ever appended to: no chunk_crc, the hinfo's
            # append-time crcs of all k+m shards stand
            want, crcs = REF.expected_shards(model.object(n), 4, 2, SU)
            for shard, (store, cid, head, gens, _) in \
                    pool.shards(oid).items():
                attrs = store.getattrs(cid, head)
                assert np.array_equal(store.read(cid, head), want[shard])
                assert CHUNK_CRC_KEY not in attrs and not gens
                assert list(HashInfo.decode(
                    attrs[HINFO_KEY]).cumulative_shard_hashes) == crcs


class RacingShards(LocalShardBackend):
    """Answers every sub-read from its own thread, all released
    together: the dispatch executor's worst case."""

    def __init__(self, *args):
        super().__init__(*args)
        self.threads = []
        self.gate = threading.Event()

    def sub_read(self, shard, oid, off, length, on_done):
        def answer():
            self.gate.wait()
            LocalShardBackend.sub_read(self, shard, oid, off, length,
                                       on_done)
        t = threading.Thread(target=answer)
        self.threads.append(t)
        t.start()


def test_pre_read_replies_racing_complete_each_extent_once():
    """k replies of one pre-read arriving at once on k threads: the
    extent completes exactly once, so an op with a second pre-read
    outstanding never assembles early over zeros."""
    k, m = 4, 2
    codec = ErasureCodePluginRegistry.instance().factory(
        "jax", Profile({"plugin": "jax", "technique": "cauchy",
                        "k": str(k), "m": str(m)}))
    sinfo = StripeInfo(k * SU, SU)
    width = k * SU
    for attempt in range(20):
        shards = RacingShards(MemStore(), pg_t(1, attempt), k + m)
        be = ECBackend(codec, sinfo, shards)
        oid = hobject_t(pool=1, name=f"o{attempt}")
        model = bytearray(payload(attempt, 4 * width))
        done = threading.Event()
        txn = PGTransaction()
        txn.write(oid, 0, np.frombuffer(bytes(model), dtype=np.uint8))
        be.submit_transaction(txn, eversion_t(1, 1), done.set)
        assert done.wait(10)
        # head in stripe 0, tail in stripe 2: two pre-reads, 2k replies
        data = payload(1000 + attempt, 2 * width)
        model[width // 2:width // 2 + 2 * width] = data
        done.clear()
        txn = PGTransaction()
        txn.write(oid, width // 2, np.frombuffer(data, dtype=np.uint8))
        op = be.submit_transaction(txn, eversion_t(1, 2), done.set)
        assert op.pending_reads == 2 and len(shards.threads) == 2 * k
        shards.gate.set()
        for t in shards.threads:
            t.join()
        assert done.wait(10) and op.error is None
        assert op.pending_reads == 0
        assert be.read(oid).tobytes() == bytes(model)
        assert be.perf.dump()["ec_rmw_reads"] == 2
