"""RBD images with a data pool (reference `rbd create --data-pool`, the
way an image is put on an erasure-coded pool: doc/rados/operations/
erasure-code.rst "Erasure coding with overwrites"): the header, the
directory, the exclusive lock and the object map stay on the image's
own replicated pool, every `rbd_data.*` object lives on the data pool;
and one exclusive handle written by many threads at once (fio's
iodepth on one image)."""

import errno
import json
import threading

import numpy as np
import pytest

from ceph_tpu.osd.types import NO_GEN
from ceph_tpu.rados.client import RadosError
from ceph_tpu.rbd import RBD, Image
from ceph_tpu.tools.vstart import Cluster

KB = 1 << 10
ORDER = 16                      # 64 KiB objects: four k4m2 stripes


def payload(seed: int, n: int) -> bytes:
    return np.random.default_rng(seed).bytes(n)


@pytest.fixture(scope="module")
def cluster():
    with Cluster(n_osds=6) as c:
        client = c.client()
        client.set_ec_profile("k4m2", {
            "plugin": "jax", "technique": "cauchy", "k": "4", "m": "2",
            "stripe_unit": "4096"})
        client.create_pool("ecdata", "erasure",
                           erasure_code_profile="k4m2", pg_num=4)
        client.create_pool("meta", "replicated", size=3, pg_num=4)
        c.wait_active_clean(timeout=120)
        yield c, client


def object_names(cluster, pool: str) -> set[str]:
    """Names of the head objects the pool's stores hold."""
    c, client = cluster
    pool_id = client.objecter.osdmap.lookup_pool(pool).id
    names = set()
    for osd in c.osds:
        for cid in osd.store.list_collections():
            if cid.pgid.pool != pool_id:
                continue
            names.update(g.hobj.name for g in osd.store.list_objects(cid)
                         if not g.hobj.name.startswith("__")
                         and g.generation == NO_GEN and not g.hobj.snap)
    return names


def test_objects_split_between_the_pools(cluster):
    _, client = cluster
    meta = client.open_ioctx("meta")
    RBD(meta).create("split", 256 * KB, order=ORDER, data_pool="ecdata")
    with Image(meta, "split", exclusive=True) as img:
        img.write(0, payload(1, 64 * KB))
        img.write(100 * KB, payload(2, 8 * KB))
    in_meta = {n for n in object_names(cluster, "meta") if "split" in n
               or n == "rbd_directory"}
    in_data = {n for n in object_names(cluster, "ecdata") if "split" in n}
    assert in_meta == {"rbd_header.split", "rbd_directory",
                       "rbd_object_map.split"}
    assert in_data == {"rbd_data.split.0000000000000000",
                       "rbd_data.split.0000000000000001"}
    header = json.loads(meta.read("rbd_header.split").decode())
    assert header["data_pool"] == "ecdata"
    assert "split" in RBD(meta).list()


def test_round_trip_resize_snapshot_remove(cluster):
    _, client = cluster
    meta = client.open_ioctx("meta")
    rbd = RBD(meta)
    rbd.create("trip", 256 * KB, order=ORDER, data_pool="ecdata")
    model = bytearray(256 * KB)
    with Image(meta, "trip", exclusive=True) as img:
        for off, seed, n in ((0, 3, 64 * KB), (64 * KB, 4, 64 * KB),
                             (5000, 5, 4096), (60 * KB, 6, 12 * KB),
                             (200 * KB, 7, 512)):
            data = payload(seed, n)
            img.write(off, data)
            model[off:off + n] = data
        assert img.read(0, 256 * KB) == bytes(model)
        # a snapshot is clones on the DATA pool: reads of it see the
        # bytes of its moment, the head moves on
        img.snap_create("s1")
        at_snap = bytes(model)
        data = payload(8, 4096)
        img.write(8192, data)
        model[8192:8192 + 4096] = data
        img.snap_set("s1")
        assert img.read(0, 256 * KB) == at_snap
        img.snap_set(None)
        assert img.read(0, 256 * KB) == bytes(model)
        # shrink drops the data objects past the end, grow reads zeros
        img.resize(128 * KB)
        assert img.size() == 128 * KB
        assert img.read(0, 256 * KB) == bytes(model[:128 * KB])
        img.resize(256 * KB)
        assert img.read(128 * KB, 128 * KB) == bytes(128 * KB)
        img.snap_remove("s1")
    assert not any("trip" in n and int(n.rsplit(".", 1)[1], 16) >= 2
                   for n in object_names(cluster, "ecdata"))
    rbd.remove("trip")
    assert "trip" not in rbd.list()
    assert not any("trip" in n for n in object_names(cluster, "ecdata"))
    assert not any("trip" in n for n in object_names(cluster, "meta"))


def test_unknown_data_pool_is_refused(cluster):
    _, client = cluster
    meta = client.open_ioctx("meta")
    with pytest.raises(RadosError) as ei:
        RBD(meta).create("nopool", 64 * KB, order=ORDER,
                         data_pool="no_such_pool")
    assert ei.value.errno == errno.ENOENT
    assert "nopool" not in RBD(meta).list()


def test_image_without_data_pool_is_as_before(cluster):
    """Byte for byte: the header has no new key, and every object of
    the image lies on the image's own pool."""
    _, client = cluster
    meta = client.open_ioctx("meta")
    RBD(meta).create("plain", 128 * KB, order=ORDER)
    assert json.loads(meta.read("rbd_header.plain").decode()) == {
        "size": 128 * KB, "order": ORDER, "snaps": [], "snap_ids": {},
        "parent": None}
    data = payload(9, 70 * KB)
    with Image(meta, "plain", exclusive=True) as img:
        img.write(1000, data)
        assert img.read(1000, 70 * KB) == data
        assert img.data_io is img.io
    assert {n for n in object_names(cluster, "meta") if "plain" in n} == {
        "rbd_header.plain", "rbd_object_map.plain",
        "rbd_data.plain.0000000000000000",
        "rbd_data.plain.0000000000000001"}
    assert not any("plain" in n for n in object_names(cluster, "ecdata"))


def test_clone_reads_through_a_parent_on_another_data_pool(cluster):
    _, client = cluster
    meta = client.open_ioctx("meta")
    rbd = RBD(meta)
    rbd.create("base", 128 * KB, order=ORDER, data_pool="ecdata")
    data = payload(10, 128 * KB)
    with Image(meta, "base") as img:
        img.write(0, data)
        img.snap_create("gold")
    rbd.clone("base", "gold", "child")          # child: no data pool
    with Image(meta, "child", exclusive=True) as child:
        assert child.read(0, 128 * KB) == data
        patch = payload(11, 4096)
        child.write(4096, patch)                # copy-up, then overlay
        want = bytearray(data)
        want[4096:8192] = patch
        assert child.read(0, 128 * KB) == bytes(want)
    assert "rbd_data.child.0000000000000000" in object_names(cluster,
                                                              "meta")


@pytest.mark.parametrize("span,what", [
    (64 * KB, "one object"),      # 16 blocks of 4 KiB: 4 stripes
    (16 * KB, "one stripe"),      # 32 blocks of 512 B in ONE stripe
])
def test_32_threads_on_one_exclusive_handle(cluster, monkeypatch, span,
                                            what):
    """Disjoint blocks of one object and of one stripe, 32 writers in
    flight on ONE handle: every block reads back whole."""
    _, client = cluster
    meta = client.open_ioctx("meta")
    name = f"qd32_{span}"
    RBD(meta).create(name, 128 * KB, order=ORDER, data_pool="ecdata")
    img = Image(meta, name, exclusive=True)
    base = payload(12, 128 * KB)
    img.write(0, base[:64 * KB])                # prefill: appends
    img.write(64 * KB, base[64 * KB:])
    block = span // 32
    want = bytearray(base)
    datas = [payload(100 + i, block) for i in range(32)]
    start = threading.Barrier(32)
    errors = []

    def writer(i: int) -> None:
        start.wait()
        try:
            img.write(i * block, datas[i])
        except Exception as e:  # noqa: BLE001 — reported below
            errors.append(repr(e))

    threads = [threading.Thread(target=writer, args=(i,))
               for i in range(32)]
    real, submitted = client.objecter.op_submit, []

    def spy(pool, oid, ops, *a, **kw):
        # (the objecter's own periodic check of the lock's watch, a
        # `listwatchers` on the header, is not the handle's)
        if ops[0][0] != "listwatchers":
            submitted.append((oid, ops[0][0]))
        return real(pool, oid, ops, *a, **kw)

    monkeypatch.setattr(client.objecter, "op_submit", spy)
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    assert not errors
    monkeypatch.undo()
    # the steady state adds no round trip: the object map knew every
    # block, so the client submitted 32 data writes and nothing else
    assert submitted == [(f"rbd_data.{name}.{0:016x}", "write")] * 32
    for i in range(32):
        want[i * block:(i + 1) * block] = datas[i]
    assert img.read(0, 128 * KB) == bytes(want)
    img.close()
    # a fresh handle sees the same bytes (nothing lived in the handle)
    with Image(meta, name) as again:
        assert again.read(0, 128 * KB) == bytes(want)
