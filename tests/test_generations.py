"""EC overwrite generations: local rollback of overwrites/deletes,
generation reclaim on rollforward, shard-maintained chunk crcs, and
crash-replay durability.

Reference analogs: doc/dev/osd_internals/erasure_coding/ecbackend.rst:
9-27 (every EC op locally rollbackable: delete keeps the old
generation), ECBackend trim_rollback_object on rollforward, and the
allow_ec_overwrites deep-scrub integrity model.
"""

import numpy as np
import pytest

from ceph_tpu.ec import ErasureCodePluginRegistry
from ceph_tpu.osd import scrub as scrub_mod
from ceph_tpu.osd.ec_backend import ECBackend, LocalShardBackend
from ceph_tpu.osd.ec_transaction import PGTransaction, shard_oid
from ceph_tpu.osd.ec_util import CHUNK_CRC_KEY, HINFO_KEY, HashInfo, StripeInfo
from ceph_tpu.osd.types import NO_GEN, eversion_t, ghobject_t, hobject_t, pg_t, spg_t
from ceph_tpu.store import MemStore
from ceph_tpu.store.file_store import FileStore
from ceph_tpu.common import crc32c as _crc

REG = ErasureCodePluginRegistry.instance()
K, M, CHUNK = 2, 1, 64


def make_backend(store=None):
    codec = REG.factory("jerasure", {"k": str(K), "m": str(M)})
    store = store or MemStore()
    if not getattr(store, "_mounted", False):
        store.mount()
    shards = LocalShardBackend(store, pg_t(1, 0), K + M)
    return ECBackend(codec, StripeInfo(K * CHUNK, CHUNK), shards), store


def put(backend, name, payload, version, offset=0, delete=False):
    txn = PGTransaction()
    oid = hobject_t(pool=1, name=name)
    if delete:
        txn.delete(oid)
    else:
        txn.write(oid, offset, payload)
    done = []
    backend.submit_transaction(txn, eversion_t(1, version),
                               lambda: done.append(1))
    assert done
    return oid


def shard_bytes(store, shard, oid, gen=None):
    cid = spg_t(pg_t(1, 0), shard)
    goid = ghobject_t(oid, NO_GEN if gen is None else gen, shard)
    try:
        return store.read(cid, goid).tobytes()
    except KeyError:
        return None


def test_overwrite_keeps_generation_and_rolls_back():
    """An in-place overwrite snapshots the old shard object under the
    op's generation; shard-local rollback restores it bit-identically
    (data AND attrs) with nothing reported for remote recovery."""
    backend, store = make_backend()
    rng = np.random.default_rng(0)
    base = rng.integers(0, 256, 4 * K * CHUNK, dtype=np.uint8)
    oid = put(backend, "g1", base, 1)
    before = {s: shard_bytes(store, s, oid) for s in range(K + M)}
    before_hinfo = store.getattr(spg_t(pg_t(1, 0), 0),
                                 shard_oid(oid, 0), HINFO_KEY)
    # overwrite the first stripe (RMW)
    put(backend, "g1", rng.integers(0, 256, 64, dtype=np.uint8), 2,
        offset=10)
    for s in range(K + M):
        assert shard_bytes(store, s, oid, gen=2) == before[s], \
            "generation must snapshot the pre-overwrite shard"
        slog = backend.shards.shard_logs[s]
        e = slog.log.entries[-1]
        assert e.rollback.kept_generation == 2
        removed = slog.rollback_to(eversion_t(1, 1))
        assert removed == [], "generation rollback is fully local"
        assert shard_bytes(store, s, oid) == before[s]
        assert shard_bytes(store, s, oid, gen=2) is None
    assert store.getattr(spg_t(pg_t(1, 0), 0),
                         shard_oid(oid, 0), HINFO_KEY) == before_hinfo


def test_delete_keeps_generation_and_rolls_back():
    backend, store = make_backend()
    rng = np.random.default_rng(1)
    base = rng.integers(0, 256, 2 * K * CHUNK, dtype=np.uint8)
    oid = put(backend, "g2", base, 1)
    before = shard_bytes(store, 0, oid)
    put(backend, "g2", None, 2, delete=True)
    assert shard_bytes(store, 0, oid) is None
    assert shard_bytes(store, 0, oid, gen=2) == before
    slog = backend.shards.shard_logs[0]
    assert slog.rollback_to(eversion_t(1, 1)) == []
    assert shard_bytes(store, 0, oid) == before


def test_generation_purged_on_rollforward():
    """Once the entry is durable everywhere (rollforward advances past
    it on a later write), the kept generation is reclaimed."""
    backend, store = make_backend()
    rng = np.random.default_rng(2)
    oid = put(backend, "g3", rng.integers(0, 256, 2 * K * CHUNK,
                                          dtype=np.uint8), 1)
    put(backend, "g3", rng.integers(0, 256, 32, dtype=np.uint8), 2,
        offset=0)   # overwrite -> gen 2 kept
    assert shard_bytes(store, 0, oid, gen=2) is not None
    # next write piggybacks rollforward_to >= (1,2) -> purge
    put(backend, "g3", rng.integers(0, 256, 32, dtype=np.uint8), 3,
        offset=4 * K * CHUNK)
    assert shard_bytes(store, 0, oid, gen=2) is None, \
        "rolled-forward generation must be reclaimed"


def test_chunk_crc_maintained_and_scrub_clean_after_overwrite():
    """Overwrites invalidate the cumulative hinfo (sticky flag); each
    shard then self-maintains a full-chunk crc, and deep scrub stays
    clean using it — including across subsequent appends."""
    backend, store = make_backend()
    rng = np.random.default_rng(3)
    oid = put(backend, "g4", rng.integers(0, 256, 2 * K * CHUNK,
                                          dtype=np.uint8), 1)
    put(backend, "g4", rng.integers(0, 256, 50, dtype=np.uint8), 2,
        offset=5)
    # hinfo is sticky-invalid, chunk_crc matches actual bytes
    h = HashInfo.decode(store.getattr(spg_t(pg_t(1, 0), 0),
                                      shard_oid(oid, 0), HINFO_KEY))
    assert h.invalidated and not h.crc_valid
    for s in range(K + M):
        cc = store.getattr(spg_t(pg_t(1, 0), s), shard_oid(oid, s),
                           CHUNK_CRC_KEY)
        data = shard_bytes(store, s, oid)
        assert int.from_bytes(cc, "little") == \
            _crc.crc32c(data, 0xFFFFFFFF)
    res = scrub_mod.scrub_pg(backend, [oid], deep=True)
    assert res.clean, res.errors
    # append after the overwrite: chunk_crc keeps tracking
    put(backend, "g4", rng.integers(0, 256, K * CHUNK,
                                    dtype=np.uint8), 3,
        offset=2 * K * CHUNK)
    h2 = HashInfo.decode(store.getattr(spg_t(pg_t(1, 0), 0),
                                       shard_oid(oid, 0), HINFO_KEY))
    assert h2.invalidated, "invalidation must be sticky across appends"
    res = scrub_mod.scrub_pg(backend, [oid], deep=True)
    assert res.clean, res.errors


def test_scrub_detects_bitrot_in_overwritten_object():
    """The chunk_crc path actually catches corruption (the crutch the
    invalidated hinfo used to leave open)."""
    from ceph_tpu.store.object_store import Transaction
    backend, store = make_backend()
    rng = np.random.default_rng(4)
    oid = put(backend, "g5", rng.integers(0, 256, 2 * K * CHUNK,
                                          dtype=np.uint8), 1)
    put(backend, "g5", rng.integers(0, 256, 40, dtype=np.uint8), 2,
        offset=3)
    # flip a byte on shard 1 behind the system's back
    cid = spg_t(pg_t(1, 0), 1)
    goid = shard_oid(oid, 1)
    data = bytearray(store.read(cid, goid).tobytes())
    data[7] ^= 0xFF
    txn = Transaction()
    txn.write(goid, 0, np.frombuffer(bytes(data), dtype=np.uint8))
    store.queue_transactions(cid, [txn])
    res = scrub_mod.scrub_pg(backend, [oid], deep=True)
    assert any(e.kind == "crc_mismatch" and e.shard == 1
               for e in res.errors), res.errors
    # and repair heals it
    res = scrub_mod.scrub_pg(backend, [oid], deep=True, repair=True)
    assert res.clean and res.repaired


def test_overwrite_does_not_launder_earlier_bitrot():
    """A byte that rotted BEFORE an overwrite of another part of the
    object stays a crc_mismatch after it: the shard patches its
    chunk_crc from the bytes the overwrite changed and never reads the
    rest.  The parent (PR 30) passes this scrub CLEAN: its whole-object
    re-hash after every overwrite hashed the rotten byte into a fresh,
    matching chunk_crc."""
    from ceph_tpu.store.object_store import Transaction
    backend, store = make_backend()
    rng = np.random.default_rng(6)
    oid = put(backend, "g7", rng.integers(0, 256, 4 * K * CHUNK,
                                          dtype=np.uint8), 1)
    put(backend, "g7", rng.integers(0, 256, 40, dtype=np.uint8), 2,
        offset=3)
    # rot a byte of shard 1 in the LAST stripe, then overwrite in the
    # first: the second overwrite's extent is chunk bytes [0, CHUNK)
    cid = spg_t(pg_t(1, 0), 1)
    goid = shard_oid(oid, 1)
    data = bytearray(store.read(cid, goid).tobytes())
    data[3 * CHUNK + 7] ^= 0xFF
    txn = Transaction()
    txn.write(goid, 0, np.frombuffer(bytes(data), dtype=np.uint8))
    store.queue_transactions(cid, [txn])
    put(backend, "g7", rng.integers(0, 256, 40, dtype=np.uint8), 3,
        offset=50)
    res = scrub_mod.scrub_pg(backend, [oid], deep=True)
    assert any(e.kind == "crc_mismatch" and e.shard == 1
               for e in res.errors), res.errors
    res = scrub_mod.scrub_pg(backend, [oid], deep=True, repair=True)
    assert res.clean and res.repaired
    res = scrub_mod.scrub_pg(backend, [oid], deep=True)
    assert res.clean, res.errors


def test_overwrite_survives_crash_replay(tmp_path):
    """FileStore: overwrite + kill (no clean umount) + remount replays
    the WAL; generation objects, hinfo flags, and chunk crcs all come
    back; read returns the post-overwrite bytes."""
    store = FileStore(str(tmp_path / "osd0"))
    store.mount()
    backend, _ = make_backend(store)
    rng = np.random.default_rng(5)
    base = rng.integers(0, 256, 4 * K * CHUNK, dtype=np.uint8)
    oid = put(backend, "g6", base, 1)
    pre_shard0 = shard_bytes(store, 0, oid)
    patch = rng.integers(0, 256, 100, dtype=np.uint8)
    put(backend, "g6", patch, 2, offset=20)
    expect = bytearray(base.tobytes())
    expect[20:120] = patch.tobytes()
    # simulate a crash: new FileStore instance on the same root, no
    # umount of the old one (journal replay on mount)
    store2 = FileStore(str(tmp_path / "osd0"))
    store2.mount()
    backend2, _ = make_backend(store2)
    got = backend2.read(oid, 0, len(expect))
    assert got.tobytes() == bytes(expect)
    # integrity state survived: sticky invalid hinfo + chunk crcs
    h = HashInfo.decode(store2.getattr(spg_t(pg_t(1, 0), 0),
                                       shard_oid(oid, 0), HINFO_KEY))
    assert h.invalidated
    res = scrub_mod.scrub_pg(backend2, [oid], deep=True)
    assert res.clean, res.errors
    # the rollback generation also survived the crash
    assert shard_bytes(store2, 0, oid, gen=2) is not None
    # and rollback still works post-replay
    slog = backend2.shards.shard_logs[0]
    assert slog.rollback_to(eversion_t(1, 1)) == []
    assert shard_bytes(store2, 0, oid) == pre_shard0
