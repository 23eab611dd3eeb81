"""A shard keeps its `chunk_crc` in O(write): `ec_util.
refresh_chunk_crcs` patches the attr from the bytes an in-place
overwrite changed (crc32c is linear over GF(2)) and re-hashes the whole
shard object only where a patch cannot be right.  Exact, on the CPU,
through a real ECBackend: after every write each shard's attr equals
crc32c(whole shard bytes, 0xFFFFFFFF), and the tally says which way the
upkeep went."""

import copy

import numpy as np
import pytest

from ceph_tpu.common import crc32c as _crc
from ceph_tpu.ec import ErasureCodePluginRegistry
from ceph_tpu.osd import ec_util
from ceph_tpu.osd.ec_backend import ECBackend, LocalShardBackend
from ceph_tpu.osd.ec_transaction import PGTransaction, shard_oid
from ceph_tpu.osd.ec_util import CHUNK_CRC_KEY, StripeInfo
from ceph_tpu.osd.pg_log import (LogEntry, RollbackInfo, entry_from_wire,
                                 entry_to_wire)
from ceph_tpu.osd.types import eversion_t, hobject_t, pg_t, spg_t
from ceph_tpu.store import MemStore
from ceph_tpu.store.object_store import Transaction
from ceph_tpu.tools.vstart import Cluster

K, M, CHUNK = 2, 1, 4096
N = K + M
WIDTH = K * CHUNK
PGID = pg_t(1, 0)
OID = hobject_t(pool=1, name="obj")


class Rig:
    """One ECBackend over a MemStore, the model of the object's bytes,
    and the tally of what `refresh_chunk_crcs` did on the way."""

    def __init__(self, nstripes: int, seed: int = 0):
        codec = ErasureCodePluginRegistry.instance().factory(
            "jerasure", {"k": str(K), "m": str(M)})
        self.store = MemStore()
        self.store.mount()
        self.shards = LocalShardBackend(self.store, PGID, N)
        self.backend = ECBackend(codec, StripeInfo(WIDTH, CHUNK),
                                 self.shards)
        self.rng = np.random.default_rng(seed)
        self.version = 0
        self.model = bytearray()
        self.write(0, nstripes * WIDTH)       # the append that makes it
        assert self.submit_tally == (0, 0)
        assert self.attr(0) is None, "append-only: hinfo covers it"

    def submit(self, txn: PGTransaction) -> None:
        self.version += 1
        ec_util.take_chunk_crc_tally()
        done = []
        self.backend.submit_transaction(
            txn, eversion_t(1, self.version), lambda: done.append(1))
        assert done
        self.submit_tally = ec_util.take_chunk_crc_tally()

    def write(self, *extents: int) -> None:
        """One entry writing fresh bytes at (off, n, off, n, ...)."""
        txn = PGTransaction()
        for off, n in zip(extents[::2], extents[1::2]):
            data = self.rng.integers(0, 256, n, dtype=np.uint8)
            txn.write(OID, off, data)
            if off + n > len(self.model):
                self.model.extend(bytes(off + n - len(self.model)))
            self.model[off:off + n] = data.tobytes()
        self.submit(txn)

    def truncate(self, size: int) -> None:
        txn = PGTransaction()
        txn.truncate(OID, size)
        del self.model[size:]
        self.submit(txn)

    def attr(self, shard: int) -> int | None:
        try:
            return int.from_bytes(self.store.getattr(
                spg_t(PGID, shard), shard_oid(OID, shard), CHUNK_CRC_KEY),
                "little")
        except KeyError:
            return None

    def data(self, shard: int) -> bytes:
        return self.store.read(spg_t(PGID, shard),
                               shard_oid(OID, shard)).tobytes()

    def assert_attrs_right(self) -> None:
        got = self.backend.read(OID, 0, len(self.model))
        assert got.tobytes() == bytes(self.model)
        for s in range(N):
            assert self.attr(s) == _crc.crc32c(self.data(s), 0xFFFFFFFF), \
                f"shard {s} chunk_crc"


# one chunk a shard .. 1 MiB a shard (the RBD cell's shard object)
SIZES = [1, 4, 256]
POSITIONS = ["first", "middle", "last", "several"]


def extents_at(position: str, nstripes: int) -> tuple[int, ...]:
    """(off, n, ...) of 512 B..one-chunk writes inside the object."""
    last = nstripes - 1
    if position == "first":
        return (10, 700)
    if position == "middle":
        return ((nstripes // 2) * WIDTH + CHUNK, CHUNK)
    if position == "last":
        return (last * WIDTH + WIDTH - 512, 512)
    # several extents in one entry: first, middle and last stripe where
    # the object has them (stripes apart stay separate chunk extents)
    return tuple(x for s in sorted({0, nstripes // 2, last})
                 for x in (s * WIDTH + 100, 300))


@pytest.mark.parametrize("position", POSITIONS)
@pytest.mark.parametrize("nstripes", SIZES)
def test_overwrite_patches_from_both_sources_of_the_old_crc(
        nstripes, position):
    rig = Rig(nstripes, seed=nstripes)
    extents = extents_at(position, nstripes)
    # first overwrite: no attr yet, seeded from the entry's hinfo_old
    rig.write(*extents)
    assert rig.submit_tally == (N, 0)
    rig.assert_attrs_right()
    # second: the attr the first one left (cloned into the generation)
    rig.write(*extents_at("first", nstripes))
    assert rig.submit_tally == (N, 0)
    rig.assert_attrs_right()
    rig.write(*extents)
    assert rig.submit_tally == (N, 0)
    rig.assert_attrs_right()


def test_patch_hashes_only_the_chunk_bytes_each_shard_wrote():
    rig = Rig(256)
    seen = []
    real = ec_util.refresh_chunk_crcs

    def counting(store, cid, shard, entries, spans_on=False):
        seen.append(real(store, cid, shard, entries, spans_on))
        return seen[-1]
    ec_util.refresh_chunk_crcs = counting
    try:
        rig.write(5 * WIDTH + 7, 100)               # one stripe
        rig.write(9 * WIDTH - 10, 20, 200 * WIDTH, WIDTH)   # 2 + 1
    finally:
        ec_util.refresh_chunk_crcs = real
    assert seen == [CHUNK] * N + [3 * CHUNK] * N
    rig.assert_attrs_right()


def _strip_extents(rig: Rig) -> None:
    """Entries as an older peer sends them: no extents."""
    real = rig.shards.sub_write

    def old_peer(shard, txn, on_commit, log_entries=None, **kw):
        wire = [entry_to_wire(e)[:9] for e in log_entries or []]
        return real(shard, txn, on_commit,
                    log_entries=[entry_from_wire(w) for w in wire], **kw)
    rig.shards.sub_write = old_peer


def _drop_attr_and_break_hinfo(rig: Rig) -> None:
    """An object in overwrite mode (hinfo invalidated) whose shards
    lost their chunk_crc attr: no source for the old crc."""
    rig.write(0, 100)
    for s in range(N):
        txn = Transaction()
        txn.rmattr(shard_oid(OID, s), CHUNK_CRC_KEY)
        rig.store.queue_transactions(spg_t(PGID, s), [txn])
    assert rig.attr(0) is None


FALLBACKS = {
    # name: (prepare, the entry that must re-hash whole)
    "truncate": (lambda rig: rig.write(0, 100),
                 lambda rig: rig.truncate(2 * WIDTH + 5)),
    "growth": (lambda rig: rig.write(0, 100),
               lambda rig: rig.write(4 * WIDTH - 50, 100)),
    "growth_on_first_overwrite": (
        lambda rig: None, lambda rig: rig.write(4 * WIDTH - 50, 100)),
    "append_in_overwrite_mode": (
        lambda rig: rig.write(0, 100),
        lambda rig: rig.write(4 * WIDTH, WIDTH)),
    "unknown_extents": (_strip_extents, lambda rig: rig.write(0, 100)),
    "no_old_crc": (_drop_attr_and_break_hinfo,
                   lambda rig: rig.write(WIDTH, 100)),
}


@pytest.mark.parametrize("name", sorted(FALLBACKS))
def test_fallback_rehashes_whole_and_is_counted(name):
    prepare, entry = FALLBACKS[name]
    rig = Rig(4)
    prepare(rig)
    entry(rig)
    assert rig.submit_tally == (0, N)
    rig.assert_attrs_right()
    # and the next in-place overwrite patches again
    rig.shards.__dict__.pop("sub_write", None)
    rig.write(WIDTH + 9, 50)
    assert rig.submit_tally == (N, 0)
    rig.assert_attrs_right()


def test_overlapping_extents_fall_back():
    """Extents of one entry never overlap as the primary plans them;
    an entry that says otherwise is not patched from."""
    rig = Rig(4)
    rig.write(0, 100)
    slog = rig.shards.shard_logs[0]
    e = copy.deepcopy(slog.log.entries[-1])
    e.rollback.extents = [(0, CHUNK), (CHUNK // 2, CHUNK)]
    ec_util.take_chunk_crc_tally()
    n = ec_util.refresh_chunk_crcs(rig.store, spg_t(PGID, 0), 0, [e])
    assert n == 4 * CHUNK
    assert ec_util.take_chunk_crc_tally() == (0, 1)
    rig.assert_attrs_right()


@pytest.mark.parametrize("first_overwrite", [True, False])
def test_sub_write_applied_twice_leaves_the_attr_right(first_overwrite):
    """A replay clones the already-written head over the generation, so
    the patch sees a zero delta and the crc the first apply left."""
    rig = Rig(4)
    if not first_overwrite:
        rig.write(0, 100)
    real = rig.shards.sub_write

    def twice(shard, txn, on_commit, **kw):
        real(shard, copy.deepcopy(txn), lambda *a, **k: None, **kw)
        return real(shard, txn, on_commit, **kw)
    rig.shards.sub_write = twice
    rig.write(2 * WIDTH + 5, 300)
    assert rig.submit_tally == (2 * N, 0)
    rig.assert_attrs_right()


def test_rollback_restores_the_old_attr():
    """Only the newest entry still has its generation (each sub-write
    rolls the one before it forward), so: one rig per undone kind."""
    # a patched overwrite: the generation comes back, attr with it
    rig = Rig(4)
    rig.write(0, 100)                           # v2
    after_v2 = [rig.attr(s) for s in range(N)]
    bytes_v2 = [rig.data(s) for s in range(N)]
    rig.write(WIDTH, 100)                       # v3: a patch
    assert [rig.attr(s) for s in range(N)] != after_v2
    for s in range(N):
        assert rig.shards.shard_logs[s].rollback_to(
            eversion_t(1, 2)) == []
        assert rig.data(s) == bytes_v2[s]
        assert rig.attr(s) == after_v2[s]
    # the first overwrite: there was no attr before it
    rig = Rig(4)
    rig.write(0, 100)
    for s in range(N):
        assert rig.shards.shard_logs[s].rollback_to(
            eversion_t(1, 1)) == []
        assert rig.attr(s) is None
    # an append onto an object in overwrite mode is undone by a
    # truncate: the attr follows the bytes that stay
    rig = Rig(4)
    rig.write(0, 100)
    rig.write(4 * WIDTH, WIDTH)                 # v3: re-hashed whole
    for s in range(N):
        assert rig.shards.shard_logs[s].rollback_to(
            eversion_t(1, 2)) == []
        assert len(rig.data(s)) == 4 * CHUNK
        assert rig.attr(s) == _crc.crc32c(rig.data(s), 0xFFFFFFFF)


def test_extents_ride_the_entry_and_nine_elements_read_as_unknown():
    e = LogEntry(eversion_t(3, 9), OID, rollback=RollbackInfo(
        append_old_size=8192, old_chunk_size=4096, kept_generation=9,
        extents=[(0, 4096), (8192, 4096)]))
    wire = entry_to_wire(e)
    assert entry_from_wire(wire).rollback.extents == \
        [(0, 4096), (8192, 4096)]
    assert entry_from_wire(wire[:9]).rollback.extents is None
    rig = Rig(4)
    rig.write(WIDTH + 5, 10, 3 * WIDTH, 10)
    for s in range(N):
        assert rig.shards.shard_logs[s].log.entries[-1] \
            .rollback.extents == [(CHUNK, CHUNK), (3 * CHUNK, CHUNK)]


def test_daemon_counts_patches_and_rehashes():
    """The `osd.N` counters beside `ec_shard_chunk_crc_bytes`, through
    the cluster path: two in-place overwrites patch on every shard, the
    truncate after them re-hashes whole."""
    with Cluster(n_osds=4) as c:
        client = c.client()
        client.set_ec_profile("p", {"plugin": "jerasure", "k": str(K),
                                    "m": str(M),
                                    "stripe_unit": str(CHUNK)})
        client.create_pool("ec", "erasure", erasure_code_profile="p",
                           pg_num=1)
        c.wait_active_clean(timeout=120)
        io = client.open_ioctx("ec")
        rng = np.random.default_rng(5)

        def counters() -> dict:
            out: dict = {}
            for osd in c.osds:
                vals = osd.cct.perf.dump()[f"osd.{osd.osd_id}"]
                for key in ("ec_shard_chunk_crc_bytes",
                            "ec_shard_chunk_crc_patches",
                            "ec_shard_chunk_crc_rehashes"):
                    out[key] = out.get(key, 0) + vals[key]
            return out

        io.write_full("o", rng.bytes(8 * WIDTH))
        assert counters() == {"ec_shard_chunk_crc_bytes": 0,
                              "ec_shard_chunk_crc_patches": 0,
                              "ec_shard_chunk_crc_rehashes": 0}
        io.write("o", rng.bytes(100), offset=WIDTH + 3)
        io.write("o", rng.bytes(100), offset=5 * WIDTH)
        assert counters() == {"ec_shard_chunk_crc_bytes": 2 * N * CHUNK,
                              "ec_shard_chunk_crc_patches": 2 * N,
                              "ec_shard_chunk_crc_rehashes": 0}
        io.truncate("o", 6 * WIDTH)
        assert counters() == {
            "ec_shard_chunk_crc_bytes": 2 * N * CHUNK + N * 6 * CHUNK,
            "ec_shard_chunk_crc_patches": 2 * N,
            "ec_shard_chunk_crc_rehashes": N}
