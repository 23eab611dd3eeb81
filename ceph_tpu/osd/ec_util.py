"""EC stripe geometry + per-shard checksums + stripe-batch codec glue.

Re-expresses reference src/osd/ECUtil.{h,cc}:

* `StripeInfo` — stripe_width/chunk_size arithmetic and logical<->chunk
  offset mapping (reference stripe_info_t, ECUtil.h:27-80).
* `HashInfo` — cumulative per-shard crc32c, persisted as a shard xattr,
  with projected sizes for in-flight ops (reference ECUtil.h:101-160;
  updated by append at ECUtil.cc:172, verified on reads by
  ECBackend::handle_sub_read, checked by deep scrub).
* `encode` / `decode` — slice a logical buffer into stripes and run the
  codec.  TPU-first difference from the reference: where ECUtil::encode
  loops stripes serially calling ec_impl->encode per stripe
  (ECUtil.cc:120-150), here the whole extent (all stripes) goes to the
  codec as ONE batched call — the kernel tiles the byte axis, so more
  stripes just means a longer axis, and cross-transaction batching in
  the backend concatenates further.
"""

from __future__ import annotations

import threading
from dataclasses import dataclass, field

import numpy as np

from ..common import crc32c as _crc
from ..common.spans import span
from ..ec.interface import ErasureCodeInterface
from .types import ghobject_t


@dataclass(frozen=True)
class StripeInfo:
    """Geometry of an EC pool's stripes (reference stripe_info_t)."""

    stripe_width: int   # bytes of logical data per stripe (k * chunk_size)
    chunk_size: int     # bytes per shard per stripe

    def __post_init__(self):
        assert self.stripe_width % self.chunk_size == 0, \
            (self.stripe_width, self.chunk_size)

    @property
    def k(self) -> int:
        return self.stripe_width // self.chunk_size

    def logical_to_prev_stripe_offset(self, off: int) -> int:
        return off - off % self.stripe_width

    def logical_to_next_stripe_offset(self, off: int) -> int:
        return -(-off // self.stripe_width) * self.stripe_width

    def logical_to_prev_chunk_offset(self, off: int) -> int:
        """Byte offset within each shard object for a logical offset."""
        return (off // self.stripe_width) * self.chunk_size

    def logical_to_next_chunk_offset(self, off: int) -> int:
        return -(-off // self.stripe_width) * self.chunk_size

    def aligned_logical_offset_to_chunk_offset(self, off: int) -> int:
        assert off % self.stripe_width == 0, off
        return (off // self.stripe_width) * self.chunk_size

    def aligned_chunk_offset_to_logical_offset(self, off: int) -> int:
        assert off % self.chunk_size == 0, off
        return (off // self.chunk_size) * self.stripe_width

    def offset_len_to_stripe_bounds(self, off: int,
                                    length: int) -> tuple[int, int]:
        """Round an extent out to stripe bounds (reference
        stripe_info_t::offset_len_to_stripe_bounds)."""
        start = self.logical_to_prev_stripe_offset(off)
        end = self.logical_to_next_stripe_offset(off + length)
        return start, end - start


HINFO_KEY = "hinfo_key"  # shard xattr name (reference ECUtil.cc get_hinfo_key)
# Per-shard full-chunk crc32c — ONE crc over ALL the shard object's
# bytes (seed 0xffffffff, no final inversion: the convention of the
# hinfo's cumulative hashes, so one function gives both) — maintained
# BY THE SHARD on every write once the object's cumulative hinfo is
# invalidated by an overwrite (the integrity story for overwritten
# objects; the reference's allow_ec_overwrites pools lean on deep-scrub
# reads the same way).  `refresh_chunk_crcs` keeps it in O(write): crc
# is linear over GF(2), so an in-place overwrite patches the four bytes
# from the bytes it changed.
CHUNK_CRC_KEY = "chunk_crc"


def chunk_crc_of(data) -> bytes:
    return _crc.crc32c(np.asarray(data).tobytes(),
                       0xFFFFFFFF).to_bytes(4, "little")


def recovery_attrs(hinfo: "HashInfo", data) -> dict[str, bytes]:
    """Xattrs a freshly-rebuilt shard should carry: the hinfo always,
    plus a chunk_crc when the hinfo's cumulative hashes are dead."""
    attrs = {HINFO_KEY: hinfo.encode()}
    if hinfo.invalidated:
        attrs[CHUNK_CRC_KEY] = chunk_crc_of(data)
    return attrs


# How often the patch engaged, per calling thread: `refresh_chunk_crcs`
# returns one int (its bytes), so the objects it patched and the ones
# it re-hashed whole are tallied here and the caller that keeps
# counters (OSDDaemon._apply_sub_write: `ec_shard_chunk_crc_patches` /
# `_rehashes`) takes them right after the call, on the same thread.
class _Tally(threading.local):
    patches = 0
    rehashes = 0


_tally = _Tally()


def take_chunk_crc_tally() -> tuple[int, int]:
    """(objects patched, objects re-hashed whole) by the calling
    thread's `refresh_chunk_crcs` calls since it last asked."""
    out = (_tally.patches, _tally.rehashes)
    _tally.patches = _tally.rehashes = 0
    return out


def _old_chunk_crc(store, cid, gen_oid, shard: int, rollback,
                   size: int) -> int | None:
    """The crc of the shard object's bytes BEFORE the entry, without
    reading them: the chunk_crc attr the generation was cloned with,
    or — on the first overwrite, no attr yet — the prior hinfo's
    cumulative hash of this shard, which is the same crc while the
    object was append-only."""
    try:
        return int.from_bytes(
            store.getattr(cid, gen_oid, CHUNK_CRC_KEY), "little")
    except KeyError:
        pass
    if rollback.hinfo_old is None:
        return None
    hinfo = HashInfo.decode(rollback.hinfo_old)
    if not hinfo.crc_valid or hinfo.total_chunk_size != size \
            or shard >= len(hinfo.cumulative_shard_hashes):
        return None
    return hinfo.get_chunk_hash(shard)


def _patched_chunk_crc(store, cid, goid, shard: int, rollback
                       ) -> tuple[int, int] | None:
    """(new chunk_crc, bytes hashed) of an object the entry overwrote
    in place, from the changed extents alone; None where only a whole
    re-hash is right (see refresh_chunk_crcs).  For bytes [off, off+n)
    of an object of size S changed in place,

        crc(new) = crc(old) ^ zeros(crc32c(old ^ new, seed 0), S-off-n)

    Old bytes come from the generation the entry's own transaction
    cloned, so an entry applied twice patches with a zero delta."""
    if rollback.kept_generation is None or rollback.extents is None:
        return None
    gen_oid = ghobject_t(goid.hobj, rollback.kept_generation, shard)
    try:
        size = store.stat(cid, gen_oid)
        if store.stat(cid, goid) != size:
            return None     # grew or shrank: offsets from the end moved
    except KeyError:
        return None
    extents = sorted(rollback.extents)
    end = 0
    for off, n in extents:
        if off < end or off + n > size:
            return None     # overlapping, or past the old end
        end = off + n
    crc = _old_chunk_crc(store, cid, gen_oid, shard, rollback, size)
    if crc is None:
        return None
    for off, n in extents:
        delta = np.bitwise_xor(store.read(cid, gen_oid, off, n),
                               store.read(cid, goid, off, n))
        crc ^= _crc.crc32c_zeros(_crc.crc32c(delta.tobytes(), 0),
                                 size - off - n)
    return crc, sum(n for _, n in extents)


def refresh_chunk_crcs(store, cid, shard: int, entries,
                       spans_on: bool = False) -> int:
    """Shard-side integrity upkeep after applying a sub-write: an
    object that has entered overwrite mode (a generation was kept, or
    a chunk_crc attr already exists from an earlier overwrite) gets
    its full-chunk crc brought up to date from local bytes.  Pure
    appends on never-overwritten objects skip this — their cumulative
    hinfo is still authoritative.

    An in-place overwrite PATCHES the attr from the bytes it changed
    (`_patched_chunk_crc`): the entry kept a generation, names its
    chunk extents, all of them inside the old size, the size did not
    change, and the old crc is known (the attr, or on the first
    overwrite the prior hinfo's hash of this shard).  Everything else
    — no generation (an append onto an object already in overwrite
    mode), a size change, unknown extents (an entry from an older peer
    or log), no usable old crc — re-reads and re-hashes the WHOLE
    shard object.  The decision is taken from the entry and the store
    alone.  A patch never reads the bytes it did not change, so bitrot
    outside the extent keeps its mismatch for deep scrub; a whole
    re-hash launders it.

    Returns the bytes it passed through crc32c (the changed extents on
    a patch, the object's size on a whole re-hash:
    `ec_shard_chunk_crc_bytes`); `take_chunk_crc_tally` says how many
    objects went which way.  Each object's upkeep is an
    `ec.chunk_crc_refresh` span when `spans_on`."""
    from ..store.object_store import Transaction
    from .pg_log import LogOp
    seen = set()
    hashed = 0
    for e in entries:
        if e.op is not LogOp.MODIFY or e.oid in seen:
            continue
        seen.add(e.oid)
        goid = ghobject_t(e.oid, shard=shard)
        if e.rollback.kept_generation is None:
            try:
                store.getattr(cid, goid, CHUNK_CRC_KEY)
            except KeyError:
                continue   # append-only object: hinfo covers it
        with span("ec.chunk_crc_refresh", spans_on):
            patched = _patched_chunk_crc(store, cid, goid, shard,
                                         e.rollback)
            if patched is not None:
                crc, n = patched
                attr = crc.to_bytes(4, "little")
                _tally.patches += 1
            else:
                try:
                    data = store.read(cid, goid)
                except KeyError:
                    continue
                attr, n = chunk_crc_of(data), int(data.size)
                _tally.rehashes += 1
            txn = Transaction()
            txn.setattr(goid, CHUNK_CRC_KEY, attr)
            store.queue_transactions(cid, [txn])
            hashed += n
    return hashed


@dataclass
class HashInfo:
    """Cumulative per-shard crc32c + shard/logical sizes.

    Invariant: cumulative_shard_hashes[s] is the crc32c (seed -1) of all
    bytes ever appended to shard s, and total_chunk_size their length.
    Append-only, like the reference (EC overwrites bump object
    generations rather than rewriting ranges in place).

    logical_size carries the object's true byte length (the reference
    keeps this in object_info_t; here it rides the hinfo xattr, which is
    already replicated on every shard) — without it, reads would return
    the stripe-padded size.
    """

    total_chunk_size: int = 0
    cumulative_shard_hashes: list[int] = field(default_factory=list)
    logical_size: int = 0
    # Sticky: once an in-place overwrite/shrink broke the cumulative
    # crcs, later appends fold onto meaningless seeds — the flag must
    # survive so consumers switch to the per-shard chunk_crc attr.
    invalidated: bool = False

    @classmethod
    def make(cls, n_shards: int) -> "HashInfo":
        return cls(0, [0xFFFFFFFF] * n_shards, 0)

    def append(self, old_size: int, shard_chunks: np.ndarray) -> None:
        """Fold one stripe-aligned append into every shard's crc
        (reference HashInfo::append, ECUtil.cc:172).  shard_chunks is
        (n_shards, added_len)."""
        assert old_size == self.total_chunk_size, \
            f"append at {old_size} != current {self.total_chunk_size}"
        n, added = shard_chunks.shape
        assert n == len(self.cumulative_shard_hashes)
        self.cumulative_shard_hashes = _crc.crc32c_rows(
            shard_chunks, self.cumulative_shard_hashes)
        self.total_chunk_size += added

    def append_precomputed(self, old_size: int, added: int,
                           new_hashes: list[int]) -> None:
        """Fold an append whose cumulative crcs were already produced —
        by the fused TPU kernel seeded with the current hashes (the
        north-star single-launch path)."""
        assert old_size == self.total_chunk_size
        assert len(new_hashes) == len(self.cumulative_shard_hashes)
        self.cumulative_shard_hashes = [int(h) & 0xFFFFFFFF
                                        for h in new_hashes]
        self.total_chunk_size += added

    def invalidate(self, new_size: int | None = None) -> None:
        """An in-place change breaks the incremental crcs permanently
        (sticky flag); rollback safety comes from the object generation
        kept at overwrite time, and integrity from the shard-maintained
        chunk_crc attr.  NOTE: a same-size overwrite must invalidate
        too — stale cumulative crcs over new bytes read as corruption."""
        if new_size is not None:
            self.total_chunk_size = new_size
        self.cumulative_shard_hashes = [
            0xFFFFFFFF] * len(self.cumulative_shard_hashes)
        self.invalidated = True

    def truncate(self, new_size: int) -> None:
        if new_size != self.total_chunk_size:
            self.invalidate(new_size)

    def get_chunk_hash(self, shard: int) -> int:
        return self.cumulative_shard_hashes[shard]

    @property
    def crc_valid(self) -> bool:
        """False once an overwrite/shrink broke the cumulative hashes:
        consumers must use the per-shard chunk_crc attr instead."""
        return not self.invalidated and (
            self.total_chunk_size == 0 or
            any(h != 0xFFFFFFFF for h in self.cumulative_shard_hashes))

    # -- persistence (shard xattr) -----------------------------------------

    _MAGIC_V2 = b"HIv2"

    def encode(self) -> bytes:
        import struct
        return self._MAGIC_V2 + struct.pack(
            "<QQII", self.total_chunk_size, self.logical_size,
            1 if self.invalidated else 0,
            len(self.cumulative_shard_hashes)) + b"".join(
            int(h).to_bytes(4, "little")
            for h in self.cumulative_shard_hashes)

    @classmethod
    def decode(cls, raw: bytes) -> "HashInfo":
        import struct
        if raw[:4] == cls._MAGIC_V2:
            size, logical, flags, n = struct.unpack_from("<QQII", raw, 4)
            off = 4 + 24
            inval = bool(flags & 1)
        else:
            # legacy (pre-invalidated-flag) layout: <QQI + hashes
            size, logical, n = struct.unpack_from("<QQI", raw)
            off = 20
            inval = False
        hashes = [int.from_bytes(raw[off + 4 * i:off + 4 + 4 * i],
                                 "little") for i in range(n)]
        return cls(size, hashes, logical, invalidated=inval)


def encode(sinfo: StripeInfo, ec_impl: ErasureCodeInterface,
           data: np.ndarray) -> np.ndarray:
    """Encode a stripe-aligned logical extent into all shard chunks.

    data: (L,) uint8 with L % stripe_width == 0.
    Returns (k+m, L/k): shard s's contiguous bytes for this extent.

    One batched codec call for all stripes: logical layout is
    [stripe0[chunk0..chunkk-1], stripe1[...], ...]; reshaping to
    (nstripes, k, chunk_size) and transposing gives each shard's rows,
    which ride the codec's byte axis in one launch.
    """
    data = np.asarray(data, dtype=np.uint8).ravel()
    assert data.size % sinfo.stripe_width == 0, \
        (data.size, sinfo.stripe_width)
    k = sinfo.k
    m = ec_impl.get_chunk_count() - ec_impl.get_data_chunk_count()
    assert k == ec_impl.get_data_chunk_count()
    nstripes = data.size // sinfo.stripe_width
    # (k, nstripes*chunk_size): row j = shard j's bytes across stripes
    chunks = data.reshape(nstripes, k, sinfo.chunk_size) \
                 .transpose(1, 0, 2).reshape(k, -1)
    parity = np.asarray(ec_impl.encode_chunks(chunks))
    return np.concatenate([chunks, parity], axis=0)


def decode(sinfo: StripeInfo, ec_impl: ErasureCodeInterface,
           shard_data: dict[int, np.ndarray], want_len: int) -> np.ndarray:
    """Rebuild a logical extent from per-shard contiguous buffers
    (reference ECUtil::decode).  shard_data maps shard id -> (chunk-run)
    bytes, all the same length and stripe-aligned."""
    lens = {v.size for v in shard_data.values()}
    assert len(lens) == 1, "mixed shard lengths"
    run = lens.pop()
    assert run % sinfo.chunk_size == 0
    k = sinfo.k
    decoded = ec_impl.decode(set(range(k)),
                             {s: d for s, d in shard_data.items()}, run)
    nstripes = run // sinfo.chunk_size
    stacked = np.stack([np.asarray(decoded[j], dtype=np.uint8)
                        for j in range(k)])        # (k, run)
    logical = stacked.reshape(k, nstripes, sinfo.chunk_size) \
                     .transpose(1, 0, 2).reshape(-1)
    return logical[:want_len]
