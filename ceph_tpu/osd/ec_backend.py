"""ECBackend: the erasure-coded write/read/recovery engine.

Re-expresses reference src/osd/ECBackend.{h,cc} — the north-star
consumer of the TPU codec.  The reference's pipeline:

  submit_transaction (:1483) -> start_rmw (:1839, WritePlan)
  check_ops loop (:2151):
    try_state_to_reads  (:1865)  RMW pre-reads for partial stripes
    try_reads_to_commit (:1939)  encode + per-shard sub-writes
    try_finish_rmw      (:2103)  all shards committed -> client ack,
                                 rollforward bookkeeping

kept stage-for-stage, with the TPU-first twist the whole build exists
for: when try_reads_to_commit drains, EVERY op that is ready encodes in
ONE batched codec launch — the per-stripe loop of ECUtil::encode and the
per-op encode of the reference are hoisted into a single (k, total_run)
kernel call whose byte axis concatenates all extents of all in-flight
transactions (launch-latency amortization; reference analog is the
waiting_reads->waiting_commit queue, which only pipelines, never
batches).

Dispatch-ahead (docs/PIPELINE.md): the drain itself is split into a
submit half (assemble extents, LAUNCH parity+crc, no host sync) and a
completion half (materialize device results, fold crc seeds, issue
sub-writes).  Up to `dispatch_depth` drains stay in flight while more
work is queued or a `pipeline()` window is open, so assembly of drain
N+1 overlaps device compute of drain N; completion always runs in
submit order, and a lone op with nothing behind it still completes
synchronously (the flush-on-idle rule — existing callers see no
change).  The staged device inputs are donated to XLA on real
accelerators (ops/bitsliced submit path).

Shard I/O goes through the ShardBackend seam: LocalShardBackend applies
to a local ObjectStore (the single-process / test topology, like
standalone clusters on MemStore); the messenger-backed implementation
(distribution layer) ships ECSubWrite/ECSubRead messages instead
(reference ECMsgTypes + MOSDECSubOp*).
"""

from __future__ import annotations

import threading
import time
from dataclasses import dataclass, field
from typing import Callable

import numpy as np

from ..common import spans
from ..common.spans import span
from ..common.tracked_op import NULL_TRACKED
from ..ec.interface import ErasureCodeError, ErasureCodeInterface
from ..ops.profiler import device_profiler
from ..parallel.launch_queue import (ECLaunchQueue, _codec_label,
                                     recovery_decode_cap)
from ..store.object_store import ObjectStore, Transaction
from . import ec_transaction as ect
from . import ec_util
from .ec_transaction import Extent, PGTransaction, WritePlan, shard_oid
from .ec_util import HINFO_KEY, HashInfo, StripeInfo
from .pg_log import LogEntry, LogOp, PGLog, RollbackInfo
from .types import eversion_t, hobject_t, spg_t


# -- shard seam --------------------------------------------------------------

class ShardBackend:
    """Transport seam to one PG's shard replicas (primary's view)."""

    # the owning ECBackend's counter set (ECBackend.__init__ sets it):
    # a transport whose probe can reach the wire counts its outcomes
    # there (ec_probe_* — docs/TRACING.md)
    perf = None

    def sub_write(self, shard: int, txn: Transaction,
                  on_commit: Callable[[int], None],
                  log_entries: list | None = None,
                  at_version=None, rollforward_to=None,
                  trace: dict | None = None, top=None) -> None:
        """Apply txn on `shard`; log_entries (pg_log.LogEntry) persist
        atomically with it (reference ECSubWrite.log_entries).  trace
        is an optional child TraceContext wire dict — remote
        transports forward it so the shard holder's sub-op span
        stitches under the primary's op span."""
        raise NotImplementedError

    def sub_read(self, shard: int, oid: hobject_t, off: int, length: int,
                 on_done: Callable[[int, np.ndarray | None], None]) -> None:
        """Read `length` bytes at chunk-offset `off` of oid's shard;
        on_done(shard, data|None-on-error)."""
        raise NotImplementedError

    def sub_read_batch(self, reqs, on_done) -> None:
        """Fan out [(shard, oid, off, length), ...]; transports
        override to amortize per-message scheduling (one reactor task
        for the whole fan-out)."""
        for shard, oid, off, length in reqs:
            self.sub_read(shard, oid, off, length, on_done)

    def get_hinfo(self, shard: int, oid: hobject_t) -> HashInfo | None:
        raise NotImplementedError

    def get_attrs(self, shard: int, oid: hobject_t) -> dict | None:
        """All xattrs of the shard object (hinfo + chunk_crc + user);
        None when the shard object is absent."""
        raise NotImplementedError

    def stat(self, shard: int, oid: hobject_t) -> int | None:
        raise NotImplementedError

    def probe(self, oid: hobject_t, n: int, repair: bool = False
              ) -> tuple["HashInfo | None", int | None]:
        """One metadata sweep: (hinfo, shard size).  hinfo is
        replicated on every shard, so transports override this to ask
        their LOCAL shard first and go to the others only when that
        shard cannot answer for the PG (MessengerShardBackend.probe) —
        the sequential per-shard fallback here is for local stores.
        repair: the caller is rebuilding a shard of an object some
        shard listed (recovery, scrub repair), so a miss on one shard
        is the damage under repair and never the answer."""
        hinfo = None
        size = None
        for s in range(n):
            if hinfo is None:
                hinfo = self.get_hinfo(s, oid)
                if hinfo is not None:
                    return hinfo, size
            if size is None:
                size = self.stat(s, oid)
        return hinfo, size


class LocalShardBackend(ShardBackend):
    """All shards in one local ObjectStore, per-shard collections —
    the MemStore test topology (and the per-OSD local shard path of
    handle_sub_write, reference ECBackend.cc:2086)."""

    def __init__(self, store: ObjectStore, pgid, n_shards: int):
        from .pg_log import ShardPGLog
        self.store = store
        self.n_shards = n_shards
        self.cids = {s: spg_t(pgid, s) for s in range(n_shards)}
        for cid in self.cids.values():
            store.create_collection(cid)
        self.shard_logs = {s: ShardPGLog(store, self.cids[s], s)
                           for s in range(n_shards)}

    def sub_write(self, shard, txn, on_commit, log_entries=None,
                  at_version=None, rollforward_to=None, trace=None,
                  top=None):
        # top: tracked op for wire-plane trace stitching — local
        # shards have no wire, so it is accepted and unused here
        slog = self.shard_logs[shard]
        if self.perf:
            self.perf.inc("ec_sub_writes_sent")
        if log_entries and at_version is not None:
            slog.append_to_txn(txn, log_entries, at_version)
        self.store.queue_transactions(self.cids[shard], [txn])
        if log_entries:
            slog.record(log_entries, at_version)
            ec_util.refresh_chunk_crcs(self.store, self.cids[shard],
                                       shard, log_entries)
        if rollforward_to is not None:
            slog.advance_rollforward(rollforward_to)
        on_commit(shard)

    def sub_read(self, shard, oid, off, length, on_done):
        goid = shard_oid(oid, shard)
        try:
            data = self.store.read(self.cids[shard], goid, off, length)
        except KeyError:
            on_done(shard, None)
            return
        if data.size < length:  # pad short reads (sparse tail)
            data = np.concatenate(
                [data, np.zeros(length - data.size, dtype=np.uint8)])
        on_done(shard, data)

    def get_hinfo(self, shard, oid):
        goid = shard_oid(oid, shard)
        try:
            raw = self.store.getattr(self.cids[shard], goid, HINFO_KEY)
        except KeyError:
            return None
        return HashInfo.decode(raw)

    def get_attrs(self, shard, oid):
        try:
            return self.store.getattrs(self.cids[shard],
                                       shard_oid(oid, shard))
        except KeyError:
            return None

    def stat(self, shard, oid):
        try:
            return self.store.stat(self.cids[shard], shard_oid(oid, shard))
        except KeyError:
            return None


# -- pipeline op -------------------------------------------------------------

@dataclass
class ECOp:
    """An in-flight client transaction (reference ECBackend::Op)."""
    txn: PGTransaction
    version: eversion_t
    on_commit: Callable[[], None]
    plan: WritePlan | None = None
    # metadata prefetched OUTSIDE the pipeline lock (oid -> probe
    # result): off a clean PG the probe is a blocking RPC fan-out,
    # and running it under be.lock starves every other op AND the
    # dispatch threads that must deliver its replies
    meta: dict = field(default_factory=dict)
    pending_reads: int = 0
    read_data: dict[tuple[hobject_t, int], np.ndarray] = field(
        default_factory=dict)
    pending_commits: int = 0
    state: str = "queued"
    error: Exception | None = None
    # extents this op actually pinned in the ExtentCache (populated
    # incrementally during assembly): release must mirror EXACTLY the
    # present() calls — releasing the full plan after a mid-assembly
    # failure would decrement another in-flight op's pin on the same
    # range and let stale store bytes satisfy a later overlay
    pinned: list[tuple[hobject_t, int, int]] = field(default_factory=list)
    # per-op trace/timeline (common/tracked_op.py); NULL_TRACKED when
    # tracking is off — every mark_event below is then a no-op
    top: object = NULL_TRACKED
    # perf_counter at the op's first RMW sub-read (`lat_ec_rmw_read`
    # ends when its last pre-read is answered)
    t_rmw_read: float = 0.0


@dataclass
class _Drain:
    """One submitted (launched, not yet materialized) pipeline drain."""
    ops: list[ECOp]
    # (op, oid, extent, run (k, W)) per stripe-aligned extent, op order
    work: list[tuple]
    kinds: list[str]                  # per work item: "fused" | "plain"
    fused_handle: object | None       # the queue's LaunchTicket
    fused_pos: dict[int, int]         # work index -> position in handle
    plain_handle: tuple | None        # ("queue", ticket) | ("mesh", h)
    plain_cols: dict[int, int]        # work index -> column offset
    t_assemble: float = 0.0
    # flight-recorder record of a MESH launch; queue launches are
    # recorded by the queue itself and stitched back through the
    # ticket's launch_id (ops/profiler.py)
    prof_plain: object | None = None
    # the launch-queue tickets this drain holds (fused, plain)
    tickets: list = field(default_factory=list)


def _build_ec_perf(name: str):
    """The backend's own counter set (registered into the daemon's
    PerfCountersCollection so `perf dump` and the prometheus exporter
    surface it)."""
    from ..common.perf_counters import PerfCountersBuilder
    return (PerfCountersBuilder(name)
            .add_u64_counter("ec_drain_submits", "pipeline drains launched")
            .add_u64_counter("ec_drain_extents", "extents encoded")
            .add_u64_counter("ec_drain_errors",
                             "sub-write/encode failures absorbed")
            .add_gauge("ec_inflight_depth",
                       "drains in flight after last submit")
            .add_gauge("ec_acting_holes",
                       "shards of the acting set without a live "
                       "holder when the PG's last recovery pass "
                       "ended: > 0 = active+undersized+degraded")
            # object-metadata sweeps (ShardBackend.probe): how often
            # the primary's own shard answered, and what the rest cost
            # on the wire (docs/PIPELINE.md "Authoritative local shard")
            .add_u64_counter("ec_probe_sweeps", "metadata probes made")
            .add_u64_counter("ec_probe_local_hits",
                             "probes answered by the local shard's hinfo")
            .add_u64_counter("ec_probe_local_authoritative_misses",
                             "local misses a clean PG answered without "
                             "the wire")
            .add_u64_counter("ec_probe_remote_sweeps",
                             "probes that went to the other shards")
            .add_u64_counter("ec_probe_remote_reads",
                             "MOSDECSubOpRead frames those probes sent")
            # the read-modify-write half (docs/PIPELINE.md
            # "Overwrites"): what a partial-stripe write reads back
            # before it can encode
            .add_u64_counter("ec_rmw_reads",
                             "stripe-aligned extents pre-read for "
                             "partial-stripe writes")
            .add_u64_counter("ec_rmw_read_bytes",
                             "logical bytes those pre-reads returned")
            .add_u64_counter("ec_rmw_cache_hit_bytes",
                             "bytes the ExtentCache laid over a "
                             "pre-read (in-flight writes of earlier "
                             "ops)")
            .add_histogram("lat_ec_rmw_read",
                           "per op: first RMW sub-read sent -> last "
                           "pre-read answered")
            .add_u64_counter("ec_plain_drains",
                             "drains that launched plain (no-crc) "
                             "parity for non-append extents")
            # the degraded half of a pre-read (docs/PIPELINE.md
            # "Overwrites on a degraded PG"): a data shard's holder
            # is down, so the stripe comes from parity and a decode
            .add_u64_counter("ec_rmw_reconstructs",
                             "pre-reads that needed parity: a data "
                             "shard failed, the extent was "
                             "reconstructed")
            .add_u64_counter("ec_rmw_parity_reads",
                             "parity sub-reads those pre-reads sent")
            .add_histogram("lat_ec_rmw_reconstruct",
                           "per reconstructing pre-read: every data "
                           "shard answered (some failed) -> parity "
                           "read, decode wait, extent reconstructed")
            .add_histogram("lat_ec_decode_wait",
                           "per reconstruct: the blocking wait on "
                           "the decode launch's ticket")
            .add_u64_counter("ec_sub_writes_sent",
                             "shard transactions handed to a live "
                             "holder (k+m an op on a whole acting "
                             "set)")
            .add_u64_counter("ec_sub_writes_skipped_down",
                             "shard transactions not sent: the "
                             "shard's holder is down (a hole in the "
                             "acting set, left to recovery)")
            .add_time_avg("ec_drain_assemble",
                          "host assemble+launch time per drain")
            .add_time_avg("ec_drain_device",
                          "device materialize (block) time per drain")
            .add_time_avg("ec_drain_commit",
                          "sub-write issue time per drain")
            .add_u64_counter("ec_fused_kernel_drains",
                             "fused drains served by the hier kernels")
            .add_u64_counter("ec_fused_fallback_drains",
                             "fused drains served by a fallback path")
            .add_u64_counter("ec_host_queue_drains",
                             "drains routed through the per-host "
                             "launch queue (cross-PG batching)")
            .add_u64_counter("ec_scrub_device_bytes",
                             "deep-scrub bytes crc'd on device")
            .add_u64_counter("ec_scrub_host_bytes",
                             "deep-scrub bytes crc'd on host")
            .add_u64_counter("ec_mesh_drains",
                             "drains dispatched to the mesh plane")
            .add_u64_counter("ec_mesh_repair_launches",
                             "batched distributed repair decodes")
            .add_u64_counter("ec_mesh_errors",
                             "mesh launch failures (plane fell back)")
            # repair subsystem (docs/REPAIR.md): the CLAY savings made
            # visible — helper bytes actually read vs bytes rebuilt —
            # plus the degraded-read path's provenance
            .add_u64_counter("ec_repair_helper_bytes",
                             "survivor/helper bytes read for repair")
            .add_u64_counter("ec_repair_reconstructed_bytes",
                             "shard bytes rebuilt by repair decodes")
            .add_u64_counter("ec_recovery_decode_launches",
                             "decode submissions of grouped recovery "
                             "(one a slice of a geometry group) handed "
                             "to the launch queue")
            .add_u64_counter("ec_recovery_decode_bytes",
                             "survivor bytes those submissions carried "
                             "(k rows x their width)")
            .add_u64_counter("ec_clay_repairs",
                             "objects repaired from repair-plane reads "
                             "(bandwidth-optimal CLAY path)")
            .add_u64_counter("ec_clay_repair_launches",
                             "batched CLAY repair-plan launches")
            .add_u64_counter("ec_clay_repair_fallbacks",
                             "CLAY plane-read repairs that fell back "
                             "to the full-read decode path")
            .add_u64_counter("ec_reconstruct_reads",
                             "degraded reads (a client's, or an "
                             "overwrite's pre-read) served by "
                             "reconstruct-on-read")
            .add_u64_counter("ec_reconstruct_read_bytes",
                             "logical bytes served by "
                             "reconstruct-on-read")
            .add_u64_counter("ec_read_timeouts",
                             "client-read shard fan-outs that hit "
                             "osd_ec_read_timeout")
            .create_perf_counters())


class ECBackend:
    def __init__(self, ec_impl: ErasureCodeInterface, sinfo: StripeInfo,
                 shards: ShardBackend, log: PGLog | None = None,
                 mesh_codec=None, mesh_service=None,
                 launch_queue=None, dispatch_depth: int = 2,
                 perf=None, perf_name: str = "ec", logger=None,
                 read_timeout: float = 30.0,
                 clay_repair: bool = True):
        self.ec_impl = ec_impl
        self.sinfo = sinfo
        self.shards = shards
        self.k = ec_impl.get_data_chunk_count()
        self.m = ec_impl.get_coding_chunk_count()
        self.n = ec_impl.get_chunk_count()
        assert sinfo.k == self.k
        # the shard that holds chunk i (upstream chunk_index): data
        # chunks first, then coding chunks.  The RS plugins map
        # nothing, so shard i is chunk i; an LRC pool interleaves them
        # (docs/PIPELINE.md "Chunk mapping")
        self.chunk_shards = ec_util.chunk_shards(ec_impl)
        self.data_shards = self.chunk_shards[: self.k]
        self.coding_shards = self.chunk_shards[self.k:]
        self.shard_chunk = {s: c for c, s in enumerate(self.chunk_shards)}
        self._logger = logger
        # Optional multi-chip data plane (parallel.DistributedStripeCodec):
        # when set, batched drains and repair decodes dispatch to the
        # sharded collective program instead of the single-chip codec.
        # Acquired from the per-host MeshService when one is supplied
        # (the deployment path, docs/MULTICHIP.md); a directly-injected
        # codec (tests, benches) takes precedence.  Geometry/matrix
        # mismatches are CONFIG errors, not crashes: the backend logs,
        # records mesh_error, and serves from the single-chip plane —
        # a mis-provisioned mesh must never take an OSD down with it.
        self.mesh_error: str | None = None
        self._mesh_service = mesh_service
        if mesh_codec is None and mesh_service is not None:
            impl_matrix = getattr(ec_impl, "matrix", None)
            if impl_matrix is None:
                # no generator matrix to validate against (bitmatrix-
                # only or layered codes): an unvalidated mesh codec
                # could silently write divergent parity — refuse it
                self._mesh_config_error(
                    "plugin exposes no generator matrix to validate "
                    "against the mesh codec")
            else:
                try:
                    mesh_codec = mesh_service.acquire(
                        self.k, self.m,
                        technique=getattr(ec_impl, "technique",
                                          "cauchy"),
                        matrix=impl_matrix)
                except Exception as e:  # noqa: BLE001 — MeshError et al
                    self._mesh_config_error(f"mesh acquire failed: {e}")
                    mesh_codec = None
        if mesh_codec is not None:
            why = self._mesh_geometry_error(mesh_codec)
            if why is not None:
                self._mesh_config_error(why)
                mesh_codec = None
        self.mesh_codec = mesh_codec
        # Per-host EC launch queue (parallel/launch_queue.py): every
        # encode, decode and repair launch of this backend is a
        # submission there (None = the host's own queue) — it coalesces
        # them with OTHER PGs' runs into one super-batch launch per
        # window, pads, counts and records them.  Completion, in-order
        # acks, and failure containment stay per-PG; the queue only
        # owns the launch.
        self._launch_queue = launch_queue if launch_queue is not None \
            else ECLaunchQueue.host_instance()
        # degraded-read fan-out wait (conf osd_ec_read_timeout): was a
        # hardcoded 30 s; timeouts now count (ec_read_timeouts) instead
        # of silently shaping latency
        self.read_timeout = max(0.05, float(read_timeout))
        # CLAY plane-read repair (docs/REPAIR.md): when the plugin is
        # sub-chunked with a repair lowering, single-shard recovery
        # reads only the repair planes of d helpers and rebuilds via a
        # batched GF matmul; off = always full-read decode
        self._clay_repair = bool(clay_repair)
        self._clay_plans: dict[tuple, object] = {}
        self.log = log or PGLog()
        self.lock = threading.RLock()
        self.waiting_state: list[ECOp] = []
        self.waiting_reads: list[ECOp] = []
        self.waiting_commit: list[ECOp] = []
        # ops entered (enqueue) and not yet acked: wherever they are,
        # reading, on the device or committing (wait_idle)
        self._open_ops = 0
        self.completed: int = 0
        self.batched_launches: int = 0
        self.batched_extents: int = 0
        # kernel path of the last fused drain ("hier_acc"/"hier_lsub"/
        # "w32_flat"/"bytes"/"xla"; None before the first fused drain)
        self.fused_path: str | None = None
        self._hold = 0
        # dispatch-ahead pipeline (docs/PIPELINE.md): submitted drains
        # whose device work is in flight, completion in submit order
        self.dispatch_depth = max(1, int(dispatch_depth))
        self.perf = perf if perf is not None else _build_ec_perf(perf_name)
        shards.perf = self.perf
        from collections import deque
        self._inflight: "deque[_Drain]" = deque()
        self._pipeline_win = 0        # pipeline() windows currently open
        self._completing = False      # re-entrancy guard for completion
        self._auto_flush_ms: float | None = None
        self._flush_timer = None
        # projected end-of-chunk per object across IN-FLIGHT drains:
        # the submit-time append/fused decision for drain N+1 must see
        # the sizes drain N will produce, which the (shared) projected
        # hinfo only reflects after N's completion stage runs
        self._sim_chunk: dict[hobject_t, int] = {}
        self._sim_refs: dict[hobject_t, int] = {}
        from .extent_cache import ExtentCache
        self.extent_cache = ExtentCache()
        # projected per-object state for queued-but-uncommitted ops
        # (reference HashInfo "projected sizes for in-flight ops",
        # ECUtil.h:101-160): later ops in the pipeline plan against the
        # in-flight hinfo instance, not the stored one.
        self._projected: dict[hobject_t, dict] = {}

    # -- mesh plane management (docs/MULTICHIP.md) --------------------------

    def _log(self, msg: str) -> None:
        if self._logger is not None:
            self._logger(msg)
        else:
            from ..common.dout import dout
            dout("ec", 1, msg)

    def _mesh_geometry_error(self, mesh_codec) -> str | None:
        """Why `mesh_codec` cannot serve this backend (None = it can).
        These were startup asserts once; a geometry/matrix mismatch is
        an operator config error and must fall back, not crash."""
        if (mesh_codec.k, mesh_codec.m) != (self.k, self.m):
            return (f"mesh codec geometry k={mesh_codec.k} "
                    f"m={mesh_codec.m} does not match the EC profile "
                    f"k={self.k} m={self.m}")
        # technique must match too: cauchy parity written by the mesh
        # is garbage to a reed_sol_van plugin's decode matrix
        impl_matrix = getattr(self.ec_impl, "matrix", None)
        if impl_matrix is not None and \
                not np.array_equal(mesh_codec.matrix, impl_matrix):
            return ("mesh codec generator matrix does not match the "
                    "plugin's — mesh parity would not decode on the "
                    "single-chip plane")
        return None

    def _mesh_config_error(self, why: str) -> None:
        self.mesh_error = why
        self._log(f"EC mesh plane unavailable ({why}); "
                  f"serving from the single-chip codec")

    def _disable_mesh(self, err: BaseException) -> None:
        """Containment: a failed mesh launch aborts its op (the caller
        does that); HERE the backend permanently falls back to the
        single-chip plane so subsequent drains/repairs never touch the
        broken mesh — the queue must not wedge retrying a dead device.
        Reported to the MeshService ledger for `mesh status`."""
        if self.mesh_codec is None:
            return
        # keep a reference for drains already in flight on the mesh:
        # their device futures may be healthy even though new work
        # must not be dispatched there
        self._mesh_fallen = self.mesh_codec
        self.mesh_codec = None
        self.mesh_error = f"mesh plane disabled after failure: {err!r}"
        self._log(self.mesh_error)
        if self.perf:
            self.perf.inc("ec_mesh_errors")
        if self._mesh_service is not None:
            self._mesh_service.note_failure(err)

    def _note_fused_path(self, path: str | None) -> None:
        """Record which fused kernel family served a drain (hier_* =
        the overlapped Pallas kernels, anything else a fallback), at
        completion: the super-batch's path is unknown until the shared
        launch fires."""
        self.fused_path = path
        if self.perf:
            self.perf.inc(
                "ec_fused_kernel_drains"
                if path and path.startswith("hier")
                else "ec_fused_fallback_drains")
            # the same drain under the kernel's own name: a 4 KiB
            # drain on the flat w32 kernel is a fused drain too, not
            # a fallback to anything
            self.perf.dinc(f"ec_drains_by_path.{path or 'none'}")

    def repair_status(self) -> dict:
        """Per-PG repair state (surfaced by the OSD's `repair status`
        asok, docs/REPAIR.md): the helper-bytes-read vs
        reconstructed-bytes ledger — the CLAY savings made visible —
        plus reconstruct-on-read and read-timeout provenance."""
        dump = self.perf.dump() if self.perf else {}

        def u64(key):
            v = dump.get(key, 0)
            return int(v) if isinstance(v, (int, float)) else 0
        helper = u64("ec_repair_helper_bytes")
        rebuilt = u64("ec_repair_reconstructed_bytes")
        return {
            "helper_bytes_read": helper,
            "reconstructed_bytes": rebuilt,
            "helper_bytes_per_rebuilt": round(helper / rebuilt, 3)
            if rebuilt else None,
            "clay_repairs": u64("ec_clay_repairs"),
            "clay_repair_launches": u64("ec_clay_repair_launches"),
            "clay_repair_fallbacks": u64("ec_clay_repair_fallbacks"),
            "clay_plans_cached": len(self._clay_plans),
            "mesh_repair_launches": u64("ec_mesh_repair_launches"),
            "reconstruct_reads": u64("ec_reconstruct_reads"),
            "reconstruct_read_bytes": u64("ec_reconstruct_read_bytes"),
            "read_timeouts": u64("ec_read_timeouts"),
            "read_timeout_s": self.read_timeout,
            "clay_plane_repair": self._clay_repair,
        }

    def mesh_status(self) -> dict:
        """Per-backend plane state (surfaced by the OSD's
        `mesh status` asok)."""
        mc = self.mesh_codec
        return {
            "active": mc is not None,
            "mesh": ({"shard": mc.n_shard, "data": mc.n_data}
                     if mc is not None else None),
            "error": self.mesh_error,
        }

    def batch(self):
        """Batch window: ops submitted inside encode in one codec launch.

        The explicit form of the pipeline's natural batching: with async
        shard I/O, ops pile up in waiting_reads while earlier launches
        are in flight and drain together; with synchronous stores (tests,
        single-process) this context manager provides the same window
        (the `BlueStore deferred`-style dynamic batch window named in
        SURVEY.md section 7 hard parts).
        """
        import contextlib

        @contextlib.contextmanager
        def _win():
            with self.lock:
                self._hold += 1
            try:
                yield
            finally:
                with self.lock:
                    self._hold -= 1
                    if self._hold == 0:
                        self.check_ops()
        return _win()

    def pipeline(self):
        """Dispatch-ahead window: while open, up to `dispatch_depth`
        drains stay in flight on the device (submit of drain N+1
        overlaps compute of drain N); everything flushes — completing
        in submit order — when the window closes.  Unlike batch()
        (which HOLDS ops to coalesce them into one launch), ops drain
        immediately here; only materialization is deferred."""
        import contextlib

        @contextlib.contextmanager
        def _win():
            with self.lock:
                self._pipeline_win += 1
            try:
                yield
            finally:
                with self.lock:
                    self._pipeline_win -= 1
                    if self._pipeline_win == 0:
                        self.flush_pipeline()
        return _win()

    def set_pipelined(self, flush_ms: float = 2.0) -> None:
        """Persistent dispatch-ahead (daemon mode): the window never
        closes, so a flush timer bounds the commit latency of the last
        drains when the op stream goes idle."""
        with self.lock:
            self._pipeline_win += 1
            self._auto_flush_ms = max(0.1, float(flush_ms))

    def flush_pipeline(self) -> None:
        """Complete every in-flight drain, in submit order."""
        with self.lock:
            if self._completing:
                return
            self._completing = True
            try:
                while self._inflight:
                    self._complete_drain(self._inflight.popleft())
            finally:
                self._completing = False
            if self.perf:
                self.perf.set("ec_inflight_depth", 0)

    def _arm_auto_flush(self) -> None:
        if self._auto_flush_ms is None or self._flush_timer is not None:
            return

        def _fire():
            with self.lock:
                self._flush_timer = None
            self.flush_pipeline()

        t = threading.Timer(self._auto_flush_ms / 1000.0, _fire)
        t.daemon = True
        self._flush_timer = t
        t.start()

    def wait_idle(self, timeout: float) -> bool:
        """Block until no op of this PG is in flight — none waiting
        for its state, its reads, the device or its commits — or
        `timeout` passes (False).  Peering waits here so that the shard
        logs it collects hold every sub-write its own ops will ever
        send in the interval that ended."""
        deadline = time.monotonic() + timeout
        while True:
            with self.lock:
                busy = self._open_ops > 0 or bool(self._inflight)
            if not busy:
                return True
            if time.monotonic() >= deadline:
                return False
            time.sleep(0.005)

    def inflight_ops(self) -> list[ECOp]:
        """Ops submitted to the device pipeline, not yet committing
        (for dump_ops_in_flight)."""
        with self.lock:
            return [op for d in self._inflight for op in d.ops]

    # -- object metadata helpers -------------------------------------------

    def _probe(self, oid: hobject_t, repair: bool = False
               ) -> tuple[HashInfo | None, int | None]:
        """Every metadata sweep of this backend (ShardBackend.probe)."""
        self.perf.inc("ec_probe_sweeps")
        return self.shards.probe(oid, self.n, repair)

    def _fetch_hinfo(self, oid: hobject_t) -> HashInfo | None:
        """The authoritative hinfo of an object under repair (recovery
        pushes, scrub repair, CLAY plane reads): hinfo is replicated on
        every shard, so any holder's copy serves — the local shard
        first, and every other one if that is the shard being rebuilt
        (see ShardBackend.probe)."""
        return self._probe(oid, repair=True)[0]

    def _get_hinfo(self, oid: hobject_t) -> HashInfo:
        return self._fetch_hinfo(oid) or HashInfo.make(self.n)

    def _get_size(self, oid: hobject_t) -> int:
        """True (unpadded) object size from the hinfo xattr; falls back
        to the stripe-derived size for objects without one."""
        hinfo, chunk = self._probe(oid)
        if hinfo is not None:
            return hinfo.logical_size
        if chunk is not None:
            return self.sinfo.aligned_chunk_offset_to_logical_offset(
                chunk)
        return 0

    def exists(self, oid: hobject_t) -> bool:
        hinfo, chunk = self._probe(oid)
        return hinfo is not None or chunk is not None

    # -- entry (reference submit_transaction :1483 / start_rmw :1839) ------

    def make_op(self, txn: PGTransaction,
                on_commit: Callable[[], None], top=None) -> ECOp:
        """Stage an op WITHOUT entering the pipeline: prefetches object
        metadata (a blocking RPC fan-out unless the PG is clean, see
        ShardBackend.probe) so no lock is held during it.
        The racy peek at _projected is benign: the plan re-checks it
        under the lock and falls back to a locked probe on a miss."""
        op = ECOp(txn, eversion_t(), on_commit,
                  top=top if top is not None else NULL_TRACKED)
        for oid in txn.ops:
            if oid not in self._projected:
                op.meta[oid] = self._probe(oid)
        return op

    def enqueue(self, op: ECOp, version: eversion_t) -> ECOp:
        """Enter the pipeline; the caller serializes version allocation
        with this call (versions must enter the FIFO in order)."""
        op.version = version
        with self.lock:
            self._open_ops += 1
            self.waiting_state.append(op)
            self.check_ops()
        return op

    def submit_transaction(self, txn: PGTransaction, version: eversion_t,
                           on_commit: Callable[[], None],
                           top=None) -> ECOp:
        return self.enqueue(self.make_op(txn, on_commit, top=top),
                            version)

    # -- pipeline (reference check_ops :2151) -------------------------------

    def check_ops(self) -> None:
        if self._hold:
            return
        self._try_state_to_reads()
        self._try_reads_to_commit()
        # (try_finish_rmw runs from the sub-write callbacks)

    def _try_state_to_reads(self) -> None:
        while self.waiting_state:
            op = self.waiting_state[0]
            # One hinfo fetch sweep per object: the plan needs both the
            # hinfo and the size, and size is derived from hinfo when it
            # exists (over the messenger each shard fetch is a blocking
            # RPC, so the sweep count matters).
            cache: dict = {}

            def fetch(oid):
                """(hinfo|None, shard_size|None): projected (in-flight)
                state first, then the op's prefetched probe, then (rare
                race fallback) a probe under the lock."""
                proj = self._projected.get(oid)
                if proj is not None:
                    return proj["hinfo"], None
                if oid in op.meta:
                    return op.meta[oid]
                if oid not in cache:
                    cache[oid] = self._probe(oid)
                return cache[oid]

            def get_hinfo(oid):
                h, _sz = fetch(oid)
                if h is None:
                    h = HashInfo.make(self.n)
                # later queued ops must chain off this same instance
                proj = self._projected.setdefault(
                    oid, {"hinfo": h, "refs": 0})
                proj["refs"] += 1
                return proj["hinfo"]

            def get_size(oid):
                h, chunk = fetch(oid)
                if h is not None:
                    return h.logical_size
                if chunk is not None:
                    return (self.sinfo
                            .aligned_chunk_offset_to_logical_offset(
                                chunk))
                return 0

            def reset_hinfo(oid):
                """Delete-then-recreate: swap a FRESH hinfo into the
                projected chain so THIS op and later queued ops seed
                from the recreate, while earlier in-flight ops keep
                folding onto the instance they planned against (refs
                bookkeeping rides the same cache entry)."""
                h = HashInfo.make(self.n)
                proj = self._projected.get(oid)
                if proj is not None:
                    proj["hinfo"] = h
                return h

            op.plan = ect.get_write_plan(
                self.sinfo, op.txn, get_hinfo, get_size,
                reset_hinfo=reset_hinfo)
            self.waiting_state.pop(0)
            op.state = "reading"
            self.waiting_reads.append(op)
            reads = []
            for oid, extents in op.plan.to_read.items():
                for e in extents:
                    reads.append((oid, e))
            op.pending_reads = len(reads)
            if reads:
                op.t_rmw_read = time.perf_counter()
            for oid, e in reads:
                self._start_rmw_read(op, oid, e)

    def _start_rmw_read(self, op: ECOp, oid: hobject_t, e: Extent) -> None:
        """Read one stripe-aligned logical extent back from the data
        shards (degraded shards reconstruct via decode)."""
        chunk_off = self.sinfo.aligned_logical_offset_to_chunk_offset(e.off)
        chunk_len = e.length // self.k
        got: dict[int, np.ndarray] = {}
        failed: set[int] = set()
        glock = threading.Lock()

        def on_done(shard: int, data: np.ndarray | None) -> None:
            # replies race on the dispatch executor's threads: count
            # them under a lock, so that exactly ONE caller — whoever
            # brings the k-th answer — continues the op
            with glock:
                if data is None:
                    failed.add(shard)
                else:
                    got[shard] = data
                if len(got) + len(failed) < self.k:
                    return
            with span("ec.rmw_read_done", device_profiler().enabled,
                      pgid=self.perf.name):
                if not failed:
                    logical = ec_util.decode(
                        self.sinfo, self.ec_impl, got, e.length)
                    self._rmw_read_complete(op, oid, e, logical)
                else:
                    self._read_with_reconstruct(op, oid, e, chunk_off,
                                                chunk_len, got, failed)

        for s in self.data_shards:
            self.shards.sub_read(s, oid, chunk_off, chunk_len, on_done)

    def _read_with_reconstruct(self, op, oid, e, chunk_off, chunk_len,
                               got, failed) -> None:
        """Degraded pre-read: pull parity shards until k available
        (reference objects_read_and_reconstruct :2345 +
        get_remaining_shards :1633).  Called once, after every data
        shard has answered."""
        tried = set(got) | set(failed)
        candidates = [s for s in range(self.n) if s not in tried]
        if len(candidates) + len(got) < self.k:
            raise ErasureCodeError(5, f"unrecoverable: {oid} extent {e}")
        if self.ec_impl.ALLOW_PARTIAL_DECODE:
            # a locality code may decode from fewer than k chunks and
            # not from any k: ask what its plan names (an LRC pool's
            # lost data chunk comes back from its local group)
            need = self.ec_impl.minimum_to_decode(
                set(self.data_shards), set(range(self.n)) - failed)
            ask = sorted(set(need) - tried)
            if not ask:
                raise ErasureCodeError(
                    5, f"unrecoverable: {oid} extent {e}")
        else:
            ask = candidates[: self.k - len(got)]
        glock = threading.Lock()
        done = [False]
        waiting = [len(ask)]
        t0 = time.perf_counter()

        def on_done(shard, data):
            with glock:
                if data is not None:
                    got[shard] = data
                waiting[0] -= 1
                if done[0] or waiting[0] > 0:
                    return
                done[0] = True
                have = dict(got)
            logical = self._reconstruct_read(oid, have, chunk_len,
                                             e.length)
            self.perf.hinc("lat_ec_rmw_reconstruct",
                           time.perf_counter() - t0)
            # inside the op's `prepare` phase (no phase anchor)
            op.top.mark_event("ec_rmw_reconstruct")
            self._rmw_read_complete(op, oid, e, logical)

        self.perf.inc("ec_rmw_reconstructs")
        self.perf.inc("ec_rmw_parity_reads", len(ask))
        # the sends of the second round (and, where a parity shard is
        # this OSD's own, its read and what follows); the wait for the
        # replies crosses threads and is `lat_ec_rmw_reconstruct`'s
        with span("ec.rmw_parity_read", device_profiler().enabled,
                  pgid=self.perf.name):
            for s in ask:
                self.shards.sub_read(s, oid, chunk_off, chunk_len,
                                     on_done)

    def _rmw_read_complete(self, op, oid, e, logical) -> None:
        with self.lock:
            op.read_data[(oid, e.off)] = logical
            op.pending_reads -= 1
            self.perf.inc("ec_rmw_reads")
            self.perf.inc("ec_rmw_read_bytes", int(logical.size))
            if op.pending_reads == 0:
                self.perf.hinc("lat_ec_rmw_read",
                               time.perf_counter() - op.t_rmw_read)
                self._try_reads_to_commit()

    # -- encode + commit (reference try_reads_to_commit :1939) --------------

    def _assemble_extent(self, op: ECOp, oid: hobject_t,
                         e: Extent) -> np.ndarray:
        """Overlay new writes on pre-read/zero background for one
        stripe-aligned extent."""
        buf = np.zeros(e.length, dtype=np.uint8)
        # every pre-read that intersects: a write over three stripes
        # or more has its head AND its tail stripe read back, two
        # extents inside this one
        for (roid, roff), data in op.read_data.items():
            if roid != oid:
                continue
            lo = max(e.off, roff)
            hi = min(e.end, roff + data.size)
            if lo < hi:
                buf[lo - e.off:hi - e.off] = data[lo - roff:hi - roff]
        # bytes assembled by earlier in-flight ops win over store reads
        laid = self.extent_cache.overlay(oid, e.off, buf)
        if laid and oid in op.plan.to_read:
            self.perf.inc("ec_rmw_cache_hit_bytes", laid)
        for w in op.txn.ops[oid].writes:
            lo = max(e.off, w.offset)
            hi = min(e.end, w.end)
            if lo < hi:
                buf[lo - e.off:hi - e.off] = w.data[lo - w.offset:hi - w.offset]
        return buf

    def _try_reads_to_commit(self) -> None:
        ready: list[ECOp] = []
        while self.waiting_reads and self.waiting_reads[0].pending_reads == 0:
            ready.append(self.waiting_reads.pop(0))
        if ready:
            try:
                drain = self._submit_drain(ready)
            except Exception as e:  # noqa: BLE001 — encode staging died
                # complete earlier in-flight drains FIRST so their acks
                # (lower versions) precede these ops' error acks —
                # completion stays in submit order even on failure
                self.flush_pipeline()
                for op in ready:
                    self._abort_op(op, e)
            else:
                self._inflight.append(drain)
                if self.perf:
                    self.perf.inc("ec_drain_submits")
                    if "plain" in drain.kinds:
                        self.perf.inc("ec_plain_drains")
                    self.perf.set("ec_inflight_depth", len(self._inflight))
                self._arm_auto_flush()
        self._drain_pipeline()

    # -- submit half: assemble + launch, NO host sync -----------------------

    def _submit_drain(self, ready: list[ECOp]) -> _Drain:
        """The submit half inside its span (`ec.assemble`, whose
        duration is also the `ec_drain_assemble` sample)."""
        # ec.* spans are on when the device profiler is; off, `sp`
        # still times the `ec_drain_assemble` sample
        with span("ec.assemble", device_profiler().enabled,
                  pgid=self.perf.name) as sp:
            drain = self._assemble_and_launch(ready)
        if drain.work:
            drain.t_assemble = sp.wall_s
            if self.perf:
                self.perf.inc("ec_drain_extents", len(drain.work))
                self.perf.tinc("ec_drain_assemble", drain.t_assemble)
        return drain

    def _assemble_and_launch(self, ready: list[ECOp]) -> _Drain:
        """Gather every extent of every ready op, encode the whole
        drain with launches that return device futures (one fused
        launch for appends + one plain launch for overwrites), and
        record the in-flight drain.  Nothing here blocks on the
        device; materialization happens in _complete_drain."""
        k = self.k
        work: list[tuple] = []
        runs: list[np.ndarray] = []
        for op in ready:
            op.state = "encoding"
            for oid, extents in op.plan.will_write.items():
                for e in extents:
                    buf = self._assemble_extent(op, oid, e)
                    # pin so later ops in this (or the next) drain see
                    # these bytes instead of stale store reads
                    self.extent_cache.present(oid, e.off, buf)
                    op.pinned.append((oid, e.off, e.length))
                    nstripes = e.length // self.sinfo.stripe_width
                    work.append((op, oid, e, buf))
                    runs.append(buf.reshape(
                        nstripes, k, self.sinfo.chunk_size)
                        .transpose(1, 0, 2).reshape(k, -1))
        drain = _Drain(ops=ready, work=work, kinds=[],
                       fused_handle=None, fused_pos={},
                       plain_handle=None, plain_cols={})
        if not work:
            # no encode work: no launch/materialize events — a
            # fabricated launch would poison per-stage blame and the
            # lat_ec_encode_launch histogram
            return drain
        # North-star fused path: every chunk-aligned appending extent
        # of the WHOLE drain gets parity + cumulative shard crcs from
        # one kernel launch.  The append decision uses _sim_chunk, the
        # projected end-of-chunk across ALL in-flight drains (the
        # shared hinfo instances only advance at completion).  Non-
        # append extents (overwrites) take the plain parity path: their
        # incremental crc is invalidated anyway (generations work).
        fused_idx: list[int] = []
        plain_idx: list[int] = []
        can_fuse = self.mesh_codec is None and \
            hasattr(self.ec_impl, "encode_extents_with_crc_submit")
        deleted: set[tuple[int, hobject_t]] = set()
        for i, ((op, oid, e, _), run) in enumerate(zip(work, runs)):
            hinfo = op.plan.hash_infos[oid]
            if op.txn.ops[oid].delete and (id(op), oid) not in deleted:
                # delete-then-recreate: the fresh plan hinfo starts at 0
                deleted.add((id(op), oid))
                self._sim_chunk[oid] = 0
            cur = self._sim_chunk.get(oid, hinfo.total_chunk_size)
            chunk_off = self.sinfo.aligned_logical_offset_to_chunk_offset(
                e.off)
            if can_fuse and chunk_off == cur:
                fused_idx.append(i)
                self._sim_chunk[oid] = cur + run.shape[1]
            else:
                plain_idx.append(i)
                self._sim_chunk[oid] = max(cur, chunk_off + run.shape[1])
            self._sim_refs[oid] = self._sim_refs.get(oid, 0) + 1
        # txn-level size effects that land after the writes (mirrors
        # generate_transactions order): truncate clamps the projection.
        # Only for objects this drain TRACKS (has a _sim_refs entry
        # from a work item) — an untracked entry would never be
        # released by _drop_sim_refs and the stale projection would
        # push all later appends off the fused path; pure truncates
        # stay safe via generate's own append re-check
        for op in ready:
            for oid, objop in op.txn.ops.items():
                if objop.truncate_to is not None and \
                        oid in self._sim_refs:
                    self._sim_chunk[oid] = \
                        self.sinfo.logical_to_next_chunk_offset(
                            objop.truncate_to)
        fused_set = set(fused_idx)
        drain.kinds = ["fused" if i in fused_set else "plain"
                       for i in range(len(work))]
        # flight recorder (ops/profiler.py): the queue records its own
        # launches — submissions carry the ops' trace ids so its
        # super-batch record can name its contributors; only a mesh
        # launch is recorded here
        prof = device_profiler()
        traces = tuple(op.top.trace.trace_id for op in ready
                       if op.top.is_tracked) if prof.enabled else ()
        try:
            if fused_idx:
                drain.fused_pos = {wi: p
                                   for p, wi in enumerate(fused_idx)}
                # per-host continuous batching: the queue coalesces
                # these runs with other PGs' into one super-batch
                # launch; kernel-path attribution waits for the launch
                # (completion half)
                drain.fused_handle = self._launch_queue.submit_extents(
                    self.ec_impl, [runs[i] for i in fused_idx],
                    owner=id(self), traces=traces)
                drain.tickets.append(drain.fused_handle)
            if plain_idx:
                col = 0
                for i in plain_idx:
                    drain.plain_cols[i] = col
                    col += runs[i].shape[1]
                plain_runs = [runs[i] for i in plain_idx]
                big = np.concatenate(plain_runs, axis=1) \
                    if len(plain_runs) > 1 else plain_runs[0]
                if self.mesh_codec is not None:
                    rec = prof.begin(
                        "mesh_encode", codec=_codec_label(self.ec_impl),
                        nbytes=int(big.size), traces=traces)
                    try:
                        drain.plain_handle = (
                            "mesh",
                            self.mesh_codec.encode_flat_submit(big))
                    except Exception as e:  # noqa: BLE001 — mesh died
                        # containment: this drain's ops abort (outer
                        # handler), later drains take the single-chip
                        # plane — the mesh never wedges the queue
                        self._disable_mesh(e)
                        raise
                    prof.submitted(rec, f"mesh:x:w{big.shape[1]}",
                                   path="mesh")
                    drain.prof_plain = rec
                    if self.perf:
                        self.perf.inc("ec_mesh_drains")
                else:
                    drain.plain_handle = (
                        "queue", self._launch_queue.submit_chunks(
                            self.ec_impl, big, owner=id(self),
                            traces=traces))
                    drain.tickets.append(drain.plain_handle[1])
            if drain.tickets:
                self.perf.inc("ec_host_queue_drains")
        except Exception:
            # withdraw any queue submissions this drain already made:
            # the owning ops are about to abort, and an orphaned
            # pending submission would launch (and hold) work nobody
            # will ever finalize
            for t in drain.tickets:
                t.cancel()
            # and its projection refs, before the caller aborts the ops
            self._drop_sim_refs(drain)
            raise
        # submit half done: the device work is in flight, no host sync
        # has happened (the launch/materialize split makes host-vs-
        # device wait attributable per op).  Only ops that contributed
        # encode extents get the event
        worked = {id(op) for op, _, _, _ in work}
        for op in ready:
            if id(op) in worked:
                op.top.mark_event("ec_encode_launch")
        drain.work = [(op, oid, e, run)
                      for (op, oid, e, _), run in zip(work, runs)]
        self.batched_launches += 1 + (1 if fused_idx and plain_idx
                                      else 0)
        self.batched_extents += len(work)
        return drain

    def _drain_pipeline(self) -> None:
        """Completion policy: keep up to dispatch_depth drains in
        flight while more work is imminent (a pipeline window is open,
        or ops are queued behind us); otherwise flush — a lone op with
        nothing behind it completes synchronously, preserving the
        pre-pipeline contract."""
        if self._completing:
            return
        self._completing = True
        try:
            while self._inflight:
                more = (self._pipeline_win > 0
                        or bool(self.waiting_state)
                        or bool(self.waiting_reads
                                and self.waiting_reads[0]
                                .pending_reads == 0))
                allowed = self.dispatch_depth if more else 0
                if len(self._inflight) <= allowed:
                    break
                self._complete_drain(self._inflight.popleft())
        finally:
            self._completing = False
        if self.perf:
            self.perf.set("ec_inflight_depth", len(self._inflight))

    # -- completion half: materialize + fold + sub-writes -------------------

    def _drop_sim_refs(self, drain: _Drain) -> None:
        """Drop this drain's projection refs; the LAST in-flight drain
        touching an object releases its _sim_chunk entry so the next
        submit re-seeds from the (now current) hinfo.  Must run on
        EVERY completion outcome — a leaked ref would strand a stale
        projection and silently push all later appends of the object
        off the fused path."""
        for _, oid, _, _ in drain.work:
            self._sim_refs[oid] -= 1
            if self._sim_refs[oid] <= 0:
                del self._sim_refs[oid]
                self._sim_chunk.pop(oid, None)

    def _complete_drain(self, drain: _Drain) -> None:
        with span("ec.complete", device_profiler().enabled,
                  pgid=self.perf.name):
            self._materialize_and_commit(drain)

    def _materialize_and_commit(self, drain: _Drain) -> None:
        t0 = time.perf_counter()
        prof = device_profiler()
        try:
            try:
                fh = drain.fused_handle
                fused_res = []
                if fh is not None:
                    # result() forces the shared super-batch to launch
                    # if the window hasn't fired (flush-on-demand keeps
                    # lone-PG sync semantics) and demuxes THIS
                    # submission's per-run results
                    fused_res = fh.result()
                    self._note_fused_path(fh.path)
                plain_par = None
                if drain.plain_handle is not None:
                    kind, h = drain.plain_handle
                    t_p = time.perf_counter()
                    if kind == "queue":
                        plain_par = np.asarray(h.result())
                    elif kind == "mesh":
                        # _mesh_fallen: the plane was disabled after
                        # this drain launched — its own future may
                        # still materialize (and aborts cleanly if not)
                        mc = self.mesh_codec or \
                            getattr(self, "_mesh_fallen", None)
                        if mc is None:
                            raise RuntimeError(self.mesh_error or
                                               "mesh plane disabled")
                        plain_par = mc.encode_flat_finalize(h)
                        prof.materialized(drain.prof_plain,
                                          time.perf_counter() - t_p)
            except Exception as e:  # noqa: BLE001 — device/encode failure
                if self.perf:
                    self.perf.inc("ec_drain_errors")
                # the fused and plain halves are separate queue
                # tickets: when one raises, withdraw the other if it
                # is still pending — otherwise the window worker
                # launches it for nobody (post-launch cancel is a
                # no-op and the unread results are simply dropped)
                for t in drain.tickets:
                    t.cancel()
                if drain.plain_handle is not None and \
                        drain.plain_handle[0] == "mesh":
                    # mesh finalize failure: abort THIS drain's ops,
                    # fall back to the single-chip plane for all later
                    # drains (reference analog: marking the backend's
                    # transport down rather than retrying into it)
                    self._disable_mesh(e)
                for op in drain.ops:
                    self._abort_op(op, e)
                return
            device_dt = time.perf_counter() - t0
            worked = {id(op) for op, _, _, _ in drain.work}
            # trace stitching (ops/profiler.py): the launch ids that
            # served this drain land as events on every contributing
            # op's timeline — and a first-compile that stalled past
            # the threshold lands FIRST, so slow-op blame (largest
            # gap ends at the event) names the bucket that compiled
            # instead of a bare "ec_encode_materialize"
            # (a ticket carries its launch record's fields; of a mesh
            # launch the record itself is at hand)
            stitches = [
                (src.launch_id, src.bucket, src.compiled, src.compile_s,
                 src.cache_hit)
                for src in drain.tickets + [drain.prof_plain]
                if src is not None and src.launch_id is not None]
            stall_s = prof.stall_s
            for op in drain.ops:
                if id(op) in worked:
                    for lid, bucket, compiled, comp_s, c_hit in stitches:
                        # a persistent-cache hit is a fast first-launch,
                        # not a stall — it never takes the compile blame
                        if compiled and not c_hit and comp_s >= stall_s:
                            op.top.mark_event(
                                f"first_compile({bucket})")
                        op.top.mark_event(f"launch({lid})")
                    op.top.mark_event("ec_encode_materialize")
            encoded_by_op: dict[int, dict] = {id(op): {}
                                              for op in drain.ops}
            crcs_by_op: dict[int, dict] = {id(op): {} for op in drain.ops}
            fused_ls: dict[int, tuple] = {}
            for i, (op, oid, e, run) in enumerate(drain.work):
                if drain.kinds[i] == "fused":
                    par, l, tail, body = fused_res[drain.fused_pos[i]]
                    par = np.asarray(par)
                    fused_ls[i] = (l, tail, body)
                else:
                    col = drain.plain_cols[i]
                    par = plain_par[:, col:col + run.shape[1]]
                encoded_by_op[id(op)][(oid, e.off)] = \
                    ec_util.shard_rows(self.chunk_shards, run, par)
            self._fold_drain_crcs(drain, encoded_by_op, fused_ls,
                                  crcs_by_op)
            t1 = time.perf_counter()
            for op in drain.ops:
                try:
                    self._commit_op(op, encoded_by_op[id(op)],
                                    crcs_by_op[id(op)])
                except Exception as e:  # noqa: BLE001
                    if self.perf:
                        self.perf.inc("ec_drain_errors")
                    self._abort_op(op, e)
            if self.perf:
                self.perf.tinc("ec_drain_device", device_dt)
                self.perf.tinc("ec_drain_commit",
                               time.perf_counter() - t1)
        finally:
            self._drop_sim_refs(drain)

    def _fold_drain_crcs(self, drain: _Drain, encoded_by_op: dict,
                         fused_ls: dict, crcs_by_op: dict) -> None:
        """ONE ordered host pass over the drain computing cumulative
        shard crcs for every appending extent: fused extents fold the
        device-combined L (O(1) combines per shard), plain extents
        (mesh drains, CPU plugins) fold all k+m shard rows per run in
        a single vectorized crc32c_rows call.  Seeds chain per object
        through the walk exactly as generate_transactions will apply
        them; a mismatch (projection raced a truncate/delete) simply
        yields no precomputed crc and generate falls back to its own
        host append — correctness never depends on the projection."""
        from ..common import crc32c as _crc
        sim_size: dict[hobject_t, int] = {}
        sim_hash: dict[hobject_t, list[int]] = {}
        items_by_op: dict[int, list[int]] = {}
        for i, (op, _, _, _) in enumerate(drain.work):
            items_by_op.setdefault(id(op), []).append(i)
        for op in drain.ops:
            for oid, objop in op.txn.ops.items():
                if objop.delete:
                    # recreate seeds from the op's FRESH plan hinfo
                    sim_size[oid] = 0
                    sim_hash.pop(oid, None)
            for i in items_by_op.get(id(op), []):
                _, oid, e, run = drain.work[i]
                hinfo = op.plan.hash_infos[oid]
                chunk_off = (self.sinfo
                             .aligned_logical_offset_to_chunk_offset(
                                 e.off))
                cur = sim_size.get(oid, hinfo.total_chunk_size)
                width = run.shape[1]
                if chunk_off != cur:
                    sim_size[oid] = max(cur, chunk_off + width)
                    sim_hash.pop(oid, None)
                    continue
                seeds = sim_hash.get(
                    oid, list(hinfo.cumulative_shard_hashes))
                if i in fused_ls:
                    # the launch's crcs are in chunk order, the
                    # hinfo's in shard order
                    l, tail, body = fused_ls[i]
                    by_chunk = self.ec_impl.fold_extent_crcs(
                        l, tail, [seeds[s] for s in self.chunk_shards],
                        body)
                    crcs = [0] * self.n
                    for c, s in enumerate(self.chunk_shards):
                        crcs[s] = by_chunk[c]
                else:
                    crcs = _crc.crc32c_rows(
                        encoded_by_op[id(op)][(oid, e.off)], seeds)
                sim_hash[oid] = crcs
                sim_size[oid] = cur + width
                crcs_by_op[id(op)][(oid, e.off)] = crcs
            for oid, objop in op.txn.ops.items():
                if objop.truncate_to is not None:
                    sim_size[oid] = \
                        self.sinfo.logical_to_next_chunk_offset(
                            objop.truncate_to)
                    sim_hash.pop(oid, None)

    def _abort_op(self, op: ECOp, err: Exception) -> None:
        """Failure path (satellite of the pipeline work): an op that
        dies before/at commit is routed through the in-order finish
        queue with its error attached — _try_finish_rmw releases its
        pinned extents (stale assembled bytes must never satisfy a
        later drain's overlay), drops its projection refs, and acks it
        AFTER every earlier op, so the pipeline never wedges and acks
        never reorder."""
        op.error = err
        op.state = "failed"
        op.pending_commits = 0
        if op not in self.waiting_commit:
            self.waiting_commit.append(op)
        self._try_finish_rmw()

    def _commit_op(self, op: ECOp, encoded: dict,
                   crcs: dict | None = None) -> None:
        # PG log entries with rollback info (reference log_operation :958
        # + ecbackend.rst local-rollbackability).  Snapshot rollback
        # state BEFORE generate_transactions mutates the hinfo.
        entries: list[LogEntry] = []
        gen_oids: set[hobject_t] = set()
        for oid, objop in op.txn.ops.items():
            rb = RollbackInfo()
            old_size = op.plan.sizes.get(oid, 0)
            hinfo = op.plan.hash_infos.get(oid)
            existed = old_size > 0 or (
                hinfo is not None and hinfo.total_chunk_size > 0)
            if not objop.delete:
                rb.append_old_size = old_size
                aligned_old = self.sinfo.logical_to_next_stripe_offset(
                    old_size)
                rb.old_chunk_size = (
                    self.sinfo.aligned_logical_offset_to_chunk_offset(
                        aligned_old))
                # pure_append == undo is a truncate: tail-only writes,
                # no truncate of existing data, and no user xattr
                # mutations
                rb.pure_append = (
                    bool(op.plan.will_write.get(oid))
                    and all(e.off >= aligned_old
                            for e in op.plan.will_write.get(oid, []))
                    and (objop.truncate_to is None or not existed)
                    and not objop.attrs)
                rb.hinfo_old = hinfo.encode() if existed else None
                # what every shard is about to write, in chunk space:
                # the shard patches its chunk_crc from these bytes
                rb.extents = [
                    (self.sinfo.aligned_logical_offset_to_chunk_offset(
                        e.off), e.length // self.sinfo.k)
                    for e in op.plan.will_write.get(oid, [])] \
                    if not objop.replace else None
                # (a write_full's hinfo is fresh: the shard re-hashes
                # its chunk_crc whole rather than patch from it)
            # anything not a pure append keeps the old object under a
            # generation so the shard can roll it back locally
            # (reference ecbackend.rst local-rollbackability contract)
            if objop.delete or (existed and not rb.pure_append):
                rb.kept_generation = op.version.version
                gen_oids.add(oid)
            self.log.add(LogEntry(
                op.version, oid,
                LogOp.DELETE if objop.delete else LogOp.MODIFY, rb))
            entries.append(self.log.entries[-1])
        with span("ec.fanout", device_profiler().enabled,
                  trace_id=op.top.trace.trace_id
                  if op.top.is_tracked else ""):
            txns, _ = ect.generate_transactions(
                self.sinfo, self.n, op.plan, op.txn, encoded, crcs,
                gen=op.version.version, gen_oids=gen_oids)
            self._fan_out(op, entries, txns)

    def _fan_out(self, op: ECOp, entries: list, txns: list) -> None:
        """Send the k+m shard transactions (`sub_write_sent`)."""
        op.state = "committing"
        op.pending_commits = self.n
        self.waiting_commit.append(op)
        top = op.top
        tracked = top.is_tracked
        # one child span for the whole shard fan-out (the holder's
        # sub-op description carries the shard); per-shard spans would
        # cost n uuid draws per op on the hot path
        wire_trace = top.trace.child().to_wire() if tracked else None
        if tracked:
            top.mark_event("sub_write_sent")

        def on_commit(shard: int,
                      error: Exception | None = None) -> None:
            # error: the shard's holder refused the write (a sub-write
            # of an interval it has since left, OSDDaemon._dispatch) —
            # the op drains and carries it to the ack like a failed send
            if tracked:
                top.mark_event(f"sub_write_ack({shard})")
            with self.lock:
                if error is not None:
                    op.error = op.error or error
                    self.perf.inc("ec_drain_errors")
                op.pending_commits -= 1
                if op.pending_commits == 0:
                    self._try_finish_rmw()

        rf = self.log.rollforward_to
        for s in range(self.n):
            try:
                self.shards.sub_write(s, txns[s], on_commit,
                                      log_entries=entries,
                                      at_version=op.version,
                                      rollforward_to=rf,
                                      trace=wire_trace,
                                      top=top if tracked else None)
            except Exception as e:  # noqa: BLE001 — a failed sub-write
                # must not wedge the in-order commit queue: count the
                # shard as resolved (failed) so the op drains, carrying
                # the error to the ack (reference marks the PG
                # inconsistent and lets scrub/peering repair the shard)
                op.error = op.error or e
                if self.perf:
                    self.perf.inc("ec_drain_errors")
                on_commit(s)

    def _try_finish_rmw(self) -> None:
        """reference try_finish_rmw :2103: in-order completion, advance
        rollforward bounds, ack clients."""
        while self.waiting_commit and \
                self.waiting_commit[0].pending_commits == 0:
            op = self.waiting_commit.pop(0)
            op.state = "failed" if op.error is not None else "done"
            op.top.mark_event("failed" if op.error is not None
                              else "commit")
            self.log.roll_forward_to(op.version)
            # unpin EXACTLY what this op presented + drop projected
            # refs (op.pinned, not the plan: a mid-assembly abort may
            # have pinned only a prefix of the plan's extents)
            for oid, off, length in op.pinned:
                self.extent_cache.release(oid, off, length)
            op.pinned.clear()
            for oid in op.txn.ops:
                proj = self._projected.get(oid)
                if proj is not None:
                    proj["refs"] -= 1
                    if proj["refs"] <= 0:
                        del self._projected[oid]
            self.completed += 1
            self._open_ops -= 1
            op.on_commit()
        self.check_ops()

    # -- client reads (reference objects_read_and_reconstruct :2345) --------

    def read(self, oid: hobject_t, off: int = 0,
             length: int | None = None) -> np.ndarray:
        """Client read.  Healthy path: the k data shards answer and the
        logical bytes reassemble without a decode.  Degraded path
        (reconstruct-on-read, docs/REPAIR.md): any data-shard failure
        fans out to the parity shards IMMEDIATELY — known-down holders
        fail synchronously, so a degraded object pays one extra fan-out,
        not a timeout — and the missing rows rebuild through the
        batched decode path (mesh / launch queue), the
        same machinery background repair uses.  The fan-out wait is
        `osd_ec_read_timeout` (was a hardcoded 30 s) and every expiry
        counts in ec_read_timeouts instead of silently returning
        short."""
        size = self._get_size(oid)
        if length is None:
            length = size - off
        if length <= 0 or off >= size:
            return np.empty(0, dtype=np.uint8)
        start, span = self.sinfo.offset_len_to_stripe_bounds(off, length)
        chunk_off = self.sinfo.aligned_logical_offset_to_chunk_offset(start)
        chunk_len = span // self.k
        glock = threading.Lock()
        got: dict[int, np.ndarray] = {}
        failed: set[int] = set()
        ready = threading.Event()
        issued = [0]
        # any k replies decode an MDS code; a locality code decodes
        # from particular shards, not from any k
        partial = self.ec_impl.ALLOW_PARTIAL_DECODE

        def enough() -> bool:
            return self.decodes(self.data_shards, got) if partial \
                else len(got) >= self.k

        def on_done(shard, data):
            with glock:       # replies race on reader threads
                if data is None:
                    failed.add(shard)
                else:
                    got[shard] = data
                # set INSIDE the lock: the degraded transition below
                # clears + re-arms (issued k -> n) under the same
                # lock, so a reply's stale-issued fire decision can
                # never land after the clear
                if enough() or len(got) + len(failed) >= issued[0]:
                    ready.set()
        on_done.loop_safe = True      # store + Event.set only: may run
        #                               inline on the reactor

        issued[0] = self.k
        self.shards.sub_read_batch(
            [(s, oid, chunk_off, chunk_len) for s in self.data_shards],
            on_done)
        timeout = self.read_timeout
        with glock:
            need_parity = bool(failed) and len(got) < self.k
        if not need_parity:
            if not ready.wait(timeout=timeout):
                if self.perf:
                    self.perf.inc("ec_read_timeouts")
            with glock:
                need_parity = len(got) < self.k
        if need_parity:
            # degraded: fan out to parity shards until k gathered
            # (reference get_remaining_shards :1633 / fast_read); a
            # locality code asks only what its plan names (an LRC
            # pool's lost data chunk: its local group)
            more = self.coding_shards
            if partial:
                with glock:
                    lost = failed | (set(self.data_shards) - set(got))
                need = self.ec_impl.minimum_to_decode(
                    set(self.data_shards), set(range(self.n)) - lost)
                more = sorted(set(need) - set(self.data_shards))
            with glock:
                ready.clear()
                issued[0] = self.k + len(more)
                if enough() or len(got) + len(failed) >= issued[0]:
                    ready.set()
            self.shards.sub_read_batch(
                [(s, oid, chunk_off, chunk_len) for s in more], on_done)
            if not ready.wait(timeout=timeout) and self.perf:
                self.perf.inc("ec_read_timeouts")
        with glock:
            have = dict(got)
        if not self.decodes(self.data_shards, have):
            raise ErasureCodeError(5, f"unrecoverable read {oid}")
        if set(self.data_shards) <= set(have):
            use = {s: have[s] for s in self.data_shards}
            logical = ec_util.decode(self.sinfo, self.ec_impl, use, span)
        else:
            logical = self._reconstruct_read(oid, have, chunk_len, span)
        return logical[off - start:off - start + length]

    def _reconstruct_read(self, oid: hobject_t,
                          have: dict[int, np.ndarray],
                          chunk_len: int, nbytes: int) -> np.ndarray:
        """Reconstruct-on-read: rebuild the missing data shards of a
        degraded read (a client's, or an overwrite's pre-read) through
        the batched decode path — the mesh
        collective when that plane is up, else the per-host launch
        queue (co-batched with other PGs' repair decodes).  Two kinds
        keep the codec's own dict decode: a code that need not decode
        from any k chunks (ALLOW_PARTIAL_DECODE: LRC, SHEC) decodes by
        its plan from every shard at hand, and a sub-chunked code
        (CLAY), whose plane layout a partial chunk run does not
        respect, from k of them."""
        with span("ec.reconstruct", device_profiler().enabled,
                  pgid=self.perf.name):
            if self.perf:
                self.perf.inc("ec_reconstruct_reads")
                self.perf.inc("ec_reconstruct_read_bytes", nbytes)
            if self.ec_impl.ALLOW_PARTIAL_DECODE:
                return ec_util.decode(self.sinfo, self.ec_impl, have,
                                      nbytes)
            use = dict(list(sorted(have.items()))[: self.k])
            if self.ec_impl.get_sub_chunk_count() != 1:
                return ec_util.decode(self.sinfo, self.ec_impl, use, nbytes)
            # the batched decode works in chunk order: rows 0..k-1 of
            # its result are the data chunks, in logical order
            use = {self.shard_chunk[s]: d for s, d in use.items()}
            survivors = tuple(sorted(use))
            erasures = [s for s in range(self.n) if s not in use]
            targets = tuple(s for s in range(self.k) if s not in use)
            dec = None
            if self.mesh_codec is not None:
                try:
                    avail = np.stack([use[s] for s in survivors])
                    rows = self.mesh_codec.decode_flat(avail, survivors,
                                                       targets)
                    dec = np.zeros((self.n, chunk_len), dtype=np.uint8)
                    for s, d in use.items():
                        dec[s] = d
                    for i, t in enumerate(targets):
                        dec[t] = rows[i]
                except Exception as e:  # noqa: BLE001 — mesh died mid-read
                    self._disable_mesh(e)
                    dec = None
            if dec is None:
                dense = np.zeros((self.n, chunk_len), dtype=np.uint8)
                for s, d in use.items():
                    dense[s] = d
                ticket = self._launch_queue.submit_decode(
                    self.ec_impl, dense, erasures, owner=id(self))
                # the calling thread blocks here until the decode launch
                # has run and its result is on the host
                with span("ec.decode_wait", device_profiler().enabled,
                          pgid=self.perf.name) as sp:
                    dec = np.asarray(ticket.result())
                self.perf.hinc("lat_ec_decode_wait", sp.wall_s)
            nstripes = chunk_len // self.sinfo.chunk_size
            logical = dec[: self.k] \
                .reshape(self.k, nstripes, self.sinfo.chunk_size) \
                .transpose(1, 0, 2).reshape(-1)
            return logical[:nbytes]

    # -- recovery (reference continue_recovery_op :570) ---------------------
    #
    # Batched and mesh-native (docs/MULTICHIP.md): an OSD-loss storm
    # queues MANY objects missing the SAME shards, so the batch entry
    # fans out every object's survivor reads concurrently, groups the
    # results by (survivors, targets) recovery geometry, and rebuilds
    # each group in as few decodes as its width allows — a sharded
    # collective launch on the mesh plane (survivor rows over the
    # 'shard' axis), or on the single-chip plane the launch queue's
    # device decode of the group's objects side by side, capped at
    # launch_queue.recovery_decode_cap.  The reference's
    # continue_recovery_op gathers k shards to one node and decodes
    # per object; here the whole queue is a handful of launches.

    def recover_shard(self, oid: hobject_t, missing: list[int],
                      push: Callable[[int, np.ndarray, HashInfo], None]
                      ) -> None:
        """Rebuild `missing` shards of oid from any k survivors and hand
        each to `push(shard, data, hinfo)` (the caller writes it to the
        new home — locally or over the wire)."""
        res = self.recover_shards_batch([(oid, list(missing))],
                                        lambda _oid: push)
        err = res.get(oid)
        if err is not None:
            raise err

    def _start_recovery_reads(self, oid: hobject_t,
                              missing: list[int]) -> dict:
        """Phase 1 of a batched recovery: metadata probe + survivor
        read fan-out for ONE object, returning the gathering state
        WITHOUT waiting — a storm of objects issues all its reads
        before the first wait, so shard holders serve them
        concurrently."""
        hinfo = self._get_hinfo(oid)
        chunk_len = None
        for s in range(self.n):
            if s in missing:
                continue
            chunk_len = self.shards.stat(s, oid)
            if chunk_len is not None:
                break
        if chunk_len is None:
            raise ErasureCodeError(5, f"cannot recover {oid}: no survivor")
        got: dict[int, np.ndarray] = {}
        glock = threading.Lock()
        done = {"n": 0}
        ready = threading.Event()
        sources = [s for s in range(self.n) if s not in missing]
        # any k replies decode an MDS code; a locality code decodes
        # from particular shards (an LRC's lost chunk: its local
        # group), not from any k
        partial = self.ec_impl.ALLOW_PARTIAL_DECODE

        def enough() -> bool:
            return self.decodes(missing, got) if partial \
                else len(got) >= self.k

        def on_done(sh, d):
            with glock:       # replies race on reader threads
                if d is not None:
                    got[sh] = d
                done["n"] += 1
                fire = enough() or done["n"] >= len(sources)
            if fire:
                ready.set()
        on_done.loop_safe = True      # store + Event.set only

        self.shards.sub_read_batch(
            [(s, oid, 0, chunk_len) for s in sources], on_done)
        return {"oid": oid, "missing": list(missing), "hinfo": hinfo,
                "chunk_len": chunk_len, "got": got, "glock": glock,
                "ready": ready}

    def decodes(self, want, have) -> bool:
        """Whether the shards in `have` decode the shards in `want`
        (upstream ECRecPred with want = every shard): any k of an MDS
        code; for a locality code, what its layers can chain to."""
        try:
            self.ec_impl.minimum_to_decode(set(want), set(have))
        except ErasureCodeError:
            return False
        return True

    def _verify_recovered(self, st: dict, s: int,
                          data: np.ndarray) -> None:
        """Verify a rebuilt shard against the stored hinfo (reference
        handle_sub_read crc check, ECBackend.cc:991)."""
        from ..common import crc32c as _crc
        hinfo = st["hinfo"]
        want = hinfo.get_chunk_hash(s)
        got_crc = _crc.crc32c(data.tobytes(), 0xFFFFFFFF)
        if hinfo.crc_valid and \
                hinfo.total_chunk_size == st["chunk_len"] and \
                got_crc != want:
            raise ErasureCodeError(
                5, f"recovered shard {s} of {st['oid']} crc mismatch "
                   f"{got_crc:#x} != {want:#x}")

    # objects per recovery sub-batch: bounds BOTH the concurrent
    # survivor-read fan-out and the peak survivor-chunk memory
    # (~max * k * chunk_len held at once) — a storm on a huge PG must
    # not OOM the daemon or flood peers the way an uncapped all-at-
    # once fan-out would, while still collapsing to one launch per
    # geometry group within each slice
    RECOVER_BATCH_MAX = 64

    def recover_shards_batch(
            self, items: list[tuple[hobject_t, list[int]]],
            push_for: Callable[[hobject_t], Callable]) -> dict:
        """Rebuild many objects' missing shards in as few decode
        launches as the recovery geometry allows.  items: [(oid,
        missing_shards)]; push_for(oid) -> the per-object
        push(shard, data, hinfo) sink.  Returns {oid: None on success
        | the per-object Exception} — one object's failure never
        blocks the rest of the queue.  Processed in bounded slices
        (RECOVER_BATCH_MAX) so arbitrarily long recovery queues run
        at bounded memory and read concurrency."""
        results: dict[hobject_t, Exception | None] = {}
        step = self.RECOVER_BATCH_MAX
        for lo in range(0, len(items), step):
            results.update(self._recover_shards_slice(
                items[lo:lo + step], push_for))
        return results

    def _recover_shards_slice(
            self, items: list[tuple[hobject_t, list[int]]],
            push_for: Callable[[hobject_t], Callable]) -> dict:
        results: dict[hobject_t, Exception | None] = {}
        states: list[dict] = []
        clay_states: list[dict] = []
        # ec.recover_reads: the slice's survivor reads issued -> the
        # replies every object waits for (k of them, or all sent)
        reads_sp = spans.begin("ec.recover_reads",
                               device_profiler().enabled,
                               pgid=self.perf.name)
        # phase 1: every object's survivor reads in flight before any
        # wait (the fan-out IS the storm's concurrency).  Single-shard
        # losses of a sub-chunked plugin with a repair lowering take
        # the bandwidth-optimal CLAY path: only the q^{t-1} repair
        # planes of d helpers are read (1/q of each helper chunk)
        for oid, missing in items:
            try:
                st = None
                if self._clay_repair_eligible(missing):
                    st = self._start_clay_repair_reads(oid, missing[0])
                if st is not None:
                    clay_states.append(st)
                else:
                    states.append(self._start_recovery_reads(
                        oid, missing))
            except Exception as e:  # noqa: BLE001
                results[oid] = e
        # phase 2 (CLAY): collect plane reads; any helper failure falls
        # back to the full-read decode path for that object
        clay_groups: dict[tuple, list[dict]] = {}
        for st in clay_states:
            st["ready"].wait(timeout=self.read_timeout)
            with st["glock"]:
                complete = not st["failed"] and st["left"] == 0
            if not complete:
                if self.perf:
                    self.perf.inc("ec_clay_repair_fallbacks")
                try:
                    states.append(self._start_recovery_reads(
                        st["oid"], st["missing"]))
                except Exception as e:  # noqa: BLE001
                    results[st["oid"]] = e
                continue
            if self.perf:
                self.perf.inc("ec_repair_helper_bytes",
                              st["helper_bytes"])
            clay_groups.setdefault(
                (st["lost"], st["helpers"], st["chunk_len"]),
                []).append(st)
        # phase 2 (full): collect; drop objects that can't reach k
        # survivors (a locality code's decode judges its own)
        groups: dict[tuple, list[dict]] = {}
        partial = self.ec_impl.ALLOW_PARTIAL_DECODE
        for st in states:
            st["ready"].wait(timeout=self.read_timeout)
            with st["glock"]:
                # snapshot under a DIFFERENT name: `got` is the
                # closure cell late on_done callbacks still write into
                have = dict(st["got"])
            if len(have) < self.k and not partial:
                results[st["oid"]] = ErasureCodeError(
                    5, f"cannot recover {st['oid']}: "
                       f"{len(have)} < k={self.k}")
                continue
            st["have"] = have
            if self.perf:
                self.perf.inc("ec_repair_helper_bytes",
                              len(have) * st["chunk_len"])
            # the first k replies in shard order are the decode's
            # survivors, whichever others answered too: the geometry
            # (and the decode's program) is the same for every object
            # of a PG missing the same shards
            survivors = tuple(sorted(have))[: self.k]
            targets = tuple(sorted(st["missing"]))
            st["survivors"] = survivors
            groups.setdefault((survivors, targets), []).append(st)
        spans.end(reads_sp)
        for (lost, helpers, _clen), sts in clay_groups.items():
            try:
                self._clay_repair_group(lost, helpers, sts, push_for)
            except Exception as e:  # noqa: BLE001 — whole-group launch
                for st in sts:
                    results.setdefault(st["oid"], e)
                continue
            for st in sts:
                results.setdefault(st["oid"], st.get("error"))
        # phase 3: one decode per geometry group (per capped slice of
        # it on the launch queue)
        for (survivors, targets), sts in groups.items():
            try:
                self._decode_recovery_group(survivors, targets, sts,
                                            push_for)
            except Exception as e:  # noqa: BLE001 — whole-group launch
                for st in sts:
                    results.setdefault(st["oid"], e)
                continue
            for st in sts:
                results.setdefault(st["oid"],
                                   st.get("error"))
        return results

    # -- CLAY plane-read repair (docs/REPAIR.md) ----------------------------

    def _clay_repair_eligible(self, missing: list[int]) -> bool:
        return (self._clay_repair and len(missing) == 1 and
                self.ec_impl.get_sub_chunk_count() > 1 and
                hasattr(self.ec_impl, "repair_matrix"))

    def _clay_plan(self, lost: int, helpers: tuple[int, ...]):
        """Cached ClayRepairPlan for one (lost, helper set) — the host
        plane-solver runs once, every repair after is a batched GF
        matmul (parallel/mesh.ClayRepairPlan)."""
        key = (lost, helpers)
        plan = self._clay_plans.get(key)
        if plan is None:
            from ..parallel.mesh import ClayRepairPlan
            plan = ClayRepairPlan.build(self.ec_impl, lost, helpers)
            self._clay_plans[key] = plan
        return plan

    def _start_clay_repair_reads(self, oid: hobject_t,
                                 lost: int) -> dict | None:
        """Phase 1 of a CLAY repair: fan out the repair-plane sub-chunk
        runs of the d chosen helpers — 1/q of each helper chunk, the
        bandwidth-optimal read set — without waiting.  Returns None
        when the geometry can't serve the plane path (no helper set,
        chunk not sub-aligned): the caller falls back to full reads."""
        impl = self.ec_impl
        sub = impl.get_sub_chunk_count()
        hinfo = self._get_hinfo(oid)
        chunk_len = None
        for s in range(self.n):
            if s == lost:
                continue
            chunk_len = self.shards.stat(s, oid)
            if chunk_len is not None:
                break
        if chunk_len is None:
            raise ErasureCodeError(5,
                                   f"cannot recover {oid}: no survivor")
        if chunk_len % sub:
            return None
        helpers = impl.choose_helpers(
            lost, set(range(self.n)) - {lost})
        if helpers is None:
            return None
        helpers = tuple(sorted(helpers))
        sub_size = chunk_len // sub
        planes = impl.repair_planes(lost)
        runs = impl._runs(planes)
        row0 = []
        acc = 0
        for _s0, cnt in runs:
            row0.append(acc)
            acc += cnt
        got = {h: np.zeros((len(planes), sub_size), dtype=np.uint8)
               for h in helpers}
        glock = threading.Lock()
        state = {"oid": oid, "missing": [lost], "lost": lost,
                 "helpers": helpers, "hinfo": hinfo,
                 "chunk_len": chunk_len, "sub_size": sub_size,
                 "got": got, "glock": glock, "failed": set(),
                 "left": len(helpers) * len(runs),
                 "helper_bytes": len(helpers) * len(planes) * sub_size,
                 "ready": threading.Event()}

        # one callback closure per run index: on_done only reports the
        # shard, so the run identity must ride the closure
        for ri, (s0, cnt) in enumerate(runs):
            def make_cb(r0=row0[ri], cnt=cnt):
                def cb(sh, d):
                    with glock:
                        if d is None:
                            state["failed"].add(sh)
                        else:
                            if d.size < cnt * sub_size:
                                # sparse tail: pad like the healthy
                                # shard-read path does
                                d = np.concatenate(
                                    [d, np.zeros(cnt * sub_size - d.size,
                                                 dtype=np.uint8)])
                            got[sh][r0:r0 + cnt] = \
                                d.reshape(cnt, sub_size)
                        state["left"] -= 1
                        fire = state["left"] == 0 or state["failed"]
                    if fire:
                        state["ready"].set()
                cb.loop_safe = True      # store + Event.set only
                return cb
            self.shards.sub_read_batch(
                [(h, oid, s0 * sub_size, cnt * sub_size)
                 for h in helpers], make_cb())
        return state

    def _clay_repair_group(self, lost: int, helpers: tuple[int, ...],
                           sts: list[dict], push_for) -> None:
        """Rebuild one (lost, helpers) CLAY group: every object's
        stacked helper plane rows ride ONE batched GF matmul — the
        mesh collective when that plane is up, the per-host launch
        queue (co-batched with writes and other PGs' repairs)
        otherwise."""
        plan = self._clay_plan(lost, helpers)
        rows_list = [
            self.ec_impl.repair_rows(
                lost, {h: st["got"][h] for h in helpers}, helpers)
            for st in sts]
        rebuilt_list = None
        if self.mesh_codec is not None:
            try:
                rebuilt_list = self.mesh_codec.clay_repair_batch(
                    plan, rows_list)
                if self.perf:
                    self.perf.inc("ec_mesh_repair_launches")
            except Exception as e:  # noqa: BLE001 — mesh died mid-storm
                self._disable_mesh(e)
                rebuilt_list = None
        if rebuilt_list is None:
            from ..common.util import concat_columns, split_columns
            big, widths = concat_columns(rows_list)
            out = np.asarray(self._launch_queue.submit_clay_repair(
                plan, big, owner=id(self)).result())
            rebuilt_list = split_columns(out, widths)
        if self.perf:
            self.perf.inc("ec_clay_repair_launches")
            self.perf.inc("ec_clay_repairs", len(sts))
        for st, rebuilt in zip(sts, rebuilt_list):
            try:
                data = np.ascontiguousarray(
                    np.asarray(rebuilt), dtype=np.uint8).reshape(-1)
                self._verify_recovered(st, lost, data)
                push_for(st["oid"])(lost, data, st["hinfo"])
                if self.perf:
                    self.perf.inc("ec_repair_reconstructed_bytes",
                                  st["chunk_len"])
            except Exception as e:  # noqa: BLE001 — per-object verify
                st["error"] = e

    def _decode_recovery_group(self, survivors, targets,
                               sts: list[dict], push_for) -> None:
        """Rebuild one (survivors, targets) geometry group: a single
        mesh collective launch (byte axes of all objects concatenated,
        survivor rows sharded over 'shard') when the mesh plane is up,
        else the launch queue's device decode of the group's objects
        side by side, one submission per slice of at most
        recovery_decode_cap summed width, rebuilding only the target
        rows from exactly the k survivors; sub-chunked codes (CLAY)
        decode per object — their plane layout does not concatenate
        along the byte axis — and so does a code that need not decode
        from any k chunks (ALLOW_PARTIAL_DECODE: LRC, SHEC), by its own
        plan from every shard at hand.  The geometry is in shards; the
        decodes work in chunk order (shard_chunk)."""
        if self.ec_impl.ALLOW_PARTIAL_DECODE:
            self._push_recovered(sts, [self._decode_own_plan(st, targets)
                                       for st in sts], push_for)
            return
        rebuilt_per_st: list[dict[int, np.ndarray]] = []
        meshed = False
        chunk = self.shard_chunk
        # sub-chunked codes (CLAY) are not an RS matrix apply AND do
        # not concatenate along the byte axis — never mesh them (the
        # service path refuses matrix-less plugins, but an injected
        # codec must hit the same guard)
        if self.mesh_codec is not None and \
                self.ec_impl.get_sub_chunk_count() == 1:
            try:
                avail_list = [
                    np.stack([st["have"][s] for s in survivors])
                    for st in sts]
                rows_list = self.mesh_codec.decode_flat_batch(
                    avail_list, [chunk[s] for s in survivors],
                    [chunk[s] for s in targets])
                meshed = True
                if self.perf:
                    self.perf.inc("ec_mesh_repair_launches")
                for rows in rows_list:
                    rebuilt_per_st.append(
                        {s: rows[i] for i, s in enumerate(targets)})
            except Exception as e:  # noqa: BLE001 — mesh died mid-storm
                # containment: fall back to the launch queue's decode
                # for this (and every later) group; recovery proceeds
                self._disable_mesh(e)
                meshed = False
        if not meshed and self.ec_impl.get_sub_chunk_count() == 1:
            # slices of at most recovery_decode_cap summed width: the
            # pow2-padded launch widths stay the few prewarm plans
            # (launch_queue.recovery_decode_widths) instead of growing
            # with the storm's queue depth; each slice is one
            # submission, which the launch queue may coalesce with
            # OTHER PGs' decodes of the same geometry
            cap = recovery_decode_cap(max(st["chunk_len"] for st in sts))
            slices: list[list[dict]] = []
            cur: list[dict] = []
            cur_w = 0
            for st in sts:
                w = st["chunk_len"]
                if cur and cur_w + w > cap:
                    slices.append(cur)
                    cur, cur_w = [], 0
                cur.append(st)
                cur_w += w
            if cur:
                slices.append(cur)
            rows = [chunk[s] for s in survivors]
            erased = [chunk[s] for s in targets]
            for chunk_sts in slices:
                widths = [st["chunk_len"] for st in chunk_sts]
                big = np.zeros((self.n, sum(widths)), dtype=np.uint8)
                col = 0
                for st, w in zip(chunk_sts, widths):
                    for s in survivors:
                        big[chunk[s], col:col + w] = st["have"][s]
                    col += w
                ticket = self._launch_queue.submit_decode(
                    self.ec_impl, big, erased, owner=id(self),
                    survivors=rows)
                if self.perf:
                    self.perf.inc("ec_recovery_decode_launches")
                    self.perf.inc("ec_recovery_decode_bytes",
                                  self.k * big.shape[1])
                with span("ec.recover_decode_wait",
                          device_profiler().enabled,
                          pgid=self.perf.name):
                    dec = np.asarray(ticket.result())
                col = 0
                for st, w in zip(chunk_sts, widths):
                    rebuilt_per_st.append(
                        {s: dec[chunk[s], col:col + w]
                         for s in targets})
                    col += w
        elif not meshed:
            for st in sts:
                dense = np.zeros((self.n, st["chunk_len"]),
                                 dtype=np.uint8)
                for s, d in st["have"].items():
                    dense[chunk[s]] = d
                erased = [chunk[s] for s in range(self.n)
                          if s not in st["have"]]
                dec = self.ec_impl.decode_chunks(dense, erased)
                rebuilt_per_st.append({s: dec[chunk[s]]
                                       for s in targets})
        self._push_recovered(sts, rebuilt_per_st, push_for)

    def _decode_own_plan(self, st: dict, targets) -> dict:
        """One object's lost shards, decoded by the codec's own plan
        from every shard at hand ({} where it cannot: st["error"])."""
        try:
            return self.ec_impl.decode(set(targets), st["have"],
                                       st["chunk_len"])
        except ErasureCodeError as e:
            st["error"] = e
            return {}

    def _push_recovered(self, sts: list[dict], rebuilt_per_st: list,
                        push_for) -> None:
        """Verify each rebuilt shard against its hinfo and push it
        (span ec.recover_push: the group's verifies and pushes)."""
        with span("ec.recover_push", device_profiler().enabled,
                  pgid=self.perf.name):
            for st, rebuilt in zip(sts, rebuilt_per_st):
                if "error" in st:
                    continue
                try:
                    push = push_for(st["oid"])
                    for s in st["missing"]:
                        data = rebuilt[s]
                        self._verify_recovered(st, s, data)
                        push(s, data, st["hinfo"])
                        if self.perf:
                            self.perf.inc(
                                "ec_repair_reconstructed_bytes",
                                int(np.asarray(data).size))
                except Exception as e:  # noqa: BLE001 — per-object
                    st["error"] = e      # verify
