"""Per-host EC launch queue: cross-PG continuous batching.

The single-PG kernel numbers (the last record before PR 1 read
~147 GB/s bare encode, device-resident) come from large,
full-occupancy device launches; a loaded OSD host with hundreds of
post-split PGs issues hundreds of partial-occupancy
launches instead, because every ECBackend drains per-PG.  This module
is the fix ROADMAP item 2 names: one per-device launch queue per host,
owned by the same `MeshService` seam that already owns the device
plane (parallel/service.py) — every ECBackend on the host submits its
assemble-complete extent runs here instead of launching its own
`encode_extents_with_crc_submit`, and the queue coalesces runs from
DIFFERENT PGs into autotuned super-batches: the continuous-batching
move inference servers use to keep an accelerator at full occupancy
under many small request streams.

Why cross-PG concatenation is safe: the fused extents contract (PR 9,
ops/bitsliced.gf_encode_extents_with_crc_submit) pads every run to a
tile multiple (front-padded on the accumulator path), emits ONE
per-run L per shard, and parity is a columnwise-linear GF map — so a
super-batch is just a longer list of independent runs, and the
per-run results demultiplex exactly.  The plain (no-crc) chunk path
concatenates along the byte axis and demuxes by column for the same
reason.

Contract with the owning backends (docs/PIPELINE.md "Host launch
queue"):

* `submit_*` returns a `LaunchTicket` immediately — the submitting
  drain never blocks.  The queue launches a super-batch when the
  batching window (`osd_ec_host_batch_window_us`) expires, when the
  pending input bytes reach the super-batch cap
  (`osd_ec_host_batch_max_bytes`), or when any ticket's `result()` is
  called first (flush-on-demand: a lone PG with nothing behind it
  keeps the synchronous flush-on-idle semantics of the per-PG
  pipeline).
* Per-PG in-order completion is untouched: the queue only owns the
  LAUNCH; each backend still materializes its drains in submit order
  through its own `_complete_drain` / `_try_finish_rmw` path.
* Repair rides the same machinery (docs/REPAIR.md): `submit_decode`
  coalesces recovery / reconstruct-on-read `decode_chunks` runs across
  PGs per (codec, erasure pattern), and `submit_clay_repair` coalesces
  CLAY repair-plan applies per plan signature — an OSD-loss storm's
  decode launches share window/byte-cap/flush-on-demand semantics and
  occupancy accounting with the write path instead of issuing
  per-object launches beside it.
* Failure containment: submissions only coalesce when their codecs
  are provably identical (generator-matrix signature).  If a combined
  launch still fails, the queue retries each submission on its OWN
  plugin, so a poison run aborts only the owning PG's ops while
  co-batched PGs' runs launch and commit.  A finalize (device)
  failure fails every ticket of that batch — each backend aborts its
  own ops and the queue keeps serving (the mesh-failure analog).
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..common import spans
from ..common.util import next_pow2
from ..ops.profiler import device_profiler

# max summed input width of one coalesced decode launch.  Decode
# launches are pow2-padded (see _do_launch), so together with this cap
# the decode jit-bucket universe is {pow2 width <= cap} x {erasure
# cardinality <= m} — finite, and exactly enumerable by the boot
# prewarm (ops/prewarm.py).  A single submission wider than the cap
# still launches alone (a recovery group's chunk is atomic); its width
# follows the object geometry, which prewarm covers separately.
DECODE_MAX_LAUNCH_W = 65536


def _codec_label(plugin) -> str:
    """Short human codec tag for the flight recorder (the full
    codec_signature carries raw matrix bytes — ledger rows want
    'JaxCodec:k8m3', not a kilobyte of generator matrix)."""
    try:
        return (f"{type(plugin).__name__}:"
                f"k{plugin.get_data_chunk_count()}"
                f"m{plugin.get_coding_chunk_count()}")
    except Exception:  # noqa: BLE001 — plans/odd plugins
        return type(plugin).__name__


# batch kind -> its name in the flight recorder and in the per-kind
# counters of launched rows, `ec_host_launch_{in,out}_bytes.<name>`
_KIND_NAMES = {"x": "fused_encode", "c": "plain_encode", "d": "decode",
               "r": "clay_repair"}
_KIND_BYTE_KEYS = {kind: (f"ec_host_launch_in_bytes.{name}",
                          f"ec_host_launch_out_bytes.{name}")
                   for kind, name in _KIND_NAMES.items()}

# launch-queue counter <- the exact count a fused submit handle carries
_HANDLE_COUNTERS = (("ec_h2d_bytes", "h2d_bytes"),
                    ("ec_h2d_const_bytes", "h2d_const_bytes"),
                    ("ec_const_cache_hits", "const_hits"),
                    ("ec_const_cache_misses", "const_misses"),
                    ("ec_d2h_bytes", "d2h_bytes"))


def _extents_bucket(handle) -> str:
    """Jit-bucket key of a fused-extents submit handle: the (path,
    padded width, bucketed run count) triple the pow2 launch-shape
    bucketing (ops/bitsliced.py) collapses XLA's cache key to.  Best
    effort — an opaque plugin handle degrades to its path alone."""
    if isinstance(handle, dict):
        if "split" in handle:
            return "+".join(_extents_bucket(h)
                            for _idx, h in handle["split"])
        w = handle.get("big_width")
        nr = next_pow2(max(1, len(handle.get("meta", ()))))
        return f"x:{handle.get('path')}:w{w}:r{nr}"
    return "x:opaque"


def codec_signature(plugin) -> tuple:
    """Coalescing key for a plugin instance: two submissions may share
    one launch only when this is equal — same geometry AND bit-equal
    generator matrix (cauchy parity is garbage to a reed_sol_van
    decode; an unproven match must never batch).  Plugins may provide
    their own `codec_signature()`; without a generator matrix the
    signature degrades to instance identity, so such plugins still
    batch with themselves but never across instances."""
    own = getattr(plugin, "codec_signature", None)
    if callable(own):
        return own()
    mat = getattr(plugin, "matrix", None)
    if mat is None or \
            not getattr(plugin, "matrix_determines_encode", False):
        # exposing a matrix is NOT proof the encode uses it (jerasure's
        # minimal-density techniques encode via bitmatrix packets) —
        # only plugins that explicitly declare matrix-determined
        # encode semantics may batch across instances on the matrix
        return ("instance", id(plugin))
    # plugin-typed: the super-batch launches and finalizes through the
    # FIRST submitter's plugin, so the capability set must be uniform
    # within a launch — two plugin classes with bit-equal matrices
    # must never co-batch on the matrix alone
    return (type(plugin).__name__,) + matrix_signature(
        mat, plugin.get_data_chunk_count(),
        plugin.get_coding_chunk_count())


def matrix_signature(matrix, k, m) -> tuple:
    """The geometry + bit-equal-generator-matrix fields every
    coalescing key shares (the fallback above and plugin
    `codec_signature()` implementations prepend their type tag).
    The RAW matrix bytes ride the key — a hash would make "provably
    identical" probabilistic, and a collision would silently encode
    one pool's runs with another pool's matrix; generator matrices
    are tiny and plugins cache the signature, so exact bytes cost
    nothing."""
    a = np.ascontiguousarray(np.asarray(matrix))
    return (int(k), int(m), a.shape, a.tobytes())


class LaunchQueueError(RuntimeError):
    """A ticket whose launch/finalize died; the owning backend aborts
    its drain's ops (never other PGs')."""


class _Sub:
    """One backend drain's submission (all its fused runs, its one
    concatenated plain chunk run, or one recovery decode / CLAY repair
    run).  `extra` carries kind-specific launch arguments (the decode
    erasure list)."""
    __slots__ = ("ticket", "plugin", "runs", "n_runs", "width",
                 "nbytes", "t_submit", "owner", "extra", "traces")

    def __init__(self, ticket, plugin, runs, owner, extra=None,
                 traces=()):
        self.ticket = ticket
        self.plugin = plugin
        self.runs = runs
        self.n_runs = len(runs)
        self.width = runs[0].shape[1]
        self.nbytes = sum(r.shape[0] * r.shape[1] for r in runs)
        self.t_submit = time.perf_counter()
        self.owner = owner
        self.extra = extra
        # trace ids of the ops whose bytes ride this submission
        # (PR 4 stitching: the flight recorder's LaunchRecord carries
        # them so a slow-op's blame can name its launch and vice versa)
        self.traces = traces


class _Batch:
    """One launched super-batch.  `combined` holds the shared handle
    (launched through the first submission's plugin) plus the demux
    order; `per_sub` is the containment fallback — each submission
    launched on its own plugin after a combined-launch failure."""

    def __init__(self, kind: str, subs: list[_Sub]):
        self.kind = kind
        self.subs = subs
        self.lock = threading.Lock()
        # set once _do_launch has issued (or containment-retried) the
        # device submit; finalizers wait on it, so a result() racing
        # the launching thread never sees a half-built batch
        self.launch_done = threading.Event()
        # one-shot claim on the device submit: the window worker
        # launches popped batches sequentially, so a finalizer whose
        # batch is still unclaimed steals the launch instead of
        # head-of-line-blocking behind another key's multi-second
        # compile (or a CPU plugin's synchronous encode)
        self._launch_claim = threading.Lock()
        self.finalized = False
        self.combined = None        # (plugin, handle)
        self.per_sub = None         # [(sub, handle | None)]
        self.path = None
        # flight-recorder state (ops/profiler.py): queue wait of the
        # oldest submission (set at pop) and the in-flight record the
        # finalizer closes with the device time
        self.queue_wait = 0.0
        self.prof_rec = None


class LaunchTicket:
    """What a backend drain holds instead of a plugin submit handle.
    `result()` blocks until the super-batch containing this
    submission has launched (forcing the launch if the window hasn't
    fired — flush-on-demand) and finalized, then returns this
    submission's demultiplexed share of the results."""

    def __init__(self, queue: "ECLaunchQueue", kind: str, key: tuple):
        self._queue = queue
        self.kind = kind
        self._key = key
        self._batch: _Batch | None = None
        self._result = None
        self._error: Exception | None = None
        self._done = False
        self.path: str | None = None
        self.cancelled = False
        # flight-recorder stitching (ops/profiler.py): filled at
        # launch so the owning backend can put the launch id (and a
        # first-compile blame event) on its ops' timelines
        self.launch_id: int | None = None
        self.bucket: str | None = None
        self.compiled = False
        self.compile_s = 0.0
        self.cache_hit = False

    @property
    def launched(self) -> bool:
        return self._batch is not None

    def cancel(self) -> None:
        """Withdraw a not-yet-launched submission (the owning drain
        died during its own submit half); post-launch this is a no-op
        and the results are simply never read."""
        self._queue._cancel(self)

    def result(self):
        if not self._done:
            if self._batch is None:
                self._queue.flush(self._key)
            batch = self._batch
            if batch is None:
                if self._error is None:
                    self._error = LaunchQueueError(
                        "launch ticket cancelled before launch")
            else:
                self._queue._finalize_batch(batch)
        if self._error is not None:
            raise self._error
        return self._result


def _build_queue_perf(name: str):
    from ..common.perf_counters import PerfCountersBuilder
    return (PerfCountersBuilder(name)
            .add_u64_counter("ec_host_launches",
                             "super-batch device launches issued")
            .add_u64_counter("ec_host_launch_runs",
                             "extent runs coalesced into launches")
            .add_u64_counter("ec_host_launch_bytes",
                             "input bytes coalesced into launches")
            .add_u64_counter("ec_host_launch_padded_bytes",
                             "bytes of the shapes actually handed to "
                             "the fused jit: after per-run tile padding "
                             "and the pow2 tile-count bucket")
            .add_u64_counter("ec_h2d_bytes",
                             "host bytes launches uploaded to the "
                             "device: the staged data, and a constant "
                             "only when that launch uploaded it")
            .add_u64_counter("ec_h2d_const_bytes",
                             "the part of ec_h2d_bytes that was "
                             "constants (crc matrices, run-layout "
                             "maps): uploaded on a cache miss only "
                             "(ops/const_cache.py)")
            .add_u64_counter("ec_const_cache_hits",
                             "constants a fused launch took "
                             "device-resident from the cache")
            .add_u64_counter("ec_const_cache_misses",
                             "constants a fused launch had to upload")
            .add_u64_counter("ec_d2h_bytes",
                             "bytes fused launches read back to the "
                             "host (parity + crc L-bits), counted at "
                             "submit from the result shapes")
            .add_u64_counter("ec_host_launch_pg_mix",
                             "sum of distinct submitters per launch")
            .add_u64_counter("ec_host_cross_pg_launches",
                             "launches coalescing >1 PG's runs")
            .add_u64_counter("ec_host_launch_retries",
                             "combined launches retried per-submission "
                             "(containment)")
            .add_u64_counter("ec_host_launch_errors",
                             "submissions whose launch failed")
            .add_u64_counter("ec_host_decode_launches",
                             "recovery/reconstruct decode super-batch "
                             "launches")
            .add_u64_counter("ec_host_decode_runs",
                             "decode submissions those launches "
                             "carried (runs per decode launch = "
                             "this / ec_host_decode_launches)")
            .add_u64_counter("ec_host_repair_launches",
                             "CLAY repair-plan super-batch launches")
            .add_gauge("ec_host_occupancy_pct",
                       "last launch bytes / max super-batch bytes")
            .add_histogram("lat_ec_batch_wait",
                           "submit -> launch batching wait")
            .create_perf_counters())


class ECLaunchQueue:
    """The per-host (per-process in the multi-process simulation,
    where each process stands in for a host — same topology rule as
    MeshService) EC launch queue."""

    # one queue per host: the MeshService seam hands this out
    _host: "ECLaunchQueue | None" = None
    _host_lock = threading.Lock()

    def __init__(self, window_us: float = 250.0,
                 max_bytes: int = 32 << 20, perf=None,
                 perf_name: str = "ec_host_queue"):
        self.window_us = float(window_us)
        self.max_bytes = max(1, int(max_bytes))
        self.perf = perf if perf is not None \
            else _build_queue_perf(perf_name)
        self._lock = threading.Lock()
        self._cv = threading.Condition(self._lock)
        # aggregates have their own leaf lock: launch/finalize threads
        # bump error counters while holding a batch lock, and must not
        # contend with (or deadlock against) the pending-queue lock
        self._stats_lock = threading.Lock()
        self._pending: dict[tuple, list[_Sub]] = {}
        self._pending_bytes: dict[tuple, int] = {}
        self._deadline: float | None = None
        self._worker: threading.Thread | None = None
        self._closed = False
        self.created_at = time.time()
        # aggregates for status()
        self.launches = 0
        self.launched_runs = 0
        self.launched_bytes = 0
        self.launched_subs = 0
        self.pg_mix_total = 0
        self.cross_pg_launches = 0
        self.launch_retries = 0
        self.launch_errors = 0
        self.decode_launches = 0
        self.repair_launches = 0
        self.last_launch: dict | None = None

    # -- host singleton (MeshService wiring rides this) ----------------------

    @classmethod
    def host_instance(cls, window_us: float | None = None,
                      max_bytes: int | None = None) -> "ECLaunchQueue":
        """The host's queue, built on first use (first caller's knobs
        win — one queue per host is the deployment contract, like the
        mesh shape)."""
        with cls._host_lock:
            if cls._host is None:
                kw = {}
                if window_us is not None:
                    kw["window_us"] = window_us
                if max_bytes is not None:
                    kw["max_bytes"] = max_bytes
                cls._host = cls(**kw)
            return cls._host

    @classmethod
    def host_get(cls) -> "ECLaunchQueue | None":
        return cls._host

    @classmethod
    def reset_host(cls) -> None:
        """Tests only (in-flight tickets of the old queue still
        resolve through their own references)."""
        with cls._host_lock:
            if cls._host is not None:
                cls._host.close()
            cls._host = None

    # -- submission ----------------------------------------------------------

    def submit_extents(self, plugin, runs: list[np.ndarray],
                       owner=None, traces=()) -> LaunchTicket:
        """Queue a drain's fused append runs (each (k, Wi) uint8) for
        a coalesced `encode_extents_with_crc_submit` launch;
        `result()` yields the per-run (parity, l, tail, body) tuples
        in this submission's run order.  traces: the contributing
        ops' trace ids (flight-recorder stitching)."""
        return self._submit("x", plugin, [
            np.ascontiguousarray(r, dtype=np.uint8) for r in runs],
            owner, traces=traces)

    def submit_chunks(self, plugin, chunks: np.ndarray,
                      owner=None, traces=()) -> LaunchTicket:
        """Queue a drain's concatenated plain (k, W) run for a
        coalesced parity-only launch; `result()` yields this
        submission's (m, W) parity columns."""
        return self._submit("c", plugin, [
            np.ascontiguousarray(chunks, dtype=np.uint8)], owner,
            traces=traces)

    def submit_decode(self, plugin, dense: np.ndarray, erasures,
                      owner=None, traces=()) -> LaunchTicket:
        """Queue one recovery/reconstruct decode: `dense` is the
        (k+m, W) array with zeros in the erased rows.  Submissions
        sharing (codec, erasure pattern) coalesce into one
        `decode_chunks` launch across PGs — repair rides the same
        launch-occupancy machinery as writes (ROADMAP item 2's named
        remainder); `result()` yields this submission's decoded
        (k+m, W) columns."""
        erasures = tuple(sorted(int(e) for e in erasures))
        return self._submit(
            "d", plugin,
            [np.ascontiguousarray(dense, dtype=np.uint8)], owner,
            key_suffix=(erasures,), extra=erasures, traces=traces)

    def submit_clay_repair(self, plan, rows: np.ndarray,
                           owner=None, traces=()) -> LaunchTicket:
        """Queue one CLAY repair-plan apply: `rows` are the stacked
        helper repair-plane symbols (d*P, W) of ONE object (or a
        backend's own concatenation of several).  Submissions sharing
        a plan signature — same (geometry, lost chunk, helper set) —
        coalesce into one batched GF matmul launch
        (parallel/mesh.ClayRepairPlan); `result()` yields this
        submission's (sub_chunks, W) rebuilt columns."""
        return self._submit(
            "r", plan, [np.ascontiguousarray(rows, dtype=np.uint8)],
            owner, key_suffix=(), traces=traces)

    def _submit(self, kind: str, plugin, runs, owner,
                key_suffix: tuple = (), extra=None,
                traces=()) -> LaunchTicket:
        if kind == "r":
            key = (kind,) + tuple(plugin.signature)
        else:
            key = (kind,) + codec_signature(plugin) + key_suffix
        ticket = LaunchTicket(self, kind, key)
        sub = _Sub(ticket, plugin, runs, owner, extra=extra,
                   traces=traces)
        batches: list[_Batch] = []
        with self._lock:
            self._pending.setdefault(key, []).append(sub)
            nb = self._pending_bytes.get(key, 0) + sub.nbytes
            self._pending_bytes[key] = nb
            if nb >= self.max_bytes or self.window_us <= 0:
                # occupancy cap reached (or batching disabled): launch
                # this key's super-batch immediately
                batches = self._pop_batches_locked(key)
            else:
                self._arm_window_locked()
        for batch in batches:
            self._do_launch(batch)
        return ticket

    def _cancel(self, ticket: LaunchTicket) -> None:
        with self._lock:
            subs = self._pending.get(ticket._key)
            if subs:
                for sub in subs:
                    if sub.ticket is ticket:
                        subs.remove(sub)
                        self._pending_bytes[ticket._key] -= sub.nbytes
                        if not subs:
                            del self._pending[ticket._key]
                            del self._pending_bytes[ticket._key]
                        if not self._pending:
                            self._deadline = None
                        break
        ticket.cancelled = True

    # -- window --------------------------------------------------------------

    def _arm_window_locked(self) -> None:
        """First pending submission of a window sets the deadline (a
        later submit never extends it) and wakes the single persistent
        window worker — NOT a fresh Timer thread per window, which at
        a 250 us default would be thousands of thread spawns per
        second on the write hot path."""
        if self._deadline is None:
            self._deadline = time.perf_counter() + self.window_us / 1e6
            self._cv.notify()
        if self._worker is None:
            self._worker = threading.Thread(
                target=self._window_loop, daemon=True,
                name="ec-launch-window")
            self._worker.start()

    def close(self) -> None:
        """Flush pending batches and retire the window worker.  For
        throwaway queues (benches, tests) — a host queue lives for
        the process.  Tickets submitted after close still launch via
        byte cap or flush-on-demand; only the window stops firing."""
        self.flush()
        with self._lock:
            self._closed = True
            self._cv.notify()

    def _window_loop(self) -> None:
        while True:
            with self._cv:
                if self._closed:
                    return
                if self._deadline is None:
                    self._cv.wait()
                    continue
                delay = self._deadline - time.perf_counter()
                if delay > 0:
                    self._cv.wait(delay)
                    continue
                batches = [b for k in list(self._pending)
                           if self._pending.get(k)
                           for b in self._pop_batches_locked(k)]
                self._deadline = None
            for batch in batches:
                self._do_launch(batch)

    def flush(self, key: tuple | None = None) -> None:
        """Launch pending super-batches now (all keys, or one):
        flush-on-demand for tickets finalized before the window
        fires, and the idle-flush hook."""
        with self._lock:
            keys = [key] if key is not None else list(self._pending)
            batches = [b for k in keys if self._pending.get(k)
                       for b in self._pop_batches_locked(k)]
        for batch in batches:
            self._do_launch(batch)

    # -- launch --------------------------------------------------------------

    def _pop_batches_locked(self, key: tuple) -> "list[_Batch]":
        """Under self._lock: claim a key's pending submissions as one
        or more batches, binding every ticket to one (so a racing
        result() waits on its batch instead of re-flushing an empty
        key).  Decode keys split at DECODE_MAX_LAUNCH_W of summed
        input width: with the pow2 padding in _do_launch this keeps
        every decode launch inside the prewarm-enumerable bucket set
        ({pow2 <= cap} x cardinality) no matter how many PGs' repair
        slices coalesce in one window.  The device submit itself
        happens OUTSIDE the queue lock in _do_launch — a multi-second
        first-bucket compile (or a CPU plugin's synchronous encode)
        must stall only its batch, not every PG's submit path."""
        subs = self._pending.pop(key)
        self._pending_bytes.pop(key, None)
        if not self._pending:
            self._deadline = None
        if key[0] != "d":
            groups = [subs]
        else:
            groups, cur, cur_w = [], [], 0
            for s in subs:
                w = int(s.runs[0].shape[1])
                if cur and cur_w + w > DECODE_MAX_LAUNCH_W:
                    groups.append(cur)
                    cur, cur_w = [], 0
                cur.append(s)
                cur_w += w
            if cur:
                groups.append(cur)
        return [self._make_batch_locked(key, g) for g in groups]

    def _make_batch_locked(self, key: tuple,
                           subs: "list[_Sub]") -> _Batch:
        batch = _Batch(key[0], subs)
        now = time.perf_counter()
        for s in subs:
            s.ticket._batch = batch
            if self.perf:
                self.perf.hinc("lat_ec_batch_wait", now - s.t_submit)
        # the launch ledger records the OLDEST submission's wait (the
        # batching cost an op actually paid, not the average)
        batch.queue_wait = now - min(s.t_submit for s in subs)
        nbytes = sum(s.nbytes for s in subs)
        nruns = sum(s.n_runs for s in subs)
        owners = {s.owner for s in subs}
        # a single submission larger than max_bytes launches alone and
        # oversizes the batch (the cap is checked after append); clamp
        # so the gauge stays a percentage
        occupancy = min(100.0, 100.0 * nbytes / self.max_bytes)
        with self._stats_lock:
            self.launches += 1
            self.launched_runs += nruns
            self.launched_bytes += nbytes
            self.launched_subs += len(subs)
            self.pg_mix_total += len(owners)
            if len(owners) > 1:
                self.cross_pg_launches += 1
            if batch.kind == "d":
                self.decode_launches += 1
            elif batch.kind == "r":
                self.repair_launches += 1
            self.last_launch = {"runs": nruns, "bytes": nbytes,
                                "submissions": len(subs),
                                "pg_mix": len(owners),
                                "occupancy_pct": round(occupancy, 2)}
        if self.perf:
            self.perf.inc("ec_host_launches")
            self.perf.inc("ec_host_launch_runs", nruns)
            self.perf.inc("ec_host_launch_bytes", nbytes)
            self.perf.inc("ec_host_launch_pg_mix", len(owners))
            if len(owners) > 1:
                self.perf.inc("ec_host_cross_pg_launches")
            if batch.kind == "d":
                self.perf.inc("ec_host_decode_launches")
                self.perf.inc("ec_host_decode_runs", nruns)
            elif batch.kind == "r":
                self.perf.inc("ec_host_repair_launches")
            self.perf.set("ec_host_occupancy_pct", round(occupancy, 2))
            self._count_kind_bytes(batch.kind, subs)
        return batch

    def _count_kind_bytes(self, kind: str, subs: "list[_Sub]") -> None:
        """The rows this launch handed to its kernel and the rows it
        took back, in bytes, unpadded, under its kind's name — what
        was launched, no more: which of it was needed is for whoever
        reads the counters to say.  fused / plain: the k data rows in,
        the m parity rows out (a fused launch's crcs are not rows);
        decode: the k survivor rows in (the submission carries all
        k+m, erased ones zero), one row out per ERASED shard, the
        parity shard a pre-read never asked for included; clay_repair:
        the helper rows in."""
        key_in, key_out = _KIND_BYTE_KEYS[kind]
        if kind == "r":
            self.perf.dinc(key_in, sum(s.nbytes for s in subs))
            return
        width = sum(r.shape[1] for s in subs for r in s.runs)
        plugin = subs[0].plugin
        rows_out = len(subs[0].extra) if kind == "d" \
            else plugin.get_coding_chunk_count()
        self.perf.dinc(key_in, plugin.get_data_chunk_count() * width)
        self.perf.dinc(key_out, rows_out * width)

    def _note_launch_error(self) -> None:
        with self._stats_lock:
            self.launch_errors += 1
        if self.perf:
            self.perf.inc("ec_host_launch_errors")

    def _do_launch(self, batch: _Batch) -> None:
        if not batch._launch_claim.acquire(blocking=False):
            # another thread owns the submit (a finalizer stole its
            # batch's launch, or vice versa); it sets launch_done
            return
        subs = batch.subs
        kind = batch.kind
        # flight recorder (ops/profiler.py): one LaunchRecord per
        # super-batch, begun before the device submit so its clock
        # covers the dispatch (and a first-bucket compile)
        prof = device_profiler()
        rec = prof.begin(
            _KIND_NAMES.get(kind, kind),
            codec=_codec_label(subs[0].plugin),
            runs=sum(s.n_runs for s in subs),
            nbytes=sum(s.nbytes for s in subs),
            pg_mix=len({s.owner for s in subs}),
            traces=[t for s in subs for t in s.traces],
            queue_wait_s=batch.queue_wait)
        # the launch's span rides the recorder's begin (on when it is);
        # the retries of the containment path stay inside it
        sp = None if rec is None else spans.begin(
            "lq.launch", launch=rec.launch_id, runs=rec.runs,
            bytes=rec.nbytes)
        bucket = None
        try:
            plugin = subs[0].plugin
            if kind == "x":
                all_runs = [r for s in subs for r in s.runs]
                handle = plugin.encode_extents_with_crc_submit(all_runs)
                batch.path = handle.get("path") \
                    if isinstance(handle, dict) else None
                if isinstance(handle, dict) and "padded_bytes" in handle:
                    padded = handle["padded_bytes"]
                    if self.perf:
                        # what the launch hands over and will read
                        # back, known from the shapes at submit
                        for counter, key in _HANDLE_COUNTERS:
                            self.perf.inc(counter, handle[key])
                else:
                    padded = sum(s.nbytes for s in subs)
                # plugins that know their real jit-key axes (the jax
                # plugin's autotuned operating point) refine the bucket
                bucket = plugin.launch_bucket(handle) \
                    if hasattr(plugin, "launch_bucket") \
                    else _extents_bucket(handle)
            elif kind == "r":
                # CLAY repair plan: one batched GF matmul for every
                # co-submitted object (plugin slot holds the shared
                # ClayRepairPlan — signatures matched, so it IS shared)
                bigs = [s.runs[0] for s in subs]
                big = np.concatenate(bigs, axis=1) if len(bigs) > 1 \
                    else bigs[0]
                sig = abs(hash(tuple(plugin.signature))) & 0xFFFFFF
                bucket = f"r:{sig:x}:w{big.shape[1]}"
                handle = ("np", np.asarray(plugin.apply_device(big)))
                padded = int(big.size)
            elif kind == "d":
                # recovery/reconstruct decode: erasure patterns match
                # within a key, so the concatenated dense array decodes
                # in one launch; zero pad columns (launch-shape
                # bucketing, like the plain path) decode to zeros the
                # demux never reads
                bigs = [s.runs[0] for s in subs]
                big = np.concatenate(bigs, axis=1) if len(bigs) > 1 \
                    else bigs[0]
                # launch-shape bucketing, UNCONDITIONAL: a solo sub
                # can carry an arbitrary width (a recovery group's
                # concatenated chunks, a non-pow2 chunk_len), and an
                # unpadded width mints a fresh jit bucket no boot
                # prewarm can enumerate.  Pow2 padding bounds the
                # decode bucket universe; the finalize demux slices
                # each sub's real width, so pad columns are never read.
                w = big.shape[1]
                w2 = next_pow2(w)
                if w2 != w:
                    big = np.concatenate(
                        [big, np.zeros((big.shape[0], w2 - w),
                                       dtype=np.uint8)], axis=1)
                era = "".join(str(e) for e in subs[0].extra)
                bucket = f"d:e{era}:w{big.shape[1]}"
                handle = ("np", np.asarray(plugin.decode_chunks(
                    big, list(subs[0].extra))))
                padded = int(big.size)
                if self.perf and getattr(plugin, "jit_backed", False):
                    # the k survivor rows in, the erased rows out (the
                    # decode matrix is device-resident)
                    self.perf.inc(
                        "ec_h2d_bytes",
                        plugin.get_data_chunk_count() * big.shape[1])
                    self.perf.inc("ec_d2h_bytes",
                                  len(subs[0].extra) * big.shape[1])
            else:
                bigs = [s.runs[0] for s in subs]
                big = np.concatenate(bigs, axis=1) if len(bigs) > 1 \
                    else bigs[0]
                if hasattr(plugin, "encode_chunks_submit"):
                    # launch-shape bucketing (see bitsliced.py), as
                    # for decodes UNCONDITIONAL: a jit'd plugin would
                    # recompile per distinct width, and one drain of
                    # three one-stripe overwrites is as odd a width
                    # as three coalesced drains — pad to the next
                    # power of two (zero columns encode to zero
                    # parity; the column demux never reads them), so
                    # {pow2 widths} is the whole plain bucket set a
                    # prewarm can enumerate
                    w = big.shape[1]
                    w2 = next_pow2(w)
                    if w2 != w:
                        big = np.concatenate(
                            [big, np.zeros((big.shape[0], w2 - w),
                                           dtype=np.uint8)],
                            axis=1)
                    handle = ("h", plugin.encode_chunks_submit(big))
                    if self.perf:
                        # the staged data in, the parity out (the
                        # encode matrix is device-resident)
                        self.perf.inc("ec_h2d_bytes", int(big.size))
                        self.perf.inc(
                            "ec_d2h_bytes",
                            plugin.get_coding_chunk_count()
                            * big.shape[1])
                else:
                    # host-synchronous CPU plugins: ONE concatenated
                    # encode for the whole super-batch (fewer, larger
                    # host matmuls — the CPU analog of occupancy)
                    handle = ("np", np.asarray(plugin.encode_chunks(big)))
                bucket = f"c:{handle[0]}:w{big.shape[1]}"
                padded = int(big.size)
            if self.perf:
                # beside ec_host_launch_bytes, for every kind: what
                # the launch was handed after padding and bucketing
                self.perf.inc("ec_host_launch_padded_bytes", padded)
            batch.combined = (plugin, handle)
            # host-synchronous launches (pure-CPU plugin encode/
            # decode: handle kind "np" on a plugin without a jitted
            # backend) carry no compiled program — their submit wall
            # must not enter the compile ledger (jit=False); the jax
            # plugin and ClayRepairPlan declare jit_backed, and a
            # device submit handle ("h") is jitted by construction
            jit = (kind == "x"
                   or (isinstance(handle, tuple) and handle[0] == "h")
                   or getattr(plugin, "jit_backed", False))
            prof.submitted(rec, bucket, path=batch.path or
                           (handle[0] if isinstance(handle, tuple)
                            else None), jit=jit)
            batch.prof_rec = rec
            if rec is not None:
                # stitching: the owning backends put these on their
                # ops' timelines (launch id event + first-compile
                # blame) at completion
                for s in subs:
                    t = s.ticket
                    t.launch_id = rec.launch_id
                    t.bucket = rec.bucket
                    t.compiled = rec.compiled
                    t.compile_s = rec.compile_s
                    t.cache_hit = rec.cache_hit
        except Exception:  # noqa: BLE001 — containment retry
            # a poison submission must fail only its owner: launch
            # each submission on its OWN plugin, recording per-ticket
            # errors instead of failing the super-batch wholesale
            with self._stats_lock:
                self.launch_retries += 1
            if self.perf:
                self.perf.inc("ec_host_launch_retries")
            batch.per_sub = []
            for s in subs:
                try:
                    if kind == "x":
                        h = s.plugin.encode_extents_with_crc_submit(
                            s.runs)
                    elif kind == "r":
                        h = ("np", np.asarray(
                            s.plugin.apply_device(s.runs[0])))
                    elif kind == "d":
                        h = ("np", np.asarray(s.plugin.decode_chunks(
                            s.runs[0], list(s.extra))))
                    elif hasattr(s.plugin, "encode_chunks_submit"):
                        h = ("h", s.plugin.encode_chunks_submit(
                            s.runs[0]))
                    else:
                        h = ("np", np.asarray(
                            s.plugin.encode_chunks(s.runs[0])))
                    batch.per_sub.append((s, h))
                except Exception as e:  # noqa: BLE001 — the poison sub
                    self._note_launch_error()
                    s.ticket._error = LaunchQueueError(
                        f"launch failed for this submission: {e!r}")
                    s.ticket._error.__cause__ = e
                    s.ticket._done = True
                    batch.per_sub.append((s, None))
        finally:
            spans.end(sp)
            for s in subs:
                s.runs = None   # the launch holds the staged arrays now
            batch.launch_done.set()

    # -- finalize ------------------------------------------------------------

    def _finalize_batch(self, batch: _Batch) -> None:
        """Materialize one super-batch ONCE and demultiplex each
        submission's share onto its ticket; errors are memoized so
        every co-batched ticket sees the same outcome.  Runs on the
        first finalizing backend's thread (completion stays in each
        PG's own submit order — the queue imposes no ordering across
        PGs)."""
        if not batch.launch_done.is_set():
            # steal the launch if the window worker hasn't started it
            # yet — a bound ticket must not wait behind other keys'
            # batches in the worker's sequential loop
            self._do_launch(batch)
        batch.launch_done.wait()
        with batch.lock:
            if batch.finalized:
                return
            rec = batch.prof_rec
            sp = None if rec is None else spans.begin(
                "lq.finalize", launch=rec.launch_id)
            try:
                if batch.per_sub is not None:
                    for sub, handle in batch.per_sub:
                        if handle is None:
                            continue        # launch already failed
                        try:
                            self._finalize_sub(batch.kind, sub, handle)
                        except Exception as e:  # noqa: BLE001
                            self._note_launch_error()
                            sub.ticket._error = e
                            sub.ticket._done = True
                else:
                    plugin, handle = batch.combined
                    if batch.kind == "x":
                        res = plugin.encode_extents_with_crc_finalize(
                            handle)
                        pos = 0
                        for sub in batch.subs:
                            sub.ticket._result = \
                                res[pos:pos + sub.n_runs]
                            sub.ticket.path = batch.path
                            sub.ticket._done = True
                            pos += sub.n_runs
                    else:
                        kind_h, h = handle
                        par = plugin.encode_chunks_finalize(h) \
                            if kind_h == "h" else h
                        col = 0
                        for sub in batch.subs:
                            sub.ticket._result = \
                                par[:, col:col + sub.width]
                            sub.ticket._done = True
                            col += sub.width
            except Exception as e:  # noqa: BLE001 — device finalize
                # died: every ticket of the batch carries the error;
                # each backend aborts ITS ops and the queue lives on
                for sub in batch.subs:
                    if not sub.ticket._done:
                        self._note_launch_error()
                        sub.ticket._error = e
                        sub.ticket._done = True
            finally:
                batch.finalized = True
                # ledger: the first finalizer blocks on the device
                # futures here, then copies to the host — a HOST-clock
                # wait (`lat_launch_device`); device time is in the
                # profiler trace, where this span is a row
                if sp is not None:
                    sp.end()
                    device_profiler().materialized(rec, sp.wall_s)

    def _finalize_sub(self, kind: str, sub: _Sub, handle) -> None:
        if kind == "x":
            sub.ticket._result = \
                sub.plugin.encode_extents_with_crc_finalize(handle)
            sub.ticket.path = handle.get("path") \
                if isinstance(handle, dict) else None
        else:
            kind_h, h = handle
            sub.ticket._result = sub.plugin.encode_chunks_finalize(h) \
                if kind_h == "h" else h
        sub.ticket._done = True

    # -- observability -------------------------------------------------------

    def status(self) -> dict:
        """The `launch queue status` asok payload: batching knobs,
        launch/coalescing/occupancy aggregates, pending backlog."""
        with self._lock:
            pending_subs = sum(len(v) for v in self._pending.values())
            pending_bytes = sum(self._pending_bytes.values())
        with self._stats_lock:
            launches = self.launches
            return {
                "window_us": self.window_us,
                "max_super_batch_bytes": self.max_bytes,
                "launches": launches,
                "coalesced_runs": self.launched_runs,
                "coalesced_bytes": self.launched_bytes,
                "submissions": self.launched_subs,
                "avg_runs_per_launch": round(
                    self.launched_runs / launches, 2)
                if launches else 0.0,
                "occupancy_pct_avg": round(min(
                    100.0, 100.0 * self.launched_bytes
                    / (launches * self.max_bytes)), 2)
                if launches else 0.0,
                "cross_pg_launches": self.cross_pg_launches,
                "pg_mix_avg": round(
                    self.pg_mix_total / launches, 2)
                if launches else 0.0,
                "launch_retries": self.launch_retries,
                "launch_errors": self.launch_errors,
                "decode_launches": self.decode_launches,
                "repair_launches": self.repair_launches,
                "last_launch": self.last_launch,
                "pending_submissions": pending_subs,
                "pending_bytes": pending_bytes,
                "uptime_s": round(time.time() - self.created_at, 1),
            }
