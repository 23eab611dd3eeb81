"""Device-plane flight recorder: launch ledger + compile attribution.

Every layer above the device has attribution — PR 4's OpTracker tells
you which op stalled at which stage, PR 7's percentile pipeline tells
you which stage's tail moved — but the device plane itself has only
counters: a launch happens, bytes move, and when a first-seen jit
bucket pays a multi-second XLA/Mosaic compile the only evidence is
folklore ("compile stalls flap OSDs", PR 8's heartbeat note; "the
64pg frac gate wanders", PR 12/14's retry notes).  This module is the
recorder that turns those into data, the same shape a training or
inference serving stack keeps for its accelerators:

* **Launch ledger** — every device launch (fused encode, plain
  encode, recovery decode, CLAY repair, mesh batch, deep-scrub CRC)
  gets a monotonic launch id and a `LaunchRecord`: kind, codec label,
  jit-bucket key, runs, input bytes, queue wait, submit wall time,
  the submit->materialize wait on the host clock (`device_ms`: it
  blocks on the device and copies back — device time proper is in a
  profiler trace, where `lq.finalize` is a row), PG mix, and the trace ids of the
  contributing ops (PR 4 stitching).  Completed records live in a
  bounded ring (`launch profile` asok); `lat_launch_submit` /
  `lat_launch_device` / `lat_launch_queue_wait` histograms share
  DEFAULT_LAT_BUCKETS with the tracing stages, so `dump_latencies`,
  the exporter's percentile gauges and the load harness's per-stage
  blame all pick them up unchanged.

* **Compile attribution** — the first submit of a jit bucket (a
  distinct (kind, path, padded-shape) key — exactly what XLA keys its
  cache on after PR 12's pow2 bucketing) carries the compile.  The
  recorder detects first-seen buckets and times them: submit-side
  wall clock on the first hit vs the bucket's steady-state minimum
  afterwards; the difference is the compile estimate.  The per-host
  compile ledger (`compile ledger` asok) lists every bucket with
  count / first_s / steady_s / compile_s; first hits over
  `stall_s` (conf osd_ec_compile_stall_s) count in the
  `ec_compile_stalls` counter and enter a bounded window of compile
  events that the OSD ships monward for the COMPILE_STORM health
  warning (mon/monitor.py) — the known "compile stall flaps OSDs"
  failure mode made visible instead of folklore.

* **Always on, null when off** — the profiler is enabled by default
  (conf osd_ec_profiler); disabled, `begin()` returns None after one
  attribute check and every other entry point no-ops on a None
  record, so the off path allocates nothing (the NULL_TRACKED rule).
  The on-path cost is one record per LAUNCH (not per op); what it
  costs is measured on the chip (PERF.md §6).

`inject_stall_s` (conf osd_ec_inject_compile_stall) is the fault
injection the gates use: a positive value sleeps that long inside the
submit of every FIRST-seen bucket — a real compile stall's exact
shape (it delays only that batch, blocks its finalizers, and trips
the slow-op / tick-lag / COMPILE_STORM detectors honestly).
"""

from __future__ import annotations

import collections
import threading
import time

from .compile_cache import hit_count as _cc_hits
from .compile_cache import status as _cc_status


def _build_prof_perf(name: str = "device_profiler"):
    from ..common.perf_counters import PerfCountersBuilder
    return (PerfCountersBuilder(name)
            .add_u64_counter("ec_launches",
                             "device launches recorded in the ledger")
            .add_u64_counter("ec_launch_runs",
                             "runs carried by recorded launches")
            .add_u64_counter("ec_launch_bytes",
                             "input bytes carried by recorded launches")
            .add_u64_counter("ec_compile_stalls",
                             "first-seen jit buckets whose submit "
                             "exceeded osd_ec_compile_stall_s "
                             "(persistent-cache hits excluded)")
            .add_u64_counter("ec_compile_cache_hits",
                             "first-seen jit buckets served from the "
                             "persistent compile cache at runtime")
            .add_u64_counter("ec_prewarm_compiles",
                             "jit buckets compiled by the boot-time "
                             "prewarm plan (cold cache)")
            .add_u64_counter("ec_prewarm_cache_hits",
                             "prewarm-plan buckets served from the "
                             "persistent compile cache")
            .add_histogram("lat_launch_submit",
                           "launch dispatch wall time (includes the "
                           "compile on a bucket's first hit)")
            .add_histogram("lat_launch_device",
                           "submit -> materialize wait on the HOST "
                           "clock (blocks on the device, then copies); "
                           "device time is in a profiler trace")
            .add_histogram("lat_launch_queue_wait",
                           "host-queue batching wait before launch")
            .add_histogram("lat_prewarm",
                           "per-bucket boot-time prewarm compile wall")
            .create_perf_counters())


class LaunchRecord:
    """One device launch's ledger entry (ring + stitching payload)."""

    __slots__ = ("launch_id", "kind", "codec", "bucket", "path",
                 "runs", "nbytes", "pg_mix", "traces", "queue_wait_s",
                 "submit_s", "device_s", "compiled", "compile_s",
                 "cache_hit", "ts", "_t0", "_cc0")

    def __init__(self, launch_id: int, kind: str, codec: str,
                 runs: int, nbytes: int, pg_mix: int, traces,
                 queue_wait_s: float):
        self.launch_id = launch_id
        self.kind = kind
        self.codec = codec
        self.bucket: str | None = None
        self.path: str | None = None
        self.runs = runs
        self.nbytes = nbytes
        self.pg_mix = pg_mix
        self.traces = tuple(traces)[:8]   # bounded: a 64-op super-
        #                                   batch must not drag 64 ids
        self.queue_wait_s = queue_wait_s
        self.submit_s = 0.0
        self.device_s = 0.0
        self.compiled = False
        self.compile_s = 0.0
        # a FIRST launch of this bucket whose compile was served by
        # the persistent compile cache (or whose bucket was prewarmed
        # at boot): fast by construction, excluded from stall counting
        self.cache_hit = False
        self.ts = time.time()
        self._t0 = time.perf_counter()
        # persistent-cache hit counter at record start: submitted()
        # deltas it to attribute a disk-served compile to THIS launch
        self._cc0 = _cc_hits()

    def to_dict(self) -> dict:
        return {
            "launch_id": self.launch_id,
            "kind": self.kind,
            "codec": self.codec,
            "bucket": self.bucket,
            "path": self.path,
            "runs": self.runs,
            "bytes": self.nbytes,
            "pg_mix": self.pg_mix,
            "traces": list(self.traces),
            "queue_wait_ms": round(self.queue_wait_s * 1e3, 3),
            "submit_ms": round(self.submit_s * 1e3, 3),
            "device_ms": round(self.device_s * 1e3, 3),
            "compiled": self.compiled,
            "compile_s": round(self.compile_s, 4),
            "cache_hit": self.cache_hit,
            "ts": self.ts,
        }


class DeviceProfiler:
    """Per-host (process-wide, like ECLaunchQueue/MeshService) launch
    ledger + compile ledger."""

    _host: "DeviceProfiler | None" = None
    _host_lock = threading.Lock()

    def __init__(self, ring_size: int = 256, stall_s: float = 0.25,
                 storm_window_s: float = 60.0, perf=None,
                 enabled: bool = True):
        self.enabled = enabled
        self.stall_s = float(stall_s)
        self.storm_window_s = float(storm_window_s)
        # fault injection (conf osd_ec_inject_compile_stall): sleep
        # inside the submit of every first-seen bucket — the shape of
        # a real compile stall, for the smoke/health gates
        self.inject_stall_s = 0.0
        self.perf = perf if perf is not None else _build_prof_perf()
        self._lock = threading.Lock()
        self._next_id = 1
        self._ring: collections.deque[LaunchRecord] = \
            collections.deque(maxlen=max(1, int(ring_size)))
        # bucket key -> {count, first_s, steady_min_s, first_ts}
        self._buckets: dict[str, dict] = {}
        # recent first-compile events (ts, bucket, seconds): the
        # COMPILE_STORM window source; bounded — steady state sees no
        # first-compiles, so this never grows past distinct buckets
        self._compile_events: collections.deque = \
            collections.deque(maxlen=512)
        self.launches = 0
        self.launched_runs = 0
        self.launched_bytes = 0
        self.compile_stalls = 0
        # first-seen buckets whose compile came off the persistent
        # compile cache at runtime (the revive-storm success metric)
        self.cache_hits = 0
        # boot-time prewarm tallies (ops/prewarm.py feeds these through
        # note_prewarm; the `prewarm status` asok reads them back)
        self.prewarm_compiles = 0
        self.prewarm_cache_hits = 0
        self.prewarm_s = 0.0
        self.created_at = time.time()

    # -- host singleton ------------------------------------------------------

    @classmethod
    def host_instance(cls) -> "DeviceProfiler":
        with cls._host_lock:
            if cls._host is None:
                cls._host = cls()
            return cls._host

    @classmethod
    def reset_host(cls) -> None:
        """Tests/benches only: drop the singleton (records of the old
        one stay readable through any direct references)."""
        with cls._host_lock:
            cls._host = None

    def set_ring_size(self, n: int) -> None:
        """Resize the completed-launch ring (startup conf
        osd_ec_profiler_ring; existing records carry over, oldest
        dropped)."""
        with self._lock:
            self._ring = collections.deque(self._ring,
                                           maxlen=max(1, int(n)))

    # -- recording -----------------------------------------------------------

    def begin(self, kind: str, codec: str = "", runs: int = 1,
              nbytes: int = 0, pg_mix: int = 1, traces=(),
              queue_wait_s: float = 0.0) -> LaunchRecord | None:
        """Start a launch record (call IMMEDIATELY before the device
        submit — the record's t0 anchors the submit wall clock).
        Returns None when profiling is off: the null fast path is one
        attribute check, no allocation."""
        if not self.enabled:
            return None
        with self._lock:
            lid = self._next_id
            self._next_id += 1
        return LaunchRecord(lid, kind, codec, runs, nbytes, pg_mix,
                            traces, queue_wait_s)

    def submitted(self, rec: LaunchRecord | None, bucket: str,
                  path: str | None = None, jit: bool = True) -> None:
        """The device submit returned: close the submit clock, detect
        a first-seen jit bucket, and feed the compile ledger.  No-op
        on a None record.

        jit=False marks a host-synchronous launch with NO compiled
        program behind it (pure-CPU plugin encode/decode, the np
        containment paths): its submit wall still lands in the
        histograms and the ring, but it must never enter the compile
        ledger — a 100 ms host matmul counted as a "compile" would
        raise false COMPILE_STORMs and grow the bucket table by one
        entry per distinct raw width."""
        if rec is None:
            return
        if not jit:
            rec.submit_s = time.perf_counter() - rec._t0
            rec.bucket = bucket
            rec.path = path
            if self.perf:
                self.perf.hinc("lat_launch_submit", rec.submit_s)
                self.perf.hinc("lat_launch_queue_wait",
                               rec.queue_wait_s)
            return
        with self._lock:
            first = bucket not in self._buckets
        injected = False
        if first and self.inject_stall_s > 0:
            time.sleep(self.inject_stall_s)
            injected = True
        now = time.perf_counter()
        rec.submit_s = now - rec._t0
        rec.bucket = bucket
        rec.path = path
        # persistent compile cache (ops/compile_cache.py): the hit
        # counter advancing during THIS submit means the first-seen
        # compile was served from disk — a fast first launch, never a
        # stall.  Best-effort under concurrency (a racing launch's hit
        # could land in this window), but misattribution only ever
        # downgrades a stall into a hit on a host where the cache IS
        # serving compiles — the semantics the ledger wants.  An ARMED
        # injection overrides the downgrade: the launch really did
        # sleep, and letting a warm disk cache reclassify the simulated
        # stall as a hit silently greens the storm/blame smokes on any
        # host that has ever compiled these buckets before.
        cache_hit = (not injected) and _cc_hits() > rec._cc0
        stalled = False
        hit = False
        with self._lock:
            ent = self._buckets.get(bucket)
            if ent is None:
                self._buckets[bucket] = {
                    "count": 1, "first_s": rec.submit_s,
                    "steady_min_s": None, "first_ts": rec.ts,
                    "cache_hit": cache_hit}
                rec.compiled = True
                # upper-bound estimate until a warm relaunch
                # establishes the bucket's steady state (the ledger
                # dump refines it; the record keeps the first-hit view)
                rec.compile_s = rec.submit_s
                rec.cache_hit = cache_hit
                if cache_hit:
                    # excluded from the stall counter AND the
                    # COMPILE_STORM window: a disk-served compile is
                    # the fix working, not a storm brewing
                    self.cache_hits += 1
                    hit = True
                else:
                    self._compile_events.append(
                        (time.time(), bucket, rec.submit_s))
                    if rec.submit_s >= self.stall_s:
                        self.compile_stalls += 1
                        stalled = True
            else:
                ent["count"] += 1
                if ent.get("prewarmed") and ent["count"] == 1:
                    # first RUNTIME launch of a boot-prewarmed bucket:
                    # the ledger shows it as a cache hit, not a compile
                    rec.cache_hit = True
                sm = ent["steady_min_s"]
                ent["steady_min_s"] = rec.submit_s if sm is None \
                    else min(sm, rec.submit_s)
        if self.perf:
            if stalled:
                self.perf.inc("ec_compile_stalls")
            if hit:
                self.perf.inc("ec_compile_cache_hits")
            self.perf.hinc("lat_launch_submit", rec.submit_s)
            self.perf.hinc("lat_launch_queue_wait", rec.queue_wait_s)

    def materialized(self, rec: LaunchRecord | None,
                     device_s: float) -> None:
        """The launch's results materialized: close the record into
        the ring.  No-op on a None record."""
        if rec is None:
            return
        rec.device_s = device_s
        with self._lock:
            self._ring.append(rec)
            self.launches += 1
            self.launched_runs += rec.runs
            self.launched_bytes += rec.nbytes
        if self.perf:
            self.perf.inc("ec_launches")
            self.perf.inc("ec_launch_runs", rec.runs)
            self.perf.inc("ec_launch_bytes", rec.nbytes)
            self.perf.hinc("lat_launch_device", device_s)

    def note_prewarm(self, bucket: str, warm_s: float,
                     cache_hit: bool) -> None:
        """Record one boot-time prewarm compile (ops/prewarm.py): the
        bucket enters the ledger PRE-SEEDED — the first runtime launch
        of a prewarmed bucket is not first-seen, so it pays no compile,
        trips no stall/injection, and records as a cache hit.  Prewarm
        compiles never enter the COMPILE_STORM window: they happen
        before the daemon reports up, by design."""
        with self._lock:
            if bucket not in self._buckets:
                self._buckets[bucket] = {
                    "count": 0, "first_s": warm_s,
                    "steady_min_s": None, "first_ts": time.time(),
                    "prewarmed": True, "cache_hit": cache_hit}
            if cache_hit:
                self.prewarm_cache_hits += 1
            else:
                self.prewarm_compiles += 1
            self.prewarm_s += warm_s
        if self.perf:
            self.perf.inc("ec_prewarm_cache_hits" if cache_hit
                          else "ec_prewarm_compiles")
            self.perf.hinc("lat_prewarm", warm_s)

    def prewarm_summary(self) -> dict:
        """The prewarm tallies block (`prewarm status` asok /
        compile-ledger provenance)."""
        with self._lock:
            prewarmed = sum(1 for e in self._buckets.values()
                            if e.get("prewarmed"))
            return {
                "compiles": self.prewarm_compiles,
                "cache_hits": self.prewarm_cache_hits,
                "buckets": prewarmed,
                "total_s": round(self.prewarm_s, 3),
            }

    # -- compile ledger ------------------------------------------------------

    def _bucket_rows(self) -> list[dict]:
        with self._lock:
            items = [(b, dict(e)) for b, e in self._buckets.items()]
        rows = []
        for bucket, e in items:
            steady = e["steady_min_s"]
            compile_s = e["first_s"] if steady is None \
                else max(0.0, e["first_s"] - steady)
            rows.append({
                "bucket": bucket,
                "count": e["count"],
                "first_s": round(e["first_s"], 4),
                "steady_s": round(steady, 6)
                if steady is not None else None,
                "compile_s": round(compile_s, 4),
                "first_ts": e["first_ts"],
                "prewarmed": bool(e.get("prewarmed")),
                "cache_hit": bool(e.get("cache_hit")),
            })
        rows.sort(key=lambda r: -r["compile_s"])
        return rows

    def compile_ledger(self) -> dict:
        """The `compile ledger` asok payload: every jit bucket this
        host ever compiled, worst first."""
        rows = self._bucket_rows()
        return {
            "enabled": self.enabled,
            "stall_threshold_s": self.stall_s,
            "buckets": rows,
            "distinct_buckets": len(rows),
            "total_compile_s": round(
                sum(r["compile_s"] for r in rows), 4),
            "max_compile_s": round(
                max((r["compile_s"] for r in rows), default=0.0), 4),
            "compile_stalls": self.compile_stalls,
            "compile_cache_hits": self.cache_hits,
            "prewarm": self.prewarm_summary(),
            "persistent_cache": _cc_status(),
            "window": self.compile_report(),
        }

    def compile_report(self, window_s: float | None = None) -> dict:
        """Windowed compile summary (the OSD ships this monward on
        MPGStats; mon/monitor.py turns budget overruns into the
        COMPILE_STORM health warning)."""
        window_s = self.storm_window_s if window_s is None \
            else float(window_s)
        cutoff = time.time() - window_s
        with self._lock:
            recent = [(b, s) for ts, b, s in self._compile_events
                      if ts >= cutoff]
        total = sum(s for _b, s in recent)
        worst = max(recent, key=lambda e: e[1], default=None)
        return {
            "window_s": window_s,
            "compile_s": round(total, 3),
            "events": len(recent),
            # IN-WINDOW stalls (against the current threshold): a
            # stall from hours ago must not read as current activity
            # nor keep the monward report shipping forever; the
            # lifetime counter stays on ec_compile_stalls / the ledger
            "stalls": sum(1 for _b, s in recent if s >= self.stall_s),
            "stalls_total": self.compile_stalls,
            "worst_bucket": worst[0] if worst else None,
            "worst_s": round(worst[1], 3) if worst else 0.0,
        }

    # -- dumps ---------------------------------------------------------------

    def profile(self, last: int | None = None) -> dict:
        """The `launch profile` asok payload: ledger aggregates +
        the (bounded) ring of recent launches, newest last."""
        with self._lock:
            ring = list(self._ring)
            launches = self.launches
        if last is not None:
            n = max(0, int(last))
            ring = ring[-n:] if n else []
        lat = self.perf.dump_latencies() if self.perf else {}
        return {
            "enabled": self.enabled,
            "launches": launches,
            "runs": self.launched_runs,
            "bytes": self.launched_bytes,
            "runs_per_launch": round(self.launched_runs / launches, 2)
            if launches else 0.0,
            "ring_size": self._ring.maxlen,
            "latencies": lat,
            "recent": [r.to_dict() for r in ring],
            "uptime_s": round(time.time() - self.created_at, 1),
        }

    def bench_summary(self) -> dict:
        """The bench-row provenance block (`launch_ledger` in
        bench.py / cluster_bench rows): enough for a BENCH_r* reader
        to see what the device plane actually did — and on which
        jax/device — without the asok."""
        def q(key, quant):
            est = self.perf.quantile(key, quant) if self.perf else None
            return round(est[0] * 1e3, 3) if est else None
        with self._lock:
            launches = self.launches
        rows = self._bucket_rows()
        out = {
            "launches": launches,
            "runs_per_launch": round(self.launched_runs / launches, 2)
            if launches else 0.0,
            "bytes": self.launched_bytes,
            "compile_buckets": len(rows),
            "compile_s_total": round(
                sum(r["compile_s"] for r in rows), 3),
            "compile_stalls": self.compile_stalls,
            "compile_cache_hits": self.cache_hits,
            "prewarm_compiles": self.prewarm_compiles,
            "prewarm_cache_hits": self.prewarm_cache_hits,
            "device_ms_p50": q("lat_launch_device", 0.5),
            "device_ms_p99": q("lat_launch_device", 0.99),
            "queue_wait_ms_p50": q("lat_launch_queue_wait", 0.5),
            "queue_wait_ms_p99": q("lat_launch_queue_wait", 0.99),
        }
        try:
            import jax
            import jaxlib
            out["jax"] = jax.__version__
            out["jaxlib"] = jaxlib.__version__
            out["device_kind"] = jax.devices()[0].device_kind
            out["backend"] = jax.default_backend()
        except Exception:  # noqa: BLE001 — provenance must not fail a row
            pass
        return out

    def reset(self) -> None:
        """Clear ledger state (benches isolating a phase; the perf
        histograms are monotonic by design and stay)."""
        with self._lock:
            self._ring.clear()
            self._buckets.clear()
            self._compile_events.clear()
            self.launches = 0
            self.launched_runs = 0
            self.launched_bytes = 0
            self.compile_stalls = 0
            self.cache_hits = 0
            self.prewarm_compiles = 0
            self.prewarm_cache_hits = 0
            self.prewarm_s = 0.0


def device_profiler() -> DeviceProfiler:
    """The host's flight recorder (built on first use, enabled)."""
    return DeviceProfiler.host_instance()
