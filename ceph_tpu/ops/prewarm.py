"""Boot-time jit-bucket prewarm: compile before reporting `up`.

The pow2 launch-shape bucketing (ops/bitsliced.py) already collapses
the jit key space to ~log2 shapes per kernel path — small enough to
enumerate and compile at OSD boot, BEFORE the daemon sends MOSDBoot.
With the persistent compile cache (ops/compile_cache.py) a prewarm
pass is compiles on the host's first boot ever and millisecond disk
reads on every boot after, so the runtime write path never sees a
first-seen bucket at all: no compile stalls, no COMPILE_STORM, no
heartbeat flaps on revive.

Exactness guarantee: the plan does NOT predict bucket strings — it
EXECUTES the same plugin entry points the launch queue and the direct
backend paths call (`encode_extents_with_crc_submit`,
`encode_chunks_submit`, `decode_chunks`), with synthetic zero runs of
the planned geometry, and reads the bucket back through the same
`launch_bucket()` the queue uses.  A prewarmed bucket therefore
matches the runtime bucket by construction, not by parallel
arithmetic.  Each executed entry also registers the AOT executable
(plugin `aot_compile_*` hooks -> ops/bitsliced.aot_compile) so the
covered shapes dispatch compiled code with zero trace-time at runtime.

Every warmed bucket is pre-seeded into the flight recorder
(DeviceProfiler.note_prewarm), so the first RUNTIME launch of a
prewarmed bucket is not first-seen: it pays no compile, trips no
stall injection, and records as a cache hit in the launch ledger.

Bounded: `budget_s` (conf osd_ec_prewarm_budget_s) caps the wall the
boot may spend here; a cutoff marks the plan truncated and the daemon
boots with whatever was warmed — prewarm is an optimization, never a
boot dependency.  Entries run cheapest-first so a tight budget still
covers the hottest small-write buckets.
"""

from __future__ import annotations

import threading
import time

import numpy as np

from ..common.util import next_pow2
from . import compile_cache

# one prewarm per process: in-process clusters (tools/vstart.py) boot
# many OSDs into one interpreter, but the jit caches being warmed are
# process-global — the first booting daemon warms for all
_guard_lock = threading.Lock()
_ran = False
_last_status: dict | None = None
# buckets whose XLA program has already been built in THIS process:
# jax's in-memory executable cache emits no persistent-cache hit
# event on reuse, so without this ledger an entry sharing an
# already-built program (same pow2 bucket from another entry, or a
# warm in-process re-run) would count as a compile it never paid
_warmed_buckets: set[str] = set()


class PrewarmPlan:
    """Ordered prewarm entries for one codec.

    widths: total fused-drain byte widths (pow2 multiples of the flat
    fused tile); for each, every pow2 run count r with r <= W/tile is
    an entry (r runs of W/r bytes — the depth-r pipelined write-storm
    shape).  run_shapes: explicit fused drains (a tuple of per-run
    byte widths each) IN PLACE of the widths x run_counts product,
    for a caller that knows its object geometry (chip_smoke.py: n
    concurrent 4 MiB writes are n runs of 512 KiB).  plain_widths:
    plain (no-crc) encode widths.  decode_widths x decode_erasures:
    recovery/reconstruct shapes.
    """

    def __init__(self, plugin, widths=None, run_counts=None,
                 plain_widths=None, decode_widths=None,
                 decode_erasures=None, budget_s: float = 8.0,
                 profiler=None, run_shapes=None):
        from .bitsliced import FUSED_TILE
        self.plugin = plugin
        self.budget_s = float(budget_s)
        self.profiler = profiler
        tile = FUSED_TILE
        if widths is None:
            widths = [tile << j for j in range(5)]     # 2K..32K
        if run_counts is None:
            run_counts = [1, 2, 4]
        if plain_widths is None:
            plain_widths = [2048 << j for j in range(4)]
        if decode_widths is None:
            # up to parallel/launch_queue.DECODE_MAX_LAUNCH_W: the
            # grouped recovery decode caps its concatenated launch
            # width there and the launch queue pow2-pads every decode,
            # so {pow2 <= cap} IS the full runtime decode width set
            # (single chunks wider than the cap excepted)
            decode_widths = [2048 << j for j in range(6)]   # 2K..64K
        if decode_erasures is None:
            # one representative per erasure CARDINALITY, for every
            # cardinality up to m: decode jits on the bitmat shape,
            # which depends only on how many shards are missing —
            # every same-cardinality pattern shares the program, so
            # _buckets_of seeds the whole combination class from one
            # execution.  Multi-loss cardinalities matter even under
            # single-OSD churn (a remapped acting set can leave a read
            # missing two shards at once), and a kill/revive storm's
            # recovery pass can decode with up to m shards missing.
            m = (plugin.get_chunk_count()
                 - plugin.get_data_chunk_count())
            decode_erasures = [tuple(range(c))
                               for c in range(1, max(1, m) + 1)]
        # entries: ("x", run_widths) | ("c", width) | ("d", width, erasures)
        entries: list[tuple] = []
        for w in sorted(set(plain_widths)):
            entries.append(("c", int(w)))
        for w, era in [(w, e) for w in sorted(set(decode_widths))
                       for e in decode_erasures]:
            entries.append(("d", int(w), tuple(era)))
        if run_shapes is not None:
            entries.extend(("x", tuple(int(w) for w in shape))
                           for shape in run_shapes)
        else:
            for w in sorted(set(widths)):
                for r in sorted(set(run_counts)):
                    if r >= 1 and w % r == 0 and w // r >= tile:
                        entries.append(("x", (int(w // r),) * int(r)))
                    elif r == 1:
                        entries.append(("x", (int(w),)))
        self.entries = entries
        self.status: dict = {
            "planned": len(entries), "done": 0, "skipped": 0,
            "truncated": False, "total_s": 0.0, "budget_s": self.budget_s,
            "compiles": 0, "cache_hits": 0, "buckets": [],
            "errors": [],
        }

    # -- plan prediction (for tests / status, no execution) -------------

    def planned_buckets(self) -> list[str]:
        """Bucket strings this plan will seed, computed WITHOUT
        compiling: submit-handle geometry is reproduced from the entry
        shapes.  Used by tests to compare against runtime buckets."""
        out = []
        for e in self.entries:
            out.extend(self._buckets_of(e, None))
        return out

    def _buckets_of(self, entry, handle) -> list[str]:
        """Bucket spellings one entry covers.  With a live submit
        handle the fused bucket comes from plugin.launch_bucket (the
        queue's own refinement); without one it is predicted from the
        entry geometry via the same pow2 arithmetic."""
        plugin = self.plugin
        kind = entry[0]
        if kind == "x":
            if handle is not None and hasattr(plugin, "launch_bucket"):
                return [plugin.launch_bucket(handle)]
            from ..parallel.launch_queue import _extents_bucket
            if handle is not None:
                return [_extents_bucket(handle)]
            from .bitsliced import FUSED_TILE
            run_ws = entry[1]
            tile = FUSED_TILE
            nt = next_pow2(sum(-(-w // tile) for w in run_ws))
            base = (f"x:xla:w{nt * tile}"
                    f":r{next_pow2(max(1, len(run_ws)))}")
            point = getattr(plugin, "_fused_point", None)
            if point and getattr(plugin, "_use_w32", False):
                base += (f":t{point['tile']}:wb{point['wb']}"
                         f":{point['combine']}")
            return [base]
        if kind == "c":
            w = entry[1]
            if hasattr(plugin, "encode_chunks_submit"):
                if handle is not None:
                    sub_kind = handle[0]
                else:
                    sub_kind = "w32" if getattr(plugin, "_use_w32",
                                                False) else "bytes"
                # both spellings: the direct backend path keys on the
                # plugin handle kind, the launch queue on its own
                # ("h", ...) wrapper
                return [f"c:{sub_kind}:w{w}", f"c:h:w{w}"]
            return [f"c:np:w{w}"]
        w, era = entry[1], entry[2]
        # the executed pattern stands in for its whole cardinality
        # class (same bitmat shape -> same jit program): seed every
        # pattern string of that cardinality
        from itertools import combinations
        n = plugin.get_chunk_count()
        return [f"d:e{''.join(str(i) for i in c)}:w{w}"
                for c in combinations(range(n), len(era))]

    # -- execution ------------------------------------------------------

    def _run_entry(self, entry):
        """Execute one entry's real plugin calls (blocking on the
        device result so the compile definitely finished) and return
        the live submit handle (fused) or None."""
        plugin = self.plugin
        k = plugin.get_data_chunk_count()
        kind = entry[0]
        if kind == "x" and hasattr(plugin,
                                   "encode_extents_with_crc_submit"):
            run_ws = entry[1]
            if hasattr(plugin, "aot_compile_fused"):
                plugin.aot_compile_fused(list(run_ws))
            runs = [np.zeros((k, w), dtype=np.uint8) for w in run_ws]
            handle = plugin.encode_extents_with_crc_submit(runs)
            plugin.encode_extents_with_crc_finalize(handle)
            return handle
        if kind == "c":
            w = entry[1]
            if hasattr(plugin, "aot_compile_encode"):
                plugin.aot_compile_encode(w)
            chunks = np.zeros((k, w), dtype=np.uint8)
            if hasattr(plugin, "encode_chunks_submit"):
                h = plugin.encode_chunks_submit(chunks)
                plugin.encode_chunks_finalize(h)
                return h
            plugin.encode_chunks(chunks)
            return None
        if kind == "d":
            w, era = entry[1], entry[2]
            if hasattr(plugin, "aot_compile_decode"):
                plugin.aot_compile_decode(w, len(era))
            n = plugin.get_chunk_count()
            dense = np.zeros((n, w), dtype=np.uint8)
            plugin.decode_chunks(dense, list(era))
        return None

    def run(self) -> dict:
        """Execute the plan within budget; returns (and stores) the
        `prewarm status` dict.  Failures of individual entries are
        counted, kept in `errors` and skipped — prewarm must never
        fail a boot, but a shape that does not compile must be
        readable in `prewarm status` (chip_smoke.py fails on one)."""
        t0 = time.perf_counter()
        st = self.status
        for entry in self.entries:
            spent = time.perf_counter() - t0
            if spent >= self.budget_s:
                st["truncated"] = True
                st["skipped"] = st["planned"] - st["done"]
                break
            hits0 = compile_cache.hit_count()
            te = time.perf_counter()
            try:
                handle = self._run_entry(entry)
            except Exception as e:  # noqa: BLE001 — warm what we can
                st["skipped"] += 1
                st["errors"].append(f"{entry}: {e!r}"[:2000])
                continue
            warm_s = time.perf_counter() - te
            buckets = self._buckets_of(entry, handle)
            # a disk-cache hit event OR every covered bucket already
            # built in-process means no XLA compile happened — the
            # in-memory program reuse path emits no event, so it must
            # be inferred from the warmed-bucket ledger or `compiles`
            # over-reports on warm boots
            cache_hit = compile_cache.hit_count() > hits0 or (
                bool(buckets) and
                all(b in _warmed_buckets for b in buckets))
            for b in buckets:
                if self.profiler is not None:
                    self.profiler.note_prewarm(b, warm_s, cache_hit)
                st["buckets"].append(b)
                _warmed_buckets.add(b)
            st["done"] += 1
            if cache_hit:
                st["cache_hits"] += 1
            else:
                st["compiles"] += 1
        st["total_s"] = round(time.perf_counter() - t0, 3)
        st["persistent_cache"] = compile_cache.status()
        return st


def run_once(plugin, profiler=None, budget_s: float = 8.0,
             **plan_kwargs) -> dict:
    """Process-level prewarm entry (OSD boot): the first caller runs
    the plan, later callers (more in-process daemons) get the stored
    status back — the warmed caches are process-global."""
    global _ran, _last_status
    with _guard_lock:
        if _ran:
            return dict(_last_status or {}, reused=True)
        _ran = True
    plan = PrewarmPlan(plugin, budget_s=budget_s, profiler=profiler,
                       **plan_kwargs)
    status = plan.run()
    with _guard_lock:
        _last_status = status
    return status


def last_status() -> dict | None:
    return _last_status


def reset_for_tests() -> None:
    """Tests only: allow another run_once (paired with
    jax.clear_caches when simulating a daemon restart)."""
    global _ran, _last_status
    with _guard_lock:
        _ran = False
        _last_status = None
        # a simulated restart clears jax's in-memory executables, so
        # the in-process warmed ledger must reset with it
        _warmed_buckets.clear()
