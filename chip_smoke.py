#!/usr/bin/env python3
"""chip_smoke.py — the quickest proof that the system still starts on
the chip.

ONE process, the one that holds the chip, drives the main path through
the entry points a user calls — tools/vstart.Cluster + RadosClient,
what `vstart` and `rados_cli` wrap — at a size a Ceph operator would
call real (`rados bench` defaults on BASELINE.json configs[1]):

  12 OSDs + 1 mon, EC pool plugin=jax technique=cauchy k=8 m=3
  stripe_unit=4096, pg_num 128, MemStore.

  codec cross-check   jax vs the isa CPU plugin on 1 MiB stripes for
                      k8m3 / k4m2 / k2m1 (parity, fused crcs vs host
                      crc32c, decode with 1..m erasures) + the pinned
                      corpus (tests/test_corpus.py)
  write               256 x 4 MiB write_full, 16 writers in flight
                      (512 KiB per shard: every drain rides hier_*)
  read back           every acked object, bytes compared
  degraded            kill + mark down a data-shard holder; read a
                      sample through the window (reconstruct-on-read
                      decodes on the device); write through it; and
                      overwrite 4 KiB blocks of an object that lost a
                      data shard (a degraded read-modify-write: the
                      pre-read reconstructs its stripe, a plain launch
                      makes the parity), read back at once
  recover             revive; wait active+clean (grouped recovery
                      decode on the device); read everything back
  deep scrub          every OSD: errors == 0, device bytes > 0
  s3                  one S3 gateway on the same cluster: data pool EC
                      k=4 m=2, index pool replicated x3; CreateBucket
                      (11 index shards, from the configuration), 8
                      signed PUTs of 64 KiB over HTTP, each read back
                      and the bucket listed

It FAILS — non-zero exit, one clear line on stderr, no result line —
when JAX reports no accelerator, when any phase raises, any client op
fails or times out, any byte differs, a write drain left the hier
kernels, a containment/fallback counter moved, scrub hashed nothing on
the device, or the native library did not build.  No phase's failure
becomes a null field.

The LAST stdout line is the result, exactly
{"ok": true, "device": {"platform": ..., "kind": ..., "count": ...}}
with the device as JAX reports it.  The line before it is one JSON
object of the run's facts (not metrics; "claim": null): set-up (boot,
prewarm, compiles) is timed apart from the serving window, and
compilations inside the window are counted.  The full report, compile
ledger included, also lands in chiprun_out/chip_smoke.json.
"""

from __future__ import annotations

import argparse
import contextlib
import dataclasses
import faulthandler
import json
import os
import sys
import time
import traceback
from concurrent.futures import ThreadPoolExecutor

ROOT = os.path.dirname(os.path.abspath(__file__))
OUT_DIR = os.path.join(ROOT, "chiprun_out")
DEADLINE_S = 1150           # the contract allows 1200 s, compiles included
POOL = "smoke83"
PROFILE = {"plugin": "jax", "technique": "cauchy", "k": "8", "m": "3",
           "stripe_unit": "4096"}
CROSSCHECK_GEOMETRIES = ((8, 3), (4, 2), (2, 1))


class SmokeFailure(Exception):
    """A check of the smoke did not hold."""


@dataclasses.dataclass(frozen=True)
class Size:
    """How big one run is.  The defaults are the real size; the tier-1
    test shrinks every field but keeps the geometry."""
    osds: int = 12
    pg_num: int = 128
    objects: int = 256
    object_bytes: int = 4 << 20
    writers: int = 16
    degraded_reads: int = 32
    degraded_writes: int = 8
    degraded_overwrites: int = 8
    s3_puts: int = 8
    s3_object_bytes: int = 64 << 10
    s3_index_shards: int = 11
    stripe_bytes: int = 1 << 20       # codec cross-check stripe
    clean_timeout_s: float = 300.0


def _require(cond, what: str) -> None:
    if not cond:
        raise SmokeFailure(what)


class _Report:
    """Per-phase wall time and compile counts (ops/compile_cache
    counters deltaed around each phase)."""

    def __init__(self, compile_cache):
        self._cc = compile_cache
        self.phases: dict[str, dict] = {}

    @contextlib.contextmanager
    def phase(self, name: str, window: str):
        c0 = self._cc.counters()
        t0 = time.perf_counter()
        rec = self.phases[name] = {"window": window}
        print(f"# chip_smoke: {name} ...", file=sys.stderr, flush=True)
        yield rec
        c1 = self._cc.counters()
        rec["wall_s"] = round(time.perf_counter() - t0, 3)
        rec["compiles"] = c1["misses"] - c0["misses"]
        rec["cache_hits"] = c1["hits"] - c0["hits"]
        rec["compile_s"] = round(c1["compile_s"] - c0["compile_s"], 3)
        print(f"# chip_smoke: {name} done {rec}", file=sys.stderr,
              flush=True)

    def window(self, window: str) -> dict:
        rows = [r for r in self.phases.values() if r["window"] == window]
        return {k: round(sum(r[k] for r in rows), 3)
                for k in ("wall_s", "compiles", "cache_hits",
                          "compile_s")}


# -- phases -----------------------------------------------------------------

def _payload(seed: int, i: int, nbytes: int) -> bytes:
    import numpy as np
    return np.random.default_rng([seed, i]).integers(
        0, 256, nbytes, dtype=np.uint8).tobytes()


def codec_crosscheck(size: Size, seed: int) -> dict:
    """jax vs isa on the same stripes: parity, fused crcs, decode."""
    import numpy as np

    from ceph_tpu.common import crc32c as host_crc
    from ceph_tpu.ec import ErasureCodePluginRegistry
    reg = ErasureCodePluginRegistry.instance()
    out = {}
    for k, m in CROSSCHECK_GEOMETRIES:
        prof = {"k": str(k), "m": str(m)}
        jx = reg.factory("jax", {**prof, "technique": "cauchy"})
        isa = reg.factory("isa", {**prof, "technique": "cauchy"})
        _require(np.array_equal(jx.matrix, isa.matrix),
                 f"k{k}m{m}: jax and isa generator matrices differ")
        rng = np.random.default_rng([seed, k, m])
        chunks = rng.integers(0, 256, (k, size.stripe_bytes // k),
                              dtype=np.uint8)
        want = np.asarray(isa.encode_chunks(chunks))
        _require(np.array_equal(np.asarray(jx.encode_chunks(chunks)),
                                want),
                 f"k{k}m{m}: jax parity differs from isa")
        par, crcs = jx.encode_chunks_with_crc(chunks)
        _require(np.array_equal(np.asarray(par), want),
                 f"k{k}m{m}: fused parity differs from isa")
        dense = np.concatenate([chunks, want], axis=0)
        _require(crcs == [host_crc.crc32c(r.tobytes(), 0xFFFFFFFF)
                          for r in dense],
                 f"k{k}m{m}: fused crcs differ from host crc32c")
        for ne in range(1, m + 1):
            erased = list(range(1, 1 + ne)) if k > ne \
                else list(range(ne))
            broken = dense.copy()
            broken[erased] = 0
            _require(np.array_equal(
                np.asarray(jx.decode_chunks(broken, erased)), dense),
                f"k{k}m{m}: decode of erasures {erased} differs")
        out[f"k{k}m{m}"] = {"fused_point": jx.fused_point()}
    # the pinned corpus: parity bytes on disk never change silently
    sys.path.insert(0, os.path.join(ROOT, "tests"))
    import test_corpus
    corpus = json.loads(test_corpus.CORPUS.read_text())
    for plugin, profile in test_corpus.CASES:
        cid = test_corpus._case_id(plugin, profile)
        _require(test_corpus._encode_digests(plugin, profile)
                 == corpus[cid], f"corpus case {cid} changed")
    out["corpus_cases"] = len(test_corpus.CASES)
    return out


def prewarm_plan(size: Size, profiler) -> dict:
    """Compile, as set-up, the launch shapes this run's object size
    produces: n concurrent writes are n runs of object_bytes/k (the
    pow2 bucketing collapses 1..writers runs to these), and degraded
    reads / recovery decode whole chunks with 1..m shards missing; a
    degraded overwrite decodes and encodes one stripe unit a shard."""
    from ceph_tpu.ec import ErasureCodePluginRegistry
    from ceph_tpu.ec.interface import Profile
    from ceph_tpu.ops import prewarm
    codec = ErasureCodePluginRegistry.instance().factory(
        "jax", Profile(dict(PROFILE)))
    chunk = size.object_bytes // codec.get_data_chunk_count()
    unit = int(PROFILE["stripe_unit"])
    counts, n = [], 1
    while n < size.writers:
        counts.append(n)
        n *= 2
    counts.append(size.writers)
    plan = prewarm.PrewarmPlan(
        codec, profiler=profiler, budget_s=float(DEADLINE_S),
        run_shapes=[(chunk,) * n for n in counts],
        plain_widths=[unit] if size.degraded_overwrites else [],
        decode_widths=[chunk, unit] if size.degraded_overwrites
        else [chunk])
    st = plan.run()
    _require(not st["truncated"] and not st["skipped"],
             f"prewarm entries failed to compile: {st['errors']}")
    return {**{k: st[k] for k in ("planned", "done", "compiles",
                                  "cache_hits", "total_s")},
            "fused_buckets": [b for b in st["buckets"]
                              if b.startswith("x:")],
            "decode_buckets": sum(b.startswith("d:")
                                  for b in st["buckets"])}


_EC_KEYS = ("ec_drain_submits", "ec_fused_kernel_drains",
            "ec_fused_fallback_drains", "ec_drain_errors",
            "ec_mesh_errors", "ec_host_queue_drains",
            "ec_reconstruct_reads", "ec_rmw_reconstructs",
            "ec_read_timeouts",
            "ec_repair_helper_bytes", "ec_repair_reconstructed_bytes",
            "ec_scrub_device_bytes", "ec_scrub_host_bytes")


def _ec_counters(osds, into: dict) -> dict:
    """Sum the per-PG EC backend counters of `osds` into `into`."""
    for osd in osds:
        for name, counters in osd.cct.perf.dump().items():
            if name.startswith("ec.") and isinstance(counters, dict):
                for key in _EC_KEYS:
                    into[key] = into.get(key, 0) + int(
                        counters.get(key, 0) or 0)
    return into


def _write_objects(client, names, payloads, writers: int) -> int:
    def work(part):
        io = client.open_ioctx(POOL)
        for name in part:
            io.write_full(name, payloads[name])
        return len(part)
    parts = [names[w::writers] for w in range(writers)]
    with ThreadPoolExecutor(max_workers=writers) as ex:
        return sum(ex.map(work, [p for p in parts if p]))


def _read_and_compare(client, payloads, names, readers: int) -> int:
    def work(part):
        io = client.open_ioctx(POOL)
        for name in part:
            want = payloads[name]
            got = io.read(name, len(want))
            _require(got == want, f"object {name}: bytes read back "
                                  f"differ from bytes acked")
        return sum(len(payloads[n]) for n in part)
    parts = [names[w::readers] for w in range(readers)]
    with ThreadPoolExecutor(max_workers=readers) as ex:
        return sum(ex.map(work, [p for p in parts if p]))


def _overwrite_blocks(client, payloads, name: str, size: Size,
                      seed: int) -> int:
    """4 KiB overwrites of an object written whole, one at a time,
    walking its stripes and the chunks within them; `payloads[name]`
    follows.  Returns how many were acknowledged."""
    k, unit = int(PROFILE["k"]), int(PROFILE["stripe_unit"])
    stripes = max(1, size.object_bytes // (k * unit))
    io = client.open_ioctx(POOL)
    now = bytearray(payloads[name])
    for i in range(size.degraded_overwrites):
        off = (i % stripes) * k * unit + (i // stripes % k) * unit
        block = _payload(seed, 1_000_000 + i, unit)
        io.write(name, block, off)
        now[off:off + unit] = block
    payloads[name] = bytes(now)
    return size.degraded_overwrites


S3_PROFILE = {"plugin": "jax", "technique": "cauchy", "k": "4",
              "m": "2", "stripe_unit": "4096"}
S3_CREDS = ("SMOKEACCESSKEY", "smoke-secret")


def s3_ingest(cluster, size: Size, seed: int) -> dict:
    """The S3 front door, end to end: the gateway's two pools as an
    operator makes them, a gateway on a client of its own, then signed
    requests over HTTP — a bucket, `s3_puts` objects, each read back,
    the bucket listed."""
    import hashlib
    import http.client

    from ceph_tpu.rgw import sigv4
    from ceph_tpu.rgw.gateway import S3Gateway
    from ceph_tpu.rgw.store import DATA_POOL, META_POOL
    admin = cluster.client()
    admin.set_ec_profile("smoke42", dict(S3_PROFILE))
    admin.create_pool(DATA_POOL, "erasure", erasure_code_profile="smoke42",
                      pg_num=32)
    admin.create_pool(META_POOL, "replicated", size=3, pg_num=8)
    cluster.wait_active_clean(timeout=size.clean_timeout_s)
    gw = S3Gateway(cluster.client(), ("127.0.0.1", 0),
                   creds={S3_CREDS[0]: S3_CREDS[1]})
    conn = http.client.HTTPConnection(*gw.addr, timeout=120)

    def call(method: str, path: str, query: str = "", body: bytes = b""):
        headers = {"host": f"{gw.addr[0]}:{gw.addr[1]}"}
        headers.update(sigv4.sign_request(
            method, path, query, headers, body, *S3_CREDS))
        conn.request(method, path + (f"?{query}" if query else ""),
                     body=body, headers=headers)
        reply = conn.getresponse()
        return reply.status, dict(reply.getheaders()), reply.read()

    try:
        _require(call("PUT", "/smoke")[0] == 200, "s3: CreateBucket")
        shards = gw.store.bucket_stats("smoke")["shards"]
        _require(shards == size.s3_index_shards,
                 f"s3: bucket has {shards} index shards, the "
                 f"configuration says {size.s3_index_shards}")
        bodies = {f"obj{i}": _payload(seed, 2_000_000 + i,
                                      size.s3_object_bytes)
                  for i in range(size.s3_puts)}
        for key, body in bodies.items():
            status, headers, _ = call("PUT", f"/smoke/{key}", body=body)
            _require(status == 200 and headers.get("ETag")
                     == f'"{hashlib.md5(body).hexdigest()}"',
                     f"s3: PUT {key}: {status} {headers.get('ETag')}")
        for key, body in bodies.items():
            status, _, got = call("GET", f"/smoke/{key}")
            _require(status == 200 and got == body,
                     f"s3: GET {key}: bytes read back differ")
        status, _, listing = call("GET", "/smoke", "list-type=2")
        listed = sorted(part.split("</Key>")[0] for part in
                        listing.decode().split("<Key>")[1:])
        _require(status == 200 and listed == sorted(bodies),
                 f"s3: listing {listed}")
        perf = gw.perf_dump()["rgw"]
        _require(perf["rgw_put"] == size.s3_puts
                 and perf["rgw_failed"] == 0, f"s3: counters {perf}")
        return {"ops": size.s3_puts,
                "bytes": size.s3_puts * size.s3_object_bytes,
                "index_shards": shards,
                "rados_ops_per_put":
                    perf["rgw_put_rados_ops"] / perf["rgw_put"]}
    finally:
        conn.close()
        gw.shutdown()


def run(size: Size = Size(), seed: int = 1,
        require_platform: str | None = "tpu") -> dict:
    """Run every phase; returns the report dict or raises.  The
    platform check is an argument, not an environment switch: the
    tier-1 test passes "cpu" with a tiny Size."""
    import jax

    from ceph_tpu.common import native
    from ceph_tpu.ops import bitsliced, compile_cache, device
    from ceph_tpu.ops.profiler import device_profiler

    dev = device.describe()
    _require(require_platform is None
             or dev["platform"] == require_platform,
             f"JAX found no {require_platform} device: "
             f"jax.devices()[0] is {dev}")
    on_chip = dev["kernels"] == "pallas-mosaic"
    cache_dir = compile_cache.enable()
    rep = _Report(compile_cache)
    out: dict = {
        "ok": False,
        "device": {"platform": dev["platform"], "kind": dev["kind"],
                   "count": dev["count"]},
        "device_used": str(jax.devices()[0]),
        "kernels": dev["kernels"],
        "versions": _versions(),
        "compile_cache": {"dir": cache_dir,
                          "placed_by": compile_cache.status()["placed_by"]},
        "deployment": {
            "osds": size.osds, "mons": 1, "pool": dict(PROFILE),
            "pg_num": size.pg_num, "store": "memstore",
            "objects": size.objects, "object_bytes": size.object_bytes,
            "writers": size.writers, "seed": seed},
        "reduced": {
            "store": "MemStore, as cluster_bench uses: no disk in "
                     "the path",
            "topology": "mon and OSDs are threads of the one process "
                        "that holds the chip (one process per chip)",
            "data": f"{size.objects} x {size.object_bytes} B written "
                    f"once (a smoke, not a `rados bench` window)"},
    }

    with rep.phase("preflight", "setup"):
        _require(native.available(),
                 f"native library did not build: {native.build_error()}")
    with rep.phase("codec_crosscheck", "setup"):
        out["codec_crosscheck"] = codec_crosscheck(size, seed)
    with rep.phase("prewarm", "setup"):
        out["prewarm"] = prewarm_plan(size, device_profiler())

    from ceph_tpu.crush.hash import crush_hash32
    from ceph_tpu.osd.types import pg_t
    from ceph_tpu.tools.vstart import Cluster
    cluster = Cluster(n_osds=size.osds, heartbeat_interval=1.0, conf={
        "rgw_bucket_index_shards": size.s3_index_shards})
    counters: dict = {}
    try:
        with rep.phase("boot", "setup"):
            cluster.start()
            client = cluster.client()
            client.set_ec_profile("smoke83", dict(PROFILE))
            client.create_pool(POOL, "erasure",
                               erasure_code_profile="smoke83",
                               pg_num=size.pg_num)
            cluster.wait_active_clean(timeout=size.clean_timeout_s)

        names = [f"obj{i:05d}" for i in range(size.objects)]
        payloads = {n: _payload(seed, i, size.object_bytes)
                    for i, n in enumerate(names)}
        with rep.phase("write", "serving") as ph:
            ph["ops"] = _write_objects(client, names, payloads,
                                       size.writers)
            ph["bytes"] = sum(len(p) for p in payloads.values())
        with rep.phase("read_back", "serving") as ph:
            ph["bytes"] = _read_and_compare(client, payloads, names,
                                            size.writers)
            ph["ops"] = len(names)

        # victim: a DATA-shard holder (acting position < k) of the
        # first object's PG, so that object is a certain reconstruct
        osdmap = cluster.osds[0].osdmap
        pool_id = next(pid for pid, pl in osdmap.pools.items()
                       if pl.name == POOL)
        _, acting, _, _ = osdmap.pg_to_up_acting_osds(
            pg_t(pool_id, crush_hash32(names[0]) % size.pg_num))
        victim = acting[2]
        with rep.phase("degraded", "serving") as ph:
            _ec_counters([cluster.osds[victim]], counters)
            cluster.kill_osd(victim)
            cluster.mark_osd_down(victim)
            sample = names[:size.degraded_reads]
            ph["victim_osd"] = victim
            ph["bytes"] = _read_and_compare(client, payloads, sample,
                                            min(size.writers, 4))
            ph["ops"] = len(sample)
            deg_names = [f"deg{i:05d}"
                         for i in range(size.degraded_writes)]
            for i, n in enumerate(deg_names):
                payloads[n] = _payload(seed, size.objects + i,
                                       size.object_bytes)
            ph["degraded_writes"] = _write_objects(
                client, deg_names, payloads, min(size.writers, 4))
            names = names + deg_names
            ph["degraded_overwrites"] = _overwrite_blocks(
                client, payloads, names[0], size, seed)
            _read_and_compare(client, payloads, names[:1], 1)
        with rep.phase("recover", "serving"):
            cluster.revive_osd(victim)
            cluster.wait_active_clean(timeout=size.clean_timeout_s)
        with rep.phase("read_back_after_recovery", "serving") as ph:
            ph["bytes"] = _read_and_compare(client, payloads, names,
                                            size.writers)
            ph["ops"] = len(names)
        with rep.phase("deep_scrub", "serving") as ph:
            scrub = {"pgs": 0, "objects": 0, "errors": 0,
                     "device_bytes": 0, "host_bytes": 0}
            for osd in cluster.osds:
                for pg in osd._asok_scrub({"deep": True}).values():
                    scrub["pgs"] += 1
                    scrub["objects"] += pg["objects"]
                    scrub["errors"] += len(pg["errors"])
                    scrub["device_bytes"] += pg.get("device_bytes", 0)
                    scrub["host_bytes"] += pg.get("host_bytes", 0)
            ph.update(scrub)
        _ec_counters(cluster.osds, counters)
        queue = cluster.osds[0]._asok_launch_queue_status({})["queue"]
        # read BEFORE the S3 step: the checks below are of the 4 MiB
        # write path, and a 16 KiB-per-shard launch rides the flat
        # kernel, which those counters call a fallback
        ledger = device_profiler().compile_ledger()
        with rep.phase("s3", "serving") as ph:
            ph.update(s3_ingest(cluster, size, seed))
    finally:
        cluster.stop()

    paths: dict[str, int] = {}
    for row in ledger["buckets"]:
        if row["bucket"].startswith("x:"):
            p = row["bucket"].split(":")[1]
            paths[p] = paths.get(p, 0) + row["count"]
    in_window = [r for r in ledger["buckets"] if not r["prewarmed"]]
    out.update({
        "phases": rep.phases,
        "setup": rep.window("setup"),
        "serving": rep.window("serving"),
        "ops_acked": len(names),
        "bytes_acked": sum(len(payloads[n]) for n in names),
        "counters": {**counters,
                     **{f"ec_host_{k}": queue[k] for k in (
                         "launches", "launch_retries", "launch_errors",
                         "decode_launches", "cross_pg_launches",
                         "avg_runs_per_launch")}},
        "scrub": scrub,
        "fused_point": out["codec_crosscheck"]["k8m3"]["fused_point"],
        "fused_paths": paths,
        "first_seen_in_window": [
            {k: r[k] for k in ("bucket", "count", "first_s",
                               "cache_hit")} for r in in_window],
        "persistent_cache": compile_cache.status(),
        "aot": bitsliced.aot_stats(),
        "device_peak_bytes": (jax.devices()[0].memory_stats() or {}
                              ).get("peak_bytes_in_use"),
        "compile_ledger": ledger,
    })

    # -- what must hold -----------------------------------------------------
    c = out["counters"]
    for key in ("ec_drain_errors", "ec_mesh_errors", "ec_read_timeouts",
                "ec_host_launch_retries", "ec_host_launch_errors"):
        _require(c[key] == 0, f"{key} = {c[key]} (must be 0)")
    _require(out["aot"]["errors"] == 0, f"AOT errors: {out['aot']}")
    _require(c["ec_reconstruct_reads"] > 0,
             "no degraded read was served by reconstruct-on-read")
    _require(c["ec_rmw_reconstructs"] == size.degraded_overwrites,
             f"{size.degraded_overwrites} degraded overwrites, "
             f"{c['ec_rmw_reconstructs']} reconstructing pre-reads")
    _require(c["ec_host_decode_launches"] > 0
             and c["ec_repair_reconstructed_bytes"] > 0,
             "recovery rebuilt nothing through the decode launch path")
    _require(scrub["errors"] == 0 and scrub["objects"] >= len(names),
             f"deep scrub: {scrub}")
    if on_chip:
        _require(c["ec_fused_kernel_drains"] > 0
                 and c["ec_fused_fallback_drains"] == 0
                 and all(p.startswith("hier") for p in paths),
                 f"write drains left the hier kernels: {paths}, "
                 f"kernel={c['ec_fused_kernel_drains']} "
                 f"fallback={c['ec_fused_fallback_drains']}")
        _require(scrub["device_bytes"] > 0,
                 "deep scrub hashed no bytes on the device")
    else:
        # the CPU twin (tier-1): the same phases, served by the XLA
        # formulations — and it must SAY so
        _require(c["ec_fused_kernel_drains"] == 0
                 and set(paths) == {"xla"},
                 f"CPU run claims device kernels: {paths}")
    out["ok"] = True
    out["claim"] = None
    return out


def _versions() -> dict:
    from importlib import metadata
    return {pkg: metadata.version(pkg)
            for pkg in ("jax", "jaxlib", "libtpu")}


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=1,
                    help="object data and stripes are made from it")
    args = ap.parse_args(argv)
    # a hang must end as a failure inside the contract's time limit,
    # with every thread's stack on stderr
    faulthandler.dump_traceback_later(DEADLINE_S, exit=True)
    try:
        report = run(seed=args.seed)
    except SmokeFailure as e:
        print(f"chip_smoke: FAILED: {e}", file=sys.stderr)
        return 1
    except Exception as e:  # noqa: BLE001 — a phase raised: that IS
        traceback.print_exc()               # the failure, said once
        print(f"chip_smoke: FAILED: a phase raised {e!r}",
              file=sys.stderr)
        return 1
    faulthandler.cancel_dump_traceback_later()
    os.makedirs(OUT_DIR, exist_ok=True)
    with open(os.path.join(OUT_DIR, "chip_smoke.json"), "w") as f:
        json.dump(report, f, indent=1)
    report.pop("compile_ledger")        # the file keeps it
    print(json.dumps(report))
    # the result line: exactly these keys, the device as JAX reports it
    print(json.dumps({"ok": report["ok"], "device": report["device"]}),
          flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
