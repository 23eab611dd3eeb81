"""Traffic generator `rbd_closed_loop_write`: the fio `ioengine=rbd
rw=randwrite` role.  N writers share ONE open image handle (exclusive
lock + object map, data objects on the cell's erasure-coded pool);
each writes `op_bytes` at an aligned offset of a prefilled image,
waits for the ack and writes the next.  The parameters come from a
traffic file and the image from the configuration; nothing here knows
a cell's name.

What the deployment needs beyond its one pool is made here, before the
window and inside set-up: the replicated metadata pool, the image, the
prefill (every object written once, whole: appends through the fused
kernel), the plain-parity launch shapes an overwrite can produce
(compiled through the program's own prewarm plan), and `warmup_ops`
throw-away overwrites that are acknowledged writes like the rest.

The window is `closed_loop_write`'s (see there): writers staggered
over `stagger_s`, `ramp_s` of uncounted traffic, opened and closed on
the clock, counters read outside it, nothing else in the process.

Offsets: op n writes block perm[n] of a seeded permutation of the
image's blocks (fio's random map: no block twice), so no two writes to
one BLOCK are ever in flight and the final content is unambiguous; two
in flight on one stripe or one object happen and must be right.
Payloads: a pool of `payload_pool` seed-drawn payloads; op n carries
payload n mod pool with its block number and n in its first 16 bytes,
so a block read back is the prefill's, one whole write, or torn.
"""

from __future__ import annotations

import gc
import itertools
import threading
import time

import numpy as np

from generators.closed_loop_write import _sleep_until
from generators.closed_loop_write import end_to_end as _window_numbers

END_TO_END = ("write_MBps", "write_p95_ms")


def _object_bytes(config: dict) -> int:
    return 1 << config["image"]["order"]


def _require_data_pool() -> None:
    """A program whose images cannot name a data pool cannot run this
    deployment: say so before anything is booted (the harness calls
    `launch_shapes` first), not after a minute of set-up."""
    import inspect

    from ceph_tpu.rbd.image import RBD
    if "data_pool" not in inspect.signature(RBD.create).parameters:
        raise SystemExit(
            "benchmark: this program's RBD.create takes no data_pool: "
            "it cannot put an image's data on an erasure-coded pool. "
            "Refusing to run.")


def launch_shapes(traffic: dict, config: dict) -> list[tuple]:
    """The FUSED launch shapes of set-up: the prefill's n concurrent
    whole-object writes are n runs of one object's bytes per shard
    (pow2-bucketed).  The window's plain shapes are compiled in
    `drive` (`plain_widths`)."""
    from deploy import ec_geometry
    _require_data_pool()
    from roofline import chunk_bytes
    k, _, su = ec_geometry(config)
    chunk = chunk_bytes(_object_bytes(config), k, su)
    writers = traffic["prefill"]["writers"]
    counts, n = [], 1
    while n < writers:
        counts.append(n)
        n *= 2
    counts.append(writers)
    return [(chunk,) * n for n in counts]


def plain_widths(traffic: dict, config: dict) -> list[int]:
    """Every plain (no-crc) parity launch width the window can
    produce: one overwrite is one stripe-run of bytes per shard, a
    drain or a coalesced launch holds 1..writers of them, and the
    launch queue pads to a power of two."""
    from deploy import ec_geometry
    from roofline import chunk_bytes
    k, _, su = ec_geometry(config)
    run = chunk_bytes(traffic["op_bytes"], k, su)
    widths, w = [], run
    while w < run * traffic["writers"]:
        widths.append(w)
        w *= 2
    widths.append(w)
    return widths


def make_payloads(traffic: dict, seed: int) -> dict:
    """The seed's data: the overwrite payloads now; the prefill per
    object on demand (`prefill_object`: written in `drive`, drawn
    again for the model in `verify`); the block order in `drive` (it
    needs the image's size)."""
    pool = [np.random.default_rng([seed, 1, j]).integers(
        0, 256, traffic["op_bytes"], dtype=np.uint8).tobytes()
        for j in range(traffic["payload_pool"])]
    return {"seed": seed, "pool": pool}


def prefill_object(seed: int, n: int, size: int) -> bytes:
    return np.random.default_rng([seed, 2, n]).bytes(size)


def payload(state: dict, block: int, n: int) -> bytes:
    """Op n's bytes for `block`: both numbers, then the pool's."""
    base = state["pool"][n % len(state["pool"])]
    return b"".join((block.to_bytes(8, "little"),
                     n.to_bytes(8, "little"), memoryview(base)[16:]))


# -- set-up inside drive -----------------------------------------------------

def _make_image(dep, state: dict) -> None:
    """The metadata pool and the image of the configuration, opened
    once: the handle every writer shares."""
    from ceph_tpu.rbd.image import RBD, Image
    spec = dep.config["image"]
    meta = spec["meta_pool"]
    dep.client.create_pool(meta["name"], meta["type"],
                           size=meta["size"], pg_num=meta["pg_num"])
    dep.cluster.wait_active_clean(timeout=300.0)
    meta_io = dep.client.open_ioctx(meta["name"])
    RBD(meta_io).create(spec["name"], spec["size"],
                        order=spec["order"], data_pool=dep.pool)
    state["image"] = Image(meta_io, spec["name"],
                           exclusive=spec["exclusive"])


def _prefill(dep, traffic: dict, state: dict) -> float:
    """Every object written once, whole, through the image handle."""
    image = state["image"]
    size = _object_bytes(dep.config)
    todo = itertools.count()
    errors = []

    def writer() -> None:
        while True:
            n = next(todo)
            if n * size >= image.size():
                return
            try:
                image.write(n * size, prefill_object(
                    state["seed"], n, min(size, image.size() - n * size)))
            except Exception as e:  # noqa: BLE001 — set-up must not
                errors.append(repr(e))          # go on half filled
                return

    t0 = time.perf_counter()
    threads = [threading.Thread(target=writer, name=f"bench-prefill-{w}")
               for w in range(traffic["prefill"]["writers"])]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError(f"prefill failed: {errors[:3]}")
    return time.perf_counter() - t0


def _warm_plain(dep, traffic: dict) -> dict:
    """Compile the window's plain launch shapes through the program's
    prewarm plan (what deploy.prewarm does for the fused ones)."""
    from ceph_tpu.ec import ErasureCodePluginRegistry
    from ceph_tpu.ec.interface import Profile
    from ceph_tpu.ops import prewarm as pw
    from ceph_tpu.ops.profiler import device_profiler
    prof = dep.config["pool"]["profile"]
    codec = ErasureCodePluginRegistry.instance().factory(
        prof["plugin"], Profile(dict(prof)))
    plan = pw.PrewarmPlan(codec, profiler=device_profiler(),
                          budget_s=600.0, run_shapes=[],
                          plain_widths=plain_widths(traffic, dep.config),
                          decode_widths=[])
    st = plan.run()
    if st["truncated"] or st["skipped"]:
        raise RuntimeError(f"plain prewarm did not finish: {st}")
    return {k: st[k] for k in ("planned", "done", "compiles",
                               "cache_hits", "total_s")}


# -- the run -----------------------------------------------------------------

def drive(dep, traffic: dict, state: dict, seconds: float,
          before_window=None, in_window=None) -> dict:
    """Set-up of the image, then ramp, window and drain as
    `closed_loop_write.drive`.  Returns the per-op records
    (n, t_start, t_ack, error name or None) — warm-up, ramp and tail
    included — and the window's clock."""
    spec = dep.config["image"]
    op_bytes = traffic["op_bytes"]
    nblocks = spec["size"] // op_bytes
    state["perm"] = np.random.default_rng(
        [state["seed"], 3]).permutation(nblocks)
    setup = state["setup"] = {}
    t0 = time.perf_counter()
    _make_image(dep, state)
    setup["image_s"] = time.perf_counter() - t0
    setup["prefill_s"] = _prefill(dep, traffic, state)
    setup["plain_prewarm"] = _warm_plain(dep, traffic)
    image, perm = state["image"], state["perm"]

    writers = traffic["writers"]
    numbers = itertools.count()
    records = [[] for _ in range(writers)]

    def one_write(mine: list) -> None:
        t0 = time.perf_counter()
        n = next(numbers)
        block = int(perm[n])
        data = payload(state, block, n)
        err = None
        try:
            image.write(block * op_bytes, data)
        except Exception as e:  # noqa: BLE001 — counted, by type
            err = type(e).__name__
        mine.append((n, t0, time.perf_counter(), err))

    # throw-away overwrites: the whole path once per writer thread
    # before anything is timed (acknowledged writes like the rest)
    t0 = time.perf_counter()
    warm_left = itertools.count()

    def warmer(w: int) -> None:
        while next(warm_left) < traffic["warmup_ops"]:
            one_write(records[w])

    threads = [threading.Thread(target=warmer, args=(w,),
                                name=f"bench-warm-{w}")
               for w in range(writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    setup["warmup_s"] = time.perf_counter() - t0
    gc.collect()
    gc.freeze()

    t_start = time.perf_counter() + 0.05
    t_open = t_start + traffic["ramp_s"]
    t_close = t_open + seconds

    def writer(w: int) -> None:
        _sleep_until(t_start + traffic["stagger_s"] * w / writers)
        while time.perf_counter() < t_close:
            one_write(records[w])

    threads = [threading.Thread(target=writer, args=(w,),
                                name=f"bench-writer-{w}")
               for w in range(writers)]
    for t in threads:
        t.start()
    _sleep_until(t_open - traffic["counter_lead_s"])
    if before_window is not None:
        before_window()
    _sleep_until(t_open)
    if in_window is not None:
        in_window(t_open, t_close)
    _sleep_until(t_close)
    for t in threads:
        t.join()
    ops = sorted(r for rows in records for r in rows)
    return {"ops": ops, "t_open": t_open, "t_close": t_close,
            "t_drained": time.perf_counter(),
            "ops_per_writer": [len(rows) for rows in records],
            "setup": setup}


def end_to_end(traffic: dict, run: dict) -> dict:
    out = _window_numbers(traffic, run)
    out["image_setup"] = run["setup"]
    return out


# -- the comparison that decides `correct` -----------------------------------

def _walk_stores(dep) -> dict:
    """What the data pool's shard collections hold: the head objects
    {(osd, shard): {name: (cid, ghobject)}}, the generation objects
    [(osd, cid, ghobject)], and the bytes of both."""
    from ceph_tpu.osd.types import NO_GEN
    pool_id = dep.pool_id()
    heads, gens, stored = {}, [], 0
    for osd in dep.cluster.osds:
        for cid in osd.store.list_collections():
            if cid.pgid.pool != pool_id:
                continue
            for goid in osd.store.list_objects(cid):
                if goid.hobj.name.startswith("__") or goid.hobj.snap:
                    continue
                stored += osd.store.stat(cid, goid)
                if goid.generation != NO_GEN:
                    gens.append((osd, cid, goid))
                else:
                    heads.setdefault((osd.osd_id, cid.shard), {}
                                     )[goid.hobj.name] = (cid, goid)
    return {"heads": heads, "generations": gens, "stored_bytes": stored}


def _stale_generations(walk: dict) -> int:
    """Generation objects at or below the roll-forward bound their
    shard was last told: the shard should have removed them."""
    stale = 0
    for osd, cid, goid in walk["generations"]:
        slog = osd.shard_logs.get(cid)
        if slog is not None and \
                goid.generation <= slog.log.rollforward_to.version:
            stale += 1
    return stale


def verify(dep, traffic: dict, state: dict, run: dict, seed: int,
           reference) -> dict:
    """Every number against its limit (all 0, all exact): failed
    writes; the WHOLE image read back through the handle, block by
    block against the model (prefill + every acknowledged write); for
    a seed-drawn sample of the objects (first and last always in) all
    k+m shards as they lie in the stores against the reference's
    encoding of the model's object — bytes, the crc each shard
    carries for its own bytes, sizes; and the generations a shard kept
    past its roll-forward bound."""
    from ceph_tpu.osd.ec_util import CHUNK_CRC_KEY, HINFO_KEY, HashInfo
    from deploy import ec_geometry
    image, perm = state["image"], state["perm"]
    spec = dep.config["image"]
    op_bytes = traffic["op_bytes"]
    acked = [n for n, _, _, err in run["ops"] if err is None]
    failed = sum(1 for op in run["ops"] if op[3] is not None)
    # the model: the seed's prefill, then every acknowledged write
    model = reference.ImageModel(spec["size"], spec["order"])
    for n in range(model.objects):
        model.fill(n * model.object_bytes, prefill_object(
            seed, n, model.object(n).size))
    for n in acked:
        block = int(perm[n])
        model.overlay(block * op_bytes, payload(state, block, n))
    written = {int(perm[n]): n for n, _, _, _ in run["ops"]}

    # -- read-back of the whole image through the client
    osize = model.object_bytes
    per_obj = osize // op_bytes
    unreadable, differing, torn = [], [], []
    todo = itertools.count()

    def block_state(b: int, got: np.ndarray) -> str:
        """Why block b differs: it is the prefill's bytes (a lost
        write), one whole write (the wrong one), or neither (torn)."""
        n_obj, off = divmod(b * op_bytes, osize)
        pre = prefill_object(seed, n_obj, osize)[off:off + op_bytes]
        raw = got.tobytes()
        if raw == pre:
            return "prefill"
        n = written.get(b)
        if n is not None and raw == payload(state, b, n):
            return "whole"
        return "torn"

    def reader() -> None:
        while True:
            n = next(todo)
            if n >= model.objects:
                return
            want = model.object(n)
            try:
                got = np.frombuffer(image.read(n * osize, want.size),
                                    dtype=np.uint8)
            except Exception:  # noqa: BLE001 — counted
                unreadable.extend(range(n * per_obj,
                                        n * per_obj + per_obj))
                continue
            if got.size == want.size and np.array_equal(got, want):
                continue
            got = np.resize(got, want.size) if got.size != want.size \
                else got
            rows = np.flatnonzero(
                (got.reshape(-1, op_bytes)
                 != want.reshape(-1, op_bytes)).any(axis=1))
            for r in rows:
                b = n * per_obj + int(r)
                differing.append(b)
                if block_state(b, got[r * op_bytes:(r + 1) * op_bytes]
                               ) == "torn":
                    torn.append(b)

    threads = [threading.Thread(target=reader)
               for _ in range(traffic["readback"]["readers"])]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    readback_s = time.perf_counter() - t0
    image.close()

    # -- what the device produced, as it lies in the stores
    t0 = time.perf_counter()
    k, m, su = ec_geometry(dep.config)
    walk = _walk_stores(dep)
    rng = np.random.default_rng([seed, 0xC0FFEE])
    limit = traffic["audit"]["max_objects"]
    if limit >= model.objects:
        to_audit = list(range(model.objects))
    else:
        keep = {0, model.objects - 1}
        keep.update(int(n) for n in rng.choice(
            np.arange(1, model.objects - 1), size=limit - 2,
            replace=False))
        to_audit = sorted(keep)
    from ceph_tpu.rbd.image import _data
    missing = bytes_wrong = crcs_wrong = sizes_wrong = shards = 0
    for n in to_audit:
        name = _data(spec["name"], n)
        want, want_crcs = reference.expected_shards(model.object(n),
                                                    k, m, su)
        for shard, osd_id in enumerate(dep.acting(name)):
            shards += 1
            hit = walk["heads"].get((osd_id, shard), {}).get(name)
            if hit is None:
                missing += 1
                continue
            store = dep.cluster.osds[osd_id].store
            data = store.read(*hit)
            attrs = store.getattrs(*hit)
            if data.shape != want[shard].shape or \
                    not np.array_equal(data, want[shard]):
                bytes_wrong += 1
            hinfo = HashInfo.decode(attrs[HINFO_KEY]) \
                if HINFO_KEY in attrs else None
            if n in model.overwritten:
                # overwritten: the shard's own crc of its whole bytes
                carried = attrs.get(CHUNK_CRC_KEY)
                ok = carried is not None and int.from_bytes(
                    carried, "little") == want_crcs[shard]
            else:
                # only ever appended to: the append-time crcs of all
                # k+m shards, in every shard's hinfo
                ok = hinfo is not None and \
                    list(hinfo.cumulative_shard_hashes) == want_crcs
            if not ok:
                crcs_wrong += 1
            if hinfo is None or hinfo.logical_size != model.object(
                    n).size or hinfo.total_chunk_size != want.shape[1]:
                sizes_wrong += 1
    stale = _stale_generations(walk)
    compared = {
        "write_errors": [failed, 0],
        "readback_unreadable": [len(unreadable), 0],
        "readback_differing": [len(differing), 0],
        "blocks_torn": [len(torn), 0],
        "audit_shards_missing": [missing, 0],
        "audit_shard_bytes_wrong": [bytes_wrong, 0],
        "audit_chunk_crcs_wrong": [crcs_wrong, 0],
        "audit_logical_size_wrong": [sizes_wrong, 0],
        "generations_stale": [stale, 0],
    }
    return {
        "compared": compared,
        "checked": {"acked": len(acked),
                    "read_back": model.objects * per_obj,
                    "audited_objects": len(to_audit),
                    "audited_shards": shards,
                    "objects_overwritten": len(model.overwritten),
                    "generations_left": len(walk["generations"])},
        "correct": bool(acked) and shards > 0
        and all(v <= lim for v, lim in compared.values()),
        "attempted": len(run["ops"]), "failed": failed,
        "acked_bytes": len(acked) * op_bytes,
        "stored_bytes": walk["stored_bytes"],
        "readback_s": readback_s,
        "audit_s": time.perf_counter() - t0,
    }
