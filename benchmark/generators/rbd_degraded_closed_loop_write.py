"""Traffic generator `rbd_degraded_closed_loop_write`: fio `ioengine=rbd
rw=randwrite` on an image whose erasure-coded data pool has lost one
OSD that is down and not yet out.  `rbd_closed_loop_write` with one
step more in set-up and more to compare afterwards; image, prefill,
payloads, offsets, window and the model are that generator's own
functions, imported (its `drive` has no place for a step between
prefill and warm-up, so the run is spelled out here again: the two are
to be made one by the next benchmark issue).

Set-up, in order: the metadata pool and the image, the prefill on the
HEALTHY cluster, the plain-parity launch shapes; then the failure the
configuration describes (`failure`: the OSD is killed and marked down
at once, it stays in, so CRUSH maps nothing elsewhere), the wait until
every PG of both pools is active again with none peering (undersized
and degraded they stay), the DECODE launch shapes the degraded path
can produce (compiled through the program's own prewarm plan), and
`warmup_ops` throw-away overwrites on the degraded cluster.  Nothing
recovers inside the window: the OSD is down from before the warm-up
to after the read-back, and the osdmap's epoch does not move.

`verify` decides `correct` in three steps, every limit 0 and exact:
the WHOLE image read back through the handle on the degraded cluster;
for a seed-drawn sample of objects the five LIVE shards against the
reference's encoding of the model's object, any k of them against the
reference's DECODE, and the dead OSD's shard against the encoding of
the PREFILL (nothing may have written to a dead store); then the OSD
is revived, the cluster waits for active+clean, and the same objects
are compared again with ALL six shards and read back once more.
"""

from __future__ import annotations

import gc
import itertools
import threading
import time

import numpy as np

from generators.closed_loop_write import _sleep_until
from generators.rbd_closed_loop_write import (  # noqa: F401 — the
    END_TO_END, _make_image, _object_bytes, _prefill,  # harness reads
    _stale_generations, _walk_stores, _warm_plain,     # some of them
    end_to_end, make_payloads, payload, prefill_object)
from generators.rbd_closed_loop_write import launch_shapes as _shapes


# audited objects whose live shards also go through the reference's
# decode (numpy, 1 MiB a shard: the first few are enough)
DECODE_OBJECTS = 8


def launch_shapes(traffic: dict, config: dict) -> list[tuple]:
    """The healthy generator's fused shapes (the prefill runs on the
    healthy cluster).  The harness calls this before anything is
    booted: a program that cannot wait for a degraded cluster to
    become active says so here, at once."""
    from ceph_tpu.tools.vstart import Cluster
    if not hasattr(Cluster, "wait_active"):
        raise SystemExit(
            "benchmark: this program's Cluster has no wait_active: "
            "it cannot tell when a cluster with an OSD down is "
            "serving again. Refusing to run.")
    return _shapes(traffic, config)


def decode_widths(traffic: dict, config: dict) -> list[int]:
    """Every decode launch width the degraded path can produce: a
    reconstructing pre-read is one stripe-run of bytes per shard (THE
    window's shape); the launch queue coalesces submissions of one
    erasure pattern up to its cap and pads to a power of two (a
    launch that carried more than one was never seen — PERF.md §6 —
    but the queue's rule allows it, and nothing may compile inside
    the window); the read-back and the recovery decode one whole
    object's shard at a time."""
    from ceph_tpu.parallel.launch_queue import DECODE_MAX_LAUNCH_W
    from deploy import ec_geometry
    from roofline import chunk_bytes
    k, _, su = ec_geometry(config)
    run = chunk_bytes(traffic["op_bytes"], k, su)
    widths, w = [], run
    while w < min(run * traffic["writers"], DECODE_MAX_LAUNCH_W):
        widths.append(w)
        w *= 2
    widths.append(w)
    widths.append(chunk_bytes(_object_bytes(config), k, su))
    return sorted(set(widths))


def _warm_decode(dep, traffic: dict) -> dict:
    """Compile the decode launch shapes through the program's prewarm
    plan: one execution per width at the one erasure CARDINALITY the
    program decodes with (a program is shared by every pattern of one
    cardinality): a pre-read, a degraded read and a recovery each
    decode from exactly k shards, so m are erased — the lost shard
    and the parity shard that was not read."""
    from ceph_tpu.ec import ErasureCodePluginRegistry
    from ceph_tpu.ec.interface import Profile
    from ceph_tpu.ops import prewarm as pw
    from ceph_tpu.ops.profiler import device_profiler
    from deploy import ec_geometry
    prof = dep.config["pool"]["profile"]
    _, m, _ = ec_geometry(dep.config)
    codec = ErasureCodePluginRegistry.instance().factory(
        prof["plugin"], Profile(dict(prof)))
    plan = pw.PrewarmPlan(
        codec, profiler=device_profiler(), budget_s=600.0,
        run_shapes=[], plain_widths=[],
        decode_widths=decode_widths(traffic, dep.config),
        decode_erasures=[tuple(range(m))])
    st = plan.run()
    if st["truncated"] or st["skipped"]:
        raise RuntimeError(f"decode prewarm did not finish: {st}")
    return {k: st[k] for k in ("planned", "done", "compiles",
                               "cache_hits", "total_s")}


def _fail_osd(dep, state: dict) -> float:
    """The configuration's failure: the OSD dies, the monitor marks it
    down (it stays in), and the cluster is waited for until every PG
    is active again.  Placement before the kill is kept for the audit:
    afterwards the osdmap has a hole where the OSD was."""
    from ceph_tpu.rbd.image import _data
    fail = dep.config["failure"]
    spec = dep.config["image"]
    t0 = time.perf_counter()
    state["victim"] = fail["osd"]
    state["placement"] = {
        n: dep.acting(_data(spec["name"], n))
        for n in range(-(-spec["size"] // _object_bytes(dep.config)))}
    dep.cluster.kill_osd(fail["osd"])
    dep.cluster.mark_osd_down(fail["osd"])
    dep.cluster.wait_active(timeout=300.0)
    state["epoch_degraded"] = dep.cluster.mon.osdmap.epoch
    return time.perf_counter() - t0


# -- the run -----------------------------------------------------------------

def drive(dep, traffic: dict, state: dict, seconds: float,
          before_window=None, in_window=None) -> dict:
    """Set-up of the image on the healthy cluster, the failure, then
    warm-up, ramp, window and drain as `rbd_closed_loop_write.drive`.
    Returns the per-op records (n, t_start, t_ack, error name or
    None) — warm-up, ramp and tail included — and the window's
    clock."""
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
    setup["degrade_s"] = _fail_osd(dep, state)
    setup["decode_prewarm"] = _warm_decode(dep, traffic)
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

    # throw-away overwrites on the degraded cluster: the whole path,
    # reconstruct included, once per writer thread before anything is
    # timed (acknowledged writes like the rest)
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


# -- the comparison that decides `correct` -----------------------------------

def _read_back(image, model, seed: int, state: dict, written: dict,
               op_bytes: int, objects, readers: int) -> dict:
    """`objects` of the image read through the handle and compared
    block by block with the model: blocks unreadable, differing, and
    of those torn (neither the prefill's bytes nor one whole write)."""
    osize = model.object_bytes
    per_obj = osize // op_bytes
    unreadable, differing, torn = [], [], []
    todo = iter(objects)
    lock = threading.Lock()

    def is_torn(b: int, raw: bytes) -> bool:
        n_obj, off = divmod(b * op_bytes, osize)
        if raw == prefill_object(seed, n_obj, osize)[off:off + op_bytes]:
            return False
        n = written.get(b)
        return n is None or raw != payload(state, b, n)

    def reader() -> None:
        while True:
            with lock:
                n = next(todo, None)
            if n is None:
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
            if got.size != want.size:
                got = np.resize(got, want.size)
            rows = np.flatnonzero(
                (got.reshape(-1, op_bytes)
                 != want.reshape(-1, op_bytes)).any(axis=1))
            for r in rows:
                b = n * per_obj + int(r)
                differing.append(b)
                if is_torn(b, got[r * op_bytes:(r + 1) * op_bytes]
                           .tobytes()):
                    torn.append(b)

    threads = [threading.Thread(target=reader) for _ in range(readers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    return {"unreadable": len(unreadable), "differing": len(differing),
            "torn": len(torn), "seconds": time.perf_counter() - t0}


def _audit(dep, reference, walk, to_audit, want_of, shards_of) -> dict:
    """Shards as they lie in the stores against the reference's
    encoding: for each audited object n, `shards_of(n)` {shard: osd}
    against `want_of(n)` (the object's bytes the shards must encode).
    Counts shards missing, with wrong bytes, a wrong crc of their own
    bytes (`chunk_crc` where the shard carries one — it does once it
    was overwritten or rebuilt —, else the append-time crcs of all k+m
    shards in its hinfo, which an overwrite invalidates), or wrong
    sizes in the hinfo."""
    from ceph_tpu.osd.ec_util import CHUNK_CRC_KEY, HINFO_KEY, HashInfo
    from ceph_tpu.rbd.image import _data
    from deploy import ec_geometry
    k, m, su = ec_geometry(dep.config)
    spec = dep.config["image"]
    out = {"shards": 0, "missing": 0, "bytes_wrong": 0, "crcs_wrong": 0,
           "sizes_wrong": 0}
    for n in to_audit:
        name = _data(spec["name"], n)
        data_n = want_of(n)
        want, want_crcs = reference.expected_shards(data_n, k, m, su)
        for shard, osd_id in shards_of(n).items():
            out["shards"] += 1
            hit = walk["heads"].get((osd_id, shard), {}).get(name)
            if hit is None:
                out["missing"] += 1
                continue
            store = dep.cluster.osds[osd_id].store
            data = store.read(*hit)
            attrs = store.getattrs(*hit)
            if data.shape != want[shard].shape or \
                    not np.array_equal(data, want[shard]):
                out["bytes_wrong"] += 1
            hinfo = HashInfo.decode(attrs[HINFO_KEY]) \
                if HINFO_KEY in attrs else None
            carried = attrs.get(CHUNK_CRC_KEY)
            if carried is not None:
                ok = int.from_bytes(carried, "little") \
                    == want_crcs[shard]
            else:
                ok = hinfo is not None and \
                    list(hinfo.cumulative_shard_hashes) == want_crcs
            if not ok:
                out["crcs_wrong"] += 1
            if hinfo is None or hinfo.logical_size != len(data_n) \
                    or hinfo.total_chunk_size != want.shape[1]:
                out["sizes_wrong"] += 1
    return out


def verify(dep, traffic: dict, state: dict, run: dict, seed: int,
           reference) -> dict:
    """Every number against its limit (all 0, all exact), in the
    three steps of the module's head: degraded read-back; the live
    and the dead stores; revive, recovery, all six shards.  Leaves
    the cluster active+clean and the image closed."""
    from ceph_tpu.rbd.image import _data
    from deploy import ec_geometry
    image, perm = state["image"], state["perm"]
    spec = dep.config["image"]
    op_bytes = traffic["op_bytes"]
    victim = state["victim"]
    placement = state["placement"]
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
    readers = traffic["readback"]["readers"]

    # -- 1: the whole image through the handle, degraded
    back = _read_back(image, model, seed, state, written, op_bytes,
                      range(model.objects), readers)

    # -- 2: the stores while the OSD is still down
    t0 = time.perf_counter()
    k, m, su = ec_geometry(dep.config)
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

    def live(n: int) -> dict:
        return {s: o for s, o in enumerate(placement[n]) if o != victim}

    def dead(n: int) -> dict:
        return {s: o for s, o in enumerate(placement[n]) if o == victim}

    def final(n: int):
        return model.object(n)

    def at_kill(n: int):
        return np.frombuffer(prefill_object(
            seed, n, model.object(n).size), dtype=np.uint8)

    walk = _walk_stores(dep)
    audit = _audit(dep, reference, walk, to_audit, final, live)
    # nothing may have written to the dead store: its shards encode
    # the prefill, with the append-time crcs
    down = _audit(dep, reference, walk, to_audit, at_kill, dead)
    down_changed = down["missing"] + down["bytes_wrong"] \
        + down["crcs_wrong"] + down["sizes_wrong"]
    # any k live shards give the object: the reference's decode of
    # what the live stores hold (objects that lost a shard)
    undecodable = 0
    for n in to_audit[:DECODE_OBJECTS]:
        if not dead(n):
            continue
        rows = {}
        name = _data(spec["name"], n)
        for shard, osd_id in live(n).items():
            hit = walk["heads"].get((osd_id, shard), {}).get(name)
            if hit is not None:
                rows[shard] = dep.cluster.osds[osd_id].store.read(*hit)
        use = dict(sorted(rows.items())[-k:])    # parity included
        if len(use) < k or not np.array_equal(
                reference.object_from_shards(use, k, m, su,
                                             final(n).size), final(n)):
            undecodable += 1
    live_osds = {o.osd_id for o in dep.cluster.osds
                 if o.osd_id != victim}
    stale = _stale_generations(
        {"generations": [g for g in walk["generations"]
                         if g[0].osd_id in live_osds]})
    generations_left = len(walk["generations"])
    stored_bytes = walk["stored_bytes"]
    audit_s = time.perf_counter() - t0

    # -- 3: revive, recover, and all six shards again
    t0 = time.perf_counter()
    # from the failure to here — warm-up, ramp, window, read-back —
    # the OSD was down and in and no map was published
    epochs_degraded = dep.cluster.mon.osdmap.epoch \
        - state["epoch_degraded"]
    dep.cluster.revive_osd(victim)
    dep.cluster.wait_active_clean(timeout=600.0)
    recover_s = time.perf_counter() - t0
    t0 = time.perf_counter()
    healed = _audit(dep, reference, _walk_stores(dep), to_audit, final,
                    lambda n: dict(enumerate(placement[n])))
    healed_back = _read_back(image, model, seed, state, written,
                             op_bytes, to_audit, readers)
    image.close()
    recovered_audit_s = time.perf_counter() - t0

    compared = {
        "write_errors": [failed, 0],
        "readback_unreadable": [back["unreadable"], 0],
        "readback_differing": [back["differing"], 0],
        "blocks_torn": [back["torn"], 0],
        "audit_shards_missing": [audit["missing"], 0],
        "audit_shard_bytes_wrong": [audit["bytes_wrong"], 0],
        "audit_chunk_crcs_wrong": [audit["crcs_wrong"], 0],
        "audit_logical_size_wrong": [audit["sizes_wrong"], 0],
        "audit_down_shard_changed": [down_changed, 0],
        "audit_objects_undecodable": [undecodable, 0],
        "generations_stale": [stale, 0],
        "osdmap_epochs_while_degraded": [epochs_degraded, 0],
        "recovered_shards_missing": [healed["missing"], 0],
        "recovered_shard_bytes_wrong": [healed["bytes_wrong"], 0],
        "recovered_chunk_crcs_wrong": [healed["crcs_wrong"], 0],
        "recovered_readback_differing": [
            healed_back["differing"] + healed_back["unreadable"], 0],
    }
    return {
        "compared": compared,
        "checked": {"acked": len(acked),
                    "read_back": model.objects * (model.object_bytes
                                                  // op_bytes),
                    "audited_objects": len(to_audit),
                    "audited_shards": audit["shards"],
                    "audited_down_shards": down["shards"],
                    "recovered_shards": healed["shards"],
                    "objects_overwritten": len(model.overwritten),
                    "generations_left": generations_left,
                    "victim": victim,
                    "recover_s": recover_s,
                    "recovered_audit_s": recovered_audit_s},
        "correct": bool(acked) and audit["shards"] > 0
        and down["shards"] > 0 and healed["shards"] > 0
        and all(v <= lim for v, lim in compared.values()),
        "attempted": len(run["ops"]), "failed": failed,
        "acked_bytes": len(acked) * op_bytes,
        "stored_bytes": stored_bytes,
        "readback_s": back["seconds"],
        "audit_s": audit_s,
    }
