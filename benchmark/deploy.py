"""Bring up the deployment a configuration file describes, on the
program's own entry (ceph_tpu.tools.vstart.Cluster + RadosClient, what
`vstart` and `rados_cli` wrap), and read its counters.  Every option
the file does not name is the program's default."""

from __future__ import annotations

import copy
import json
import os
import time

HERE = os.path.dirname(os.path.abspath(__file__))


def load_json(kind: str, name: str) -> dict:
    """benchmark/<kind>/<name>.json, found by the name alone."""
    path = os.path.join(HERE, kind, f"{name}.json")
    with open(path) as f:
        return json.load(f)


def merged(base: dict, over: dict) -> dict:
    """`base` with `over` laid on top, dicts merged key by key."""
    out = copy.deepcopy(base)
    for key, val in over.items():
        if isinstance(val, dict) and isinstance(out.get(key), dict):
            out[key] = merged(out[key], val)
        else:
            out[key] = copy.deepcopy(val)
    return out


def rehearsal_of(spec: dict) -> dict:
    """The tiny form of a configuration or traffic file: its own
    "rehearsal" block laid over it."""
    return merged(spec, spec.get("rehearsal", {}))


def ec_geometry(config: dict) -> tuple[int, int, int]:
    prof = config["pool"]["profile"]
    return int(prof["k"]), int(prof["m"]), int(prof["stripe_unit"])


def prewarm(config: dict, run_shapes: list[tuple]) -> dict:
    """Compile, as set-up, the fused launch shapes this cell's traffic
    produces, and no others (no decode or plain-encode buckets)."""
    from ceph_tpu.ec import ErasureCodePluginRegistry
    from ceph_tpu.ec.interface import Profile
    from ceph_tpu.ops import prewarm as pw
    from ceph_tpu.ops.profiler import device_profiler
    codec = ErasureCodePluginRegistry.instance().factory(
        config["pool"]["profile"]["plugin"],
        Profile(dict(config["pool"]["profile"])))
    plan = pw.PrewarmPlan(codec, profiler=device_profiler(),
                          budget_s=900.0, run_shapes=run_shapes,
                          plain_widths=[], decode_widths=[])
    st = plan.run()
    if st["truncated"] or st["skipped"]:
        raise RuntimeError(f"prewarm did not finish: {st}")
    return {k: st[k] for k in ("planned", "done", "compiles",
                               "cache_hits", "total_s")}


class Deployment:
    """The running cluster of one cell, with one client."""

    def __init__(self, config: dict):
        from ceph_tpu.tools.vstart import Cluster
        dep = config["deployment"]
        self.config = config
        self.pool = config["pool"]["name"]
        self.cluster = Cluster(
            n_osds=dep["osds"], n_mons=dep["mons"],
            heartbeat_interval=dep["heartbeat_interval"],
            objectstore=dep["objectstore"],
            boot_parallel=dep["boot_parallel"],
            conf=dict(dep.get("conf", {})))
        self.client = None

    def start(self, clean_timeout_s: float = 300.0) -> None:
        pool = self.config["pool"]
        self.cluster.start()
        self.client = self.cluster.client()
        if pool["type"] == "erasure":
            self.client.set_ec_profile(pool["name"], dict(pool["profile"]))
            self.client.create_pool(pool["name"], "erasure",
                                    erasure_code_profile=pool["name"],
                                    pg_num=pool["pg_num"])
        else:
            self.client.create_pool(pool["name"], "replicated",
                                    size=pool["size"],
                                    pg_num=pool["pg_num"])
        self.cluster.wait_active_clean(timeout=clean_timeout_s)

    def stop(self) -> None:
        self.cluster.stop()

    # -- counters ----------------------------------------------------------

    def snapshot(self) -> dict:
        """One reading of every counter the per-layer readers use:
        each OSD's `perf dump`, the host launch queue's status and the
        process's compile counters, with the clock they were read
        at."""
        from ceph_tpu.ops import compile_cache
        t0 = time.perf_counter()
        snap = {
            "osd_perf": [osd.cct.perf.dump()
                         for osd in self.cluster.osds],
            "launch_queue": self._launch_queue_status(),
            "compile": compile_cache.counters(),
            "osdmap_epoch": self.cluster.mon.osdmap.epoch,
        }
        snap["t"] = time.perf_counter()
        snap["took_s"] = snap["t"] - t0
        return snap

    @staticmethod
    def _launch_queue_status() -> dict | None:
        from ceph_tpu.parallel.launch_queue import ECLaunchQueue
        queue = ECLaunchQueue.host_get()
        return None if queue is None else queue.status()

    def launch_queue_bytes(self) -> int:
        """Input bytes the host launch queue has sent to the device."""
        status = self._launch_queue_status()
        return 0 if status is None else int(status["coalesced_bytes"])

    # -- what lies in the stores -------------------------------------------

    def pool_id(self) -> int:
        return self.cluster.mon.osdmap.lookup_pool(self.pool).id

    def store_index(self) -> dict:
        """{(osd, shard): {object name: (cid, ghobject)}} for every
        shard object of the pool, and the bytes they hold together."""
        pool_id = self.pool_id()
        index, stored = {}, 0
        for osd in self.cluster.osds:
            for cid in osd.store.list_collections():
                if cid.pgid.pool != pool_id:
                    continue
                for goid in osd.store.list_objects(cid):
                    if goid.hobj.name.startswith("__"):
                        continue
                    stored += osd.store.stat(cid, goid)
                    index.setdefault((osd.osd_id, cid.shard), {}
                                     )[goid.hobj.name] = (cid, goid)
        return {"objects": index, "stored_bytes": stored}

    def acting(self, name: str) -> list[int]:
        """OSD ids holding shards 0..n-1 of object `name`."""
        osdmap = self.cluster.mon.osdmap
        pgid = osdmap.object_to_pg(self.pool_id(), name)
        return list(osdmap.pg_to_up_acting_osds(pgid)[1])

    def read_shard(self, index: dict, osd_id: int, shard: int,
                   name: str):
        """(bytes, crcs in hinfo, shard size in hinfo, logical size) of
        one shard object as it lies in the store, or None."""
        from ceph_tpu.osd.ec_util import HINFO_KEY, HashInfo
        hit = index["objects"].get((osd_id, shard), {}).get(name)
        if hit is None:
            return None
        cid, goid = hit
        store = self.cluster.osds[osd_id].store
        data = store.read(cid, goid)
        raw = store.getattrs(cid, goid).get(HINFO_KEY)
        if raw is None:
            return data, None, None, None
        hinfo = HashInfo.decode(raw)
        return (data, list(hinfo.cumulative_shard_hashes),
                hinfo.total_chunk_size, hinfo.logical_size)
