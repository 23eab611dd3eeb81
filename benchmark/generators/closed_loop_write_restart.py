"""Traffic generator `closed_loop_write_restart`: `closed_loop_write`
(the `rados bench write` role) while one OSD is restarted inside the
window — stopped, down for a while, started again on the store it
had, and recovered by the primaries while the writers go on.

The ramp, the window and the drain are closed_loop_write's own
`drive`; this file hooks into it twice and writes no loop of its own:

- through the `dep` it hands over, whose client's handles note every
  acknowledged object with its clock (the schedule below needs to
  know what was acked while the OSD was down);
- through `in_window`: at the window's opening a schedule thread
  starts that, at `restart.stop_s` after the opening, kills the
  configuration's `failure.osd` and marks it down (a clean stop marks
  itself down).  Until `restart.restart_s` it looks into the live
  stores for each object as soon as it is acknowledged — each must
  have all its live shards, the `ack` guarantee (a later look alone
  can miss a shard that recovery rebuilt meanwhile) —, then once more
  for every object acknowledged since the stop, and revives the OSD
  on its surviving store.

Set-up adds the recovery decode's launch shapes (compiled through the
program's own prewarm plan, at the widths the program says its
grouped recovery decode launches for this object size, one erased
shard) to closed_loop_write's fused ones: nothing may compile inside
the window.

`verify` waits for active+clean (bounded: a program that never gets
there reads not correct and the run goes on to its end), takes a
third reading of the counters there (the recovery readers of
benchmark/metrics/recovery.py read it: the harness's second reading
comes right after the drain, before recovery need have ended), reads
back every acknowledged object, and audits a seed-drawn sample of
objects x all k+m shards against the reference — half of the sample
drawn from the objects acknowledged while the OSD was down whose
placement group holds it, the shards recovery had to rebuild — and
counts, over ALL such objects, those whose shard on the restarted OSD
is missing."""

from __future__ import annotations

import importlib.util
import os
import queue
import threading
import time

import numpy as np

_spec = importlib.util.spec_from_file_location(
    "closed_loop_write", os.path.join(
        os.path.dirname(os.path.abspath(__file__)), "closed_loop_write.py"))
base = importlib.util.module_from_spec(_spec)
_spec.loader.exec_module(base)

END_TO_END = base.END_TO_END
make_payloads = base.make_payloads
end_to_end = base.end_to_end
object_name = base.object_name
object_data = base.object_data


def launch_shapes(traffic: dict, config: dict) -> list[tuple]:
    """closed_loop_write's fused shapes.  The harness calls this before
    anything is booted: a program that cannot restart an OSD says so
    here, at once."""
    from ceph_tpu.tools.vstart import Cluster
    for need in ("kill_osd", "mark_osd_down", "revive_osd",
                 "wait_active_clean"):
        if not hasattr(Cluster, need):
            raise SystemExit(
                f"benchmark: this program's Cluster has no {need}: it "
                f"cannot restart an OSD. Refusing to run.")
    return base.launch_shapes(traffic, config)


def recovery_decode_widths(traffic: dict, config: dict) -> list[int]:
    """The widths of the decode launches the program's grouped
    recovery makes for objects of this size: the program's own rule
    (`ceph_tpu.parallel.launch_queue.recovery_decode_widths`) where it
    has one; else one object's shard, padded as the launch queue
    pads."""
    from ceph_tpu.common.util import next_pow2
    from ceph_tpu.parallel import launch_queue
    from deploy import ec_geometry
    from roofline import chunk_bytes
    k, _, su = ec_geometry(config)
    chunk = chunk_bytes(traffic["object_bytes"], k, su)
    rule = getattr(launch_queue, "recovery_decode_widths", None)
    return list(rule(chunk)) if rule is not None else [next_pow2(chunk)]


def _warm_recovery_decode(dep, traffic: dict) -> dict:
    """Compile the recovery decode's launch shapes through the
    program's prewarm plan: one execution per width with ONE shard
    erased (one OSD restarted: an object lacks one shard, and a
    decode's program is shared by every pattern of one cardinality)."""
    from ceph_tpu.ec import ErasureCodePluginRegistry
    from ceph_tpu.ec.interface import Profile
    from ceph_tpu.ops import prewarm as pw
    from ceph_tpu.ops.profiler import device_profiler
    prof = dep.config["pool"]["profile"]
    codec = ErasureCodePluginRegistry.instance().factory(
        prof["plugin"], Profile(dict(prof)))
    plan = pw.PrewarmPlan(
        codec, profiler=device_profiler(), budget_s=600.0,
        run_shapes=[], plain_widths=[],
        decode_widths=recovery_decode_widths(traffic, dep.config),
        decode_erasures=[(0,)])
    st = plan.run()
    if st["truncated"] or st["skipped"]:
        raise RuntimeError(f"recovery decode prewarm did not finish: {st}")
    return {k: st[k] for k in ("planned", "done", "compiles",
                               "cache_hits", "total_s")}


# -- noting acks -------------------------------------------------------------

class _AckLog:
    """Every acknowledged `write_full` (name, ack clock) and every
    failed one (name, clock, what was raised, the client's timeline of
    it).  While `watching`, each acknowledged name is also handed to
    `fresh` at once, for the schedule's look at the live stores."""

    def __init__(self):
        self.acks: list = []
        self.failures: list = []
        self.fresh: queue.SimpleQueue = queue.SimpleQueue()
        self.watching = False

    def acked(self, name: str) -> None:
        self.acks.append((name, time.perf_counter()))
        if self.watching:
            self.fresh.put(name)


class _NotingIoCtx:
    """An IoCtx whose `write_full`s go into an _AckLog; everything
    else is the IoCtx's."""

    def __init__(self, io, log: _AckLog):
        self._io, self._log = io, log

    def write_full(self, name, data):
        try:
            out = self._io.write_full(name, data)
        except Exception as e:
            self._log.failures.append(
                (name, time.perf_counter(),
                 f"{type(e).__name__}: {e}"[:200],
                 _client_timeline(self._io, name)))
            raise
        self._log.acked(name)
        return out

    def __getattr__(self, attr):
        return getattr(self._io, attr)


def _client_timeline(io, name: str) -> list | None:
    """The client's own record of its last op on `name`: each event
    (seconds after the op began, name) and its result — what a failed
    write waited for.  None where the client keeps no such record."""
    try:
        ops = io.client.objecter.op_tracker.dump_historic_ops()["ops"]
    except Exception:  # noqa: BLE001 — a client without a tracker
        return None
    mine = [o for o in ops if f"/{name} " in o.get("description", "")]
    if not mine:
        return None
    op = mine[-1]
    events = [[round(e["ts"] - op["initiated_at"], 3), e["event"]]
              for e in op.get("events", [])]
    return events[-40:] + [["result", op.get("result")]]


class _NotingClient:
    def __init__(self, client, log: _AckLog):
        self._client, self._log = client, log

    def open_ioctx(self, pool):
        return _NotingIoCtx(self._client.open_ioctx(pool), self._log)

    def __getattr__(self, attr):
        return getattr(self._client, attr)


class _NotingDeployment:
    """What closed_loop_write.drive uses of a deployment — its client
    and pool — with the client's acks noted."""

    def __init__(self, dep, log: _AckLog):
        self.client = _NotingClient(dep.client, log)
        self.pool = dep.pool


# -- the schedule ------------------------------------------------------------

def _live_shard_names(dep, victim: int) -> set:
    """{(osd, shard, object name)} of every head shard object of the
    pool in the stores of the OSDs other than `victim`."""
    pool_id = dep.pool_id()
    out = set()
    for osd in dep.cluster.osds:
        if osd is None or osd.osd_id == victim:
            continue
        for cid in osd.store.list_collections():
            if cid.pgid.pool != pool_id:
                continue
            for goid in osd.store.list_objects(cid):
                if not goid.hobj.name.startswith("__"):
                    out.add((osd.osd_id, cid.shard, goid.hobj.name))
    return out


def _lacking_now(dep, victim: int, name: str) -> set:
    """{(object name, shard)} of the shards of `name` whose live holder
    does not hold them this moment (the head object in the shard's
    collection)."""
    from ceph_tpu.osd.ec_transaction import shard_oid
    from ceph_tpu.osd.types import hobject_t, spg_t
    osdmap = dep.cluster.mon.osdmap
    pool_id = osdmap.lookup_pool(dep.pool).id
    pgid = osdmap.object_to_pg(pool_id, name)
    head = hobject_t(pool=pool_id, name=name)
    osds = dep.cluster.osds
    out = set()
    for shard, osd_id in enumerate(
            osdmap.pg_to_up_acting_osds(pgid)[1]):
        if osd_id == victim or not 0 <= osd_id < len(osds) or \
                osds[osd_id] is None:
            continue
        if not osds[osd_id].store.exists(spg_t(pgid, shard),
                                         shard_oid(head, shard)):
            out.add((name, shard))
    return out


def _schedule(dep, traffic: dict, log: _AckLog, t_open: float,
              facts: dict) -> None:
    """Stop, the looks at the live stores, restart — each on the
    window's own clock."""
    victim = dep.config["failure"]["osd"]
    rs = traffic["restart"]
    base._sleep_until(t_open + rs["stop_s"])
    facts["t_stop"] = time.perf_counter()
    log.watching = True
    dep.cluster.kill_osd(victim)
    dep.cluster.mark_osd_down(victim)
    facts["t_down"] = time.perf_counter()
    facts["epoch_down"] = dep.cluster.mon.osdmap.epoch
    # the `ack` guarantee while the OSD is down: every object acked
    # since the stop has a shard on each of its live holders — looked
    # at as each ack comes, and once more for all of them at the end
    lacking: set = set()
    looked = 0
    t_restart = t_open + rs["restart_s"]
    while True:
        left = t_restart - time.perf_counter()
        try:
            name = log.fresh.get(timeout=left) if left > 0 \
                else log.fresh.get_nowait()
        except queue.Empty:
            if left <= 0:
                break
            continue
        lacking |= _lacking_now(dep, victim, name)
        looked += 1
    log.watching = False
    t0 = time.perf_counter()
    acked = [name for name, t in list(log.acks) if t >= facts["t_stop"]]
    have = _live_shard_names(dep, victim)
    live = {o.osd_id for o in dep.cluster.osds
            if o is not None and o.osd_id != victim}
    for name in acked:
        for shard, osd in enumerate(dep.acting(name)):
            if osd in live and (osd, shard, name) not in have:
                lacking.add((name, shard))
    facts["acked_while_down_checked"] = len(acked)
    facts["acked_while_down_looked_at_ack"] = looked
    facts["acked_while_down_live_shards_missing"] = len(lacking)
    facts["live_check_s"] = time.perf_counter() - t0
    facts["t_restart"] = time.perf_counter()
    dep.cluster.revive_osd(victim)
    facts["t_revived"] = time.perf_counter()


def drive(dep, traffic: dict, payloads: list[bytes], seconds: float,
          before_window=None, in_window=None) -> dict:
    """The recovery decode's shapes, then closed_loop_write's ramp,
    window and drain with the restart scheduled inside the window.
    Returns closed_loop_write's run record with the schedule's clock
    and facts under "restart"."""
    setup = {"recovery_decode_prewarm": _warm_recovery_decode(dep,
                                                              traffic)}
    log = _AckLog()
    facts: dict = {"victim": dep.config["failure"]["osd"],
                   "failures": log.failures}
    errors: list = []
    thread = []

    def scheduled() -> None:
        try:
            _schedule(dep, traffic, log, thread[1], facts)
        except Exception as e:  # noqa: BLE001 — a schedule that died
            errors.append(repr(e))     # makes the run not correct

    def hook(t_open: float, t_close: float) -> None:
        t = threading.Thread(target=scheduled, name="bench-restart")
        thread[:] = [t, t_open]
        t.start()
        if in_window is not None:
            in_window(t_open, t_close)

    run = base.drive(_NotingDeployment(dep, log), traffic, payloads,
                     seconds, before_window=before_window, in_window=hook)
    if thread:
        thread[0].join()
    facts["errors"] = errors
    run["restart"] = facts
    run["setup"] = setup
    return run


# -- the comparison that decides `correct` -----------------------------------

def _wait_clean(dep, timeout: float) -> tuple[bool, float, str]:
    t0 = time.perf_counter()
    try:
        dep.cluster.wait_active_clean(timeout=timeout)
    except Exception as e:  # noqa: BLE001 — a timeout is a finding
        return False, time.perf_counter() - t0, repr(e)[:300]
    return True, time.perf_counter() - t0, ""


def _draw(rng, pool: list, count: int) -> set:
    if count >= len(pool):
        return set(pool)
    return {int(n) for n in rng.choice(pool, size=count, replace=False)}


def verify(dep, traffic: dict, payloads: list[bytes], run: dict,
           seed: int, reference) -> dict:
    """Every number against its limit (all 0, all exact), after the
    cluster is active+clean again: the restart's own facts (the
    schedule ran; every object acked while the OSD was down had all
    its live shards at its ack and at the restart; none of those
    whose placement group holds the OSD lacks its shard there now),
    the read-back of every acked object, and the audit of the sample
    (module head)."""
    from deploy import ec_geometry
    facts = run["restart"]
    victim = facts["victim"]
    acked = [n for n, _, _, err in run["ops"] if err is None]
    failed = sum(1 for op in run["ops"] if op[3] is not None)
    size = traffic["object_bytes"]

    ok, clean_s, why = _wait_clean(
        dep, traffic["restart"]["clean_timeout_s"])
    recovered = dep.snapshot()
    t_stop = facts.get("t_stop", float("inf"))
    t_restart = facts.get("t_restart", float("-inf"))
    down = [n for n, _, t1, err in run["ops"]
            if err is None and t_stop <= t1 <= t_restart]
    # acked while the OSD was down AND placed on it: recovery's work
    rebuilt = [n for n in down
               if victim in dep.acting(object_name(traffic, n))]

    # read-back of every acknowledged object
    unreadable, differing = [], []

    def reader(part):
        io = dep.client.open_ioctx(dep.pool)
        for n in part:
            try:
                got = io.read(object_name(traffic, n), size)
            except Exception:  # noqa: BLE001 — counted
                unreadable.append(n)
                continue
            if got != object_data(payloads, n):
                differing.append(n)

    readers = traffic["readback"]["readers"]
    threads = [threading.Thread(target=reader, args=(acked[r::readers],))
               for r in range(readers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    readback_s = time.perf_counter() - t0

    # the audit: half the sample from recovery's work, the rest (first
    # and last acked always in) from every acked object
    t0 = time.perf_counter()
    rng = np.random.default_rng([seed, 0xC0FFEE])
    limit = traffic["audit"]["max_objects"]
    from_down = _draw(rng, rebuilt,
                      int(round(limit * traffic["audit"]["down_share"])))
    sample = set(from_down)
    if acked:
        sample.update((acked[0], acked[-1]))
    rest = [n for n in acked if n not in sample]
    sample |= _draw(rng, rest, max(0, limit - len(sample)))
    to_audit = sorted(sample)
    k, m, su = ec_geometry(dep.config)
    index = dep.store_index()
    missing = bytes_wrong = crcs_wrong = shards = 0
    for n in to_audit:
        name = object_name(traffic, n)
        want, want_crcs = reference.expected_shards(
            object_data(payloads, n), k, m, su)
        for shard, osd_id in enumerate(dep.acting(name)):
            shards += 1
            got = dep.read_shard(index, osd_id, shard, name)
            if got is None:
                missing += 1
                continue
            data, crcs, shard_size, logical = got
            if data.shape != want[shard].shape or \
                    not np.array_equal(data, want[shard]):
                bytes_wrong += 1
            if crcs != want_crcs or shard_size != want.shape[1] \
                    or logical != size:
                crcs_wrong += 1
    on_victim = index["objects"]
    victim_missing = 0
    for n in rebuilt:
        name = object_name(traffic, n)
        shard = dep.acting(name).index(victim)
        if name not in on_victim.get((victim, shard), {}):
            victim_missing += 1
    scheduled = "t_revived" in facts and not facts["errors"]
    compared = {
        "write_errors": [failed, 0],
        "restart_schedule_failed": [0 if scheduled else 1, 0],
        "active_clean_timeout": [0 if ok else 1, 0],
        "acked_while_down_live_shards_missing": [
            facts.get("acked_while_down_live_shards_missing", 0), 0],
        "restarted_osd_shards_missing": [victim_missing, 0],
        "readback_unreadable": [len(unreadable), 0],
        "readback_differing": [len(differing), 0],
        "audit_shards_missing": [missing, 0],
        "audit_shard_bytes_wrong": [bytes_wrong, 0],
        "audit_shard_crcs_wrong": [crcs_wrong, 0],
    }
    return {
        "compared": compared,
        "checked": {"acked": len(acked), "read_back": len(acked),
                    "acked_while_down": len(down),
                    "acked_while_down_on_victim": len(rebuilt),
                    "audited_objects": len(to_audit),
                    "audited_from_down": len(from_down),
                    "audited_shards": shards,
                    "victim": victim, "clean_s": clean_s,
                    "clean_blocked_by": why,
                    "looked_at_ack": facts.get(
                        "acked_while_down_looked_at_ack"),
                    "live_check_s": facts.get("live_check_s"),
                    "schedule_errors": facts["errors"],
                    # what each failed write raised, on the window's
                    # clock (the first 16)
                    "write_failures": [
                        [name, round(t - run["t_open"], 3), what, events]
                        for name, t, what, events
                        in facts["failures"][:16]],
                    "stop_at_s": facts.get("t_stop", 0.0) - run["t_open"],
                    "restart_at_s": facts.get("t_restart", 0.0)
                    - run["t_open"],
                    "revive_s": facts.get("t_revived", 0.0)
                    - facts.get("t_restart", 0.0)},
        # the third reading of the counters, at active+clean: the
        # recovery readers' (benchmark/metrics/recovery.py)
        "recovered_snapshot": recovered,
        "correct": bool(acked) and shards > 0 and bool(rebuilt)
        and all(v <= lim for v, lim in compared.values()),
        "attempted": len(run["ops"]), "failed": failed,
        "acked_bytes": len(acked) * size,
        "stored_bytes": index["stored_bytes"],
        "readback_s": readback_s,
        "audit_s": time.perf_counter() - t0,
    }
