"""Traffic generator `closed_loop_write`: the `rados bench write`
role.  N writers each `write_full` a fresh object, wait for the ack
and write the next.  The parameters come from a traffic file; nothing
here knows a cell's name.

Built so that a window's numbers repeat:

1. placement does not depend on the seed: object names are
   <prefix><N>, N counting up from 0 across all writers as `rados
   bench` names them, so every run loads the same PGs in the same
   order.  The seed makes the DATA: a pool of `payload_pool` payloads
   from numpy.random.default_rng([seed, j]), built during set-up;
   object N carries payload N mod pool with N in its first 8 bytes;
2. the writers start staggered over `stagger_s` and write the same
   traffic for `ramp_s` before the window opens; ramp writes are not
   counted (they are acknowledged writes, and verified like the rest);
3. the window opens and closes on the clock, not on a writer;
4. nothing else runs in the process inside the window: counters are
   read `counter_lead_s` before it opens and after it has closed and
   the writes in flight have finished; only a traced run starts and
   stops the profiler inside it.
"""

from __future__ import annotations

import itertools
import threading
import time

import numpy as np

END_TO_END = ("write_MBps", "write_p95_ms")


def launch_shapes(traffic: dict, config: dict) -> list[tuple]:
    """The fused launch shapes this traffic produces: n concurrent
    writes are n runs of one object's bytes per shard, and the launch
    queue's pow2 bucketing collapses 1..writers runs to these."""
    from deploy import ec_geometry
    from roofline import chunk_bytes
    k, _, su = ec_geometry(config)
    chunk = chunk_bytes(traffic["object_bytes"], k, su)
    counts, n = [], 1
    while n < traffic["writers"]:
        counts.append(n)
        n *= 2
    counts.append(traffic["writers"])
    return [(chunk,) * n for n in counts]


def make_payloads(traffic: dict, seed: int) -> list[bytes]:
    return [np.random.default_rng([seed, j]).integers(
        0, 256, traffic["object_bytes"], dtype=np.uint8).tobytes()
        for j in range(traffic["payload_pool"])]


def object_name(traffic: dict, n: int) -> str:
    return f"{traffic['object_prefix']}{n}"


def object_data(payloads: list[bytes], n: int) -> bytes:
    """Payload n mod pool with n stamped into its first 8 bytes."""
    base = payloads[n % len(payloads)]
    return b"".join((n.to_bytes(8, "little"), memoryview(base)[8:]))


def drive(dep, traffic: dict, payloads: list[bytes], seconds: float,
          before_window=None, in_window=None) -> dict:
    """Ramp, window and drain.  `before_window()` is called
    counter_lead_s before the window opens, `in_window(t_open,
    t_close)` right after it opened (a traced run profiles there);
    both run on the calling thread.  Returns the per-op records
    (n, t_start, t_ack, error name or None) and the window's clock."""
    writers = traffic["writers"]
    ioctxs = [dep.client.open_ioctx(dep.pool) for _ in range(writers)]
    numbers = itertools.count()
    records = [[] for _ in range(writers)]
    t_start = time.perf_counter() + 0.05
    t_open = t_start + traffic["ramp_s"]
    t_close = t_open + seconds

    def writer(w: int) -> None:
        io, mine = ioctxs[w], records[w]
        delay = t_start + traffic["stagger_s"] * w / writers \
            - time.perf_counter()
        if delay > 0:
            time.sleep(delay)
        while True:
            t0 = time.perf_counter()
            if t0 >= t_close:
                return
            n = next(numbers)
            err = None
            try:
                io.write_full(object_name(traffic, n),
                              object_data(payloads, n))
            except Exception as e:  # noqa: BLE001 — counted, by type
                err = type(e).__name__
            mine.append((n, t0, time.perf_counter(), err))

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
            "ops_per_writer": [len(rows) for rows in records]}


def _sleep_until(t: float) -> None:
    delay = t - time.perf_counter()
    if delay > 0:
        time.sleep(delay)


def end_to_end(traffic: dict, run: dict) -> dict:
    """The window's own numbers: every write acknowledged inside it,
    over its whole length."""
    from stats import percentile_nearest_rank, rate_per_s
    inside = [(t1 - t0) for _, t0, t1, err in run["ops"]
              if err is None and run["t_open"] <= t1 <= run["t_close"]]
    seconds = run["t_close"] - run["t_open"]
    acks = sorted(t1 for _, _, t1, err in run["ops"] if err is None)
    out = {"window_ops": len(inside),
           # facts for the reader of a noisy run, not metrics: acks in
           # each 3 s of the window, and the longest silence
           "acks_per_3s": np.histogram(
               acks, bins=np.arange(run["t_open"], run["t_close"] + 3.0,
                                    3.0))[0].tolist(),
           "longest_ack_gap_s": max(
               (b - a for a, b in zip(acks, acks[1:])
                if run["t_open"] <= b <= run["t_close"]), default=0.0)}
    out["ops_per_writer"] = run["ops_per_writer"]
    if inside:
        out["write_p50_ms"] = percentile_nearest_rank(inside, 0.5) * 1e3
        out["write_mean_ms"] = sum(inside) / len(inside) * 1e3
        out["write_MBps"] = rate_per_s(
            len(inside) * traffic["object_bytes"], seconds) / 1e6
        out["write_p95_ms"] = percentile_nearest_rank(inside, 0.95) * 1e3
    return out


def verify(dep, traffic: dict, payloads: list[bytes], run: dict,
           seed: int, reference) -> dict:
    """The numbers that decide `correct`, each against its limit:
    every acknowledged write read back through the client and compared
    with what was written; and, for a sample of the objects drawn from
    the seed (first and last always in it), all k+m shards as they lie
    in the stores — bytes and the crcs of every shard in every shard's
    hinfo — against the plain reference's encoding of the object."""
    from deploy import ec_geometry
    acked = [n for n, _, _, err in run["ops"] if err is None]
    failed = sum(1 for op in run["ops"] if op[3] is not None)
    rng = np.random.default_rng([seed, 0xC0FFEE])

    def sample(limit: int) -> list[int]:
        if not limit or limit >= len(acked):
            return list(acked)
        keep = {acked[0], acked[-1]}
        keep.update(int(n) for n in rng.choice(
            acked, size=limit - 2, replace=False))
        return sorted(keep)

    # read-back through the client
    to_read = sample(traffic["readback"]["max_objects"])
    size = traffic["object_bytes"]
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
    threads = [threading.Thread(target=reader,
                                args=(to_read[r::readers],))
               for r in range(readers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    readback_s = time.perf_counter() - t0

    # what the device produced, as it lies in the stores
    t0 = time.perf_counter()
    k, m, su = ec_geometry(dep.config)
    index = dep.store_index()
    to_audit = sample(traffic["audit"]["max_objects"])
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
    compared = {
        "write_errors": [failed, 0],
        "readback_unreadable": [len(unreadable), 0],
        "readback_differing": [len(differing), 0],
        "audit_shards_missing": [missing, 0],
        "audit_shard_bytes_wrong": [bytes_wrong, 0],
        "audit_shard_crcs_wrong": [crcs_wrong, 0],
    }
    return {
        "compared": compared,
        "checked": {"acked": len(acked), "read_back": len(to_read),
                    "audited_objects": len(to_audit),
                    "audited_shards": shards},
        "correct": bool(acked) and shards > 0
        and all(v <= lim for v, lim in compared.values()),
        "attempted": len(run["ops"]), "failed": failed,
        "acked_bytes": len(acked) * size,
        "stored_bytes": index["stored_bytes"],
        "readback_s": readback_s,
        "audit_s": time.perf_counter() - t0,
    }
