"""Per-host EC launch queue tests (ISSUE 12, docs/PIPELINE.md "Host
launch queue"): cross-PG continuous batching on the MeshService seam.

What must hold: runs from different PGs coalesce into ONE super-batch
launch (bit-identical results to per-PG launches), per-PG in-order
completion and flush-on-idle sync semantics survive, and failure is
contained — a sub-write or poison-launch failure aborts only the
owning PG's ops while co-batched PGs commit.
"""

import time

import numpy as np
import pytest

from ceph_tpu.ec import ErasureCodePluginRegistry
from ceph_tpu.osd.ec_backend import ECBackend, LocalShardBackend
from ceph_tpu.osd.ec_transaction import PGTransaction
from ceph_tpu.osd.ec_util import StripeInfo
from ceph_tpu.osd.types import eversion_t, hobject_t, pg_t
from ceph_tpu.parallel.launch_queue import (ECLaunchQueue,
                                            LaunchQueueError,
                                            codec_signature)
from ceph_tpu.store import MemStore

REG = ErasureCodePluginRegistry.instance()

# a window long enough that tests stay deterministic: the timer never
# fires on its own; launches happen via byte cap or flush-on-demand
WIN_NEVER = 60_000_000.0


def oid(name):
    return hobject_t(pool=1, name=name)


def make_backend(pg, queue, plugin="jerasure", k=4, m=2, chunk=64,
                 shards_cls=LocalShardBackend):
    codec = REG.factory(plugin, {"k": str(k), "m": str(m)})
    store = MemStore()
    store.mount()
    shards = shards_cls(store, pg_t(1, pg), k + m)
    return ECBackend(codec, StripeInfo(k * chunk, chunk), shards,
                     launch_queue=queue, perf_name=f"ec.1.{pg}")


def write_one(backend, name, payload, version=1):
    txn = PGTransaction()
    txn.write(oid(name), 0, payload)
    done = []
    backend.submit_transaction(txn, eversion_t(1, version),
                               lambda: done.append(1))
    return done


# -- coalescing --------------------------------------------------------------

@pytest.mark.parametrize("plugin", ["jerasure", "jax"])
def test_cross_pg_runs_coalesce_into_one_launch(plugin):
    """Two PGs' drains, one launch: the first finalize flushes the
    whole pending super-batch (both PGs), the second completes from
    the memoized batch — and both PGs' data reads back intact."""
    q = ECLaunchQueue(window_us=WIN_NEVER)
    a = make_backend(0, q, plugin)
    b = make_backend(1, q, plugin)
    rng = np.random.default_rng(2)
    pa = rng.integers(0, 256, 1000, dtype=np.uint8)
    pb = rng.integers(0, 256, 777, dtype=np.uint8)
    acks = []
    with a.pipeline(), b.pipeline():
        ta = PGTransaction()
        ta.write(oid("oa"), 0, pa)
        a.submit_transaction(ta, eversion_t(1, 1),
                             lambda: acks.append("a"))
        tb = PGTransaction()
        tb.write(oid("ob"), 0, pb)
        b.submit_transaction(tb, eversion_t(1, 1),
                             lambda: acks.append("b"))
    assert sorted(acks) == ["a", "b"]
    st = q.status()
    assert st["launches"] == 1
    assert st["cross_pg_launches"] == 1
    assert st["pg_mix_avg"] == 2.0
    assert st["pending_submissions"] == 0
    np.testing.assert_array_equal(a.read(oid("oa"), 0, 1000), pa)
    np.testing.assert_array_equal(b.read(oid("ob"), 0, 777), pb)


def test_cross_pg_fused_results_match_unbatched():
    """The demuxed super-batch results (parity on disk AND cumulative
    hinfo shard crcs) must be bit-identical to what each PG computes
    launching alone — including chained appends whose seeds fold
    across the shared launch."""
    q = ECLaunchQueue(window_us=WIN_NEVER)
    batched = [make_backend(i, q, "jax") for i in range(2)]
    # window 0: every submission is a launch of its own
    alone = ECLaunchQueue(window_us=0)
    solo = [make_backend(10 + i, alone, "jax") for i in range(2)]
    rng = np.random.default_rng(3)
    chunks = [rng.integers(0, 256, 512, dtype=np.uint8)
              for _ in range(4)]
    for group in (batched, solo):
        with group[0].pipeline(), group[1].pipeline():
            for v, payload in enumerate(chunks[:2]):
                txn = PGTransaction()
                txn.write(oid("x"), v * 512, payload)
                group[0].submit_transaction(txn, eversion_t(1, v + 1),
                                            lambda: None)
            txn = PGTransaction()
            txn.write(oid("y"), 0, chunks[2])
            group[1].submit_transaction(txn, eversion_t(1, 1),
                                        lambda: None)
    assert q.status()["launches"] >= 1
    assert alone.status()["launches"] == alone.status()["submissions"] == 3
    for bq, bs, name, ln in ((batched[0], solo[0], "x", 1024),
                             (batched[1], solo[1], "y", 512)):
        np.testing.assert_array_equal(bq.read(oid(name), 0, ln),
                                      bs.read(oid(name), 0, ln))
        hq = bq.shards.get_hinfo(0, oid(name))
        hs = bs.shards.get_hinfo(0, oid(name))
        assert hq.cumulative_shard_hashes == hs.cumulative_shard_hashes
        assert hq.total_chunk_size == hs.total_chunk_size


def test_lone_pg_flush_on_idle_stays_synchronous():
    """No pipeline window, nothing behind the op: submit_transaction
    must return with the op committed (the queue's flush-on-demand
    preserves the pre-queue sync contract for a lone PG)."""
    q = ECLaunchQueue(window_us=WIN_NEVER)
    backend = make_backend(0, q, "jax")
    p = (np.arange(512) % 256).astype(np.uint8)
    done = write_one(backend, "solo", p)
    assert done == [1], "lone op did not complete synchronously"
    assert q.status()["launches"] == 1
    np.testing.assert_array_equal(backend.read(oid("solo"), 0, 512), p)


def test_window_timer_launches_without_finalize():
    """An open dispatch window + a short batching window: the queue's
    timer must launch the pending super-batch in the background, not
    wait for a finalize that may be far away."""
    q = ECLaunchQueue(window_us=40_000.0)     # 40 ms
    backend = make_backend(0, q, "jerasure")
    acks = []
    with backend.pipeline():
        txn = PGTransaction()
        txn.write(oid("w"), 0, np.ones(512, dtype=np.uint8))
        op = backend.submit_transaction(txn, eversion_t(1, 1),
                                        lambda: acks.append(1))
        deadline = time.time() + 10.0
        while q.status()["launches"] < 1 and time.time() < deadline:
            time.sleep(0.01)
        assert q.status()["launches"] == 1, \
            "window timer did not launch the pending batch"
        assert acks == []                     # launched, NOT completed
        assert op.state != "done"
    assert acks == [1]


def test_byte_cap_launches_immediately():
    """Pending input bytes at/over the super-batch cap launch without
    waiting for the window (the occupancy ceiling)."""
    q = ECLaunchQueue(window_us=WIN_NEVER, max_bytes=1)
    backend = make_backend(0, q, "jerasure")
    with backend.pipeline():
        txn = PGTransaction()
        txn.write(oid("c"), 0, np.ones(512, dtype=np.uint8))
        backend.submit_transaction(txn, eversion_t(1, 1), lambda: None)
        assert q.status()["launches"] == 1
        assert q.status()["last_launch"]["occupancy_pct"] >= 100.0


# -- failure containment -----------------------------------------------------

class _FailingShards(LocalShardBackend):
    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.fail_on = None       # (oid_name, shard)

    def sub_write(self, shard, txn, on_commit, **kw):
        if self.fail_on is not None and shard == self.fail_on[1] and \
                any(self.fail_on[0] in str(g) for g in txn.ops):
            self.fail_on = None
            raise IOError("injected sub-write failure")
        return super().sub_write(shard, txn, on_commit, **kw)


def test_subwrite_failure_in_shared_batch_contained():
    """One PG's sub-write failure inside a SHARED super-batch aborts
    only that PG's op (error ack, pins released, zero extent-cache
    balance) while the co-batched PG commits."""
    q = ECLaunchQueue(window_us=WIN_NEVER)
    a = make_backend(0, q, "jerasure", shards_cls=_FailingShards)
    b = make_backend(1, q, "jerasure")
    a.shards.fail_on = ("fa", 5)
    rng = np.random.default_rng(5)
    pa = rng.integers(0, 256, 512, dtype=np.uint8)
    pb = rng.integers(0, 256, 512, dtype=np.uint8)
    ops = {}
    with a.pipeline(), b.pipeline():
        ta = PGTransaction()
        ta.write(oid("fa"), 0, pa)
        ops["a"] = a.submit_transaction(ta, eversion_t(1, 1),
                                        lambda: None)
        tb = PGTransaction()
        tb.write(oid("fb"), 0, pb)
        ops["b"] = b.submit_transaction(tb, eversion_t(1, 1),
                                        lambda: None)
    assert q.status()["launches"] == 1          # one shared launch
    assert ops["a"].state == "failed"
    assert ops["a"].error is not None
    assert ops["b"].state == "done" and ops["b"].error is None
    np.testing.assert_array_equal(b.read(oid("fb"), 0, 512), pb)
    for be in (a, b):
        assert len(be.extent_cache) == 0
        assert not be._projected
    # both pipelines keep serving
    assert write_one(a, "fa2", pa, 2) == [1]
    assert write_one(b, "fb2", pb, 2) == [1]


def test_poison_launch_fails_only_owner():
    """A submission whose plugin dies at launch poisons the combined
    launch; the queue's per-submission retry must fail ONLY the
    owner's ticket — the co-batched PG's runs launch on its own plugin
    and commit."""
    q = ECLaunchQueue(window_us=WIN_NEVER)
    a = make_backend(0, q, "jerasure")
    b = make_backend(1, q, "jerasure")

    def boom(_chunks):
        raise RuntimeError("injected launch failure")
    a.ec_impl.encode_chunks = boom              # poison A's plugin
    rng = np.random.default_rng(6)
    pa = rng.integers(0, 256, 512, dtype=np.uint8)
    pb = rng.integers(0, 256, 512, dtype=np.uint8)
    ops = {}
    with a.pipeline(), b.pipeline():            # A submits FIRST, so
        ta = PGTransaction()                    # the combined launch
        ta.write(oid("pa"), 0, pa)              # rides A's plugin
        ops["a"] = a.submit_transaction(ta, eversion_t(1, 1),
                                        lambda: None)
        tb = PGTransaction()
        tb.write(oid("pb"), 0, pb)
        ops["b"] = b.submit_transaction(tb, eversion_t(1, 1),
                                        lambda: None)
    st = q.status()
    assert st["launch_retries"] == 1
    assert st["launch_errors"] == 1
    assert ops["a"].state == "failed"
    assert isinstance(ops["a"].error, LaunchQueueError)
    assert ops["b"].state == "done" and ops["b"].error is None
    np.testing.assert_array_equal(b.read(oid("pb"), 0, 512), pb)
    assert len(a.extent_cache) == 0 and not a._projected
    assert not a._sim_chunk and not a._sim_refs


def test_finalize_failure_fails_batch_queue_survives():
    """A device finalize failure (the mesh-failure analog) fails every
    ticket of THAT batch — each backend aborts its own ops cleanly —
    and the queue keeps serving later launches."""
    q = ECLaunchQueue(window_us=WIN_NEVER)
    a = make_backend(0, q, "jax")
    b = make_backend(1, q, "jax")
    orig = a.ec_impl.encode_extents_with_crc_finalize
    armed = {"on": True}

    def failing(handle):
        if armed["on"]:
            armed["on"] = False
            raise RuntimeError("injected finalize failure")
        return orig(handle)
    # the combined batch finalizes through the FIRST submitter's plugin
    a.ec_impl.encode_extents_with_crc_finalize = failing
    rng = np.random.default_rng(7)
    pa = rng.integers(0, 256, 512, dtype=np.uint8)
    ops = {}
    with a.pipeline(), b.pipeline():
        ta = PGTransaction()
        ta.write(oid("za"), 0, pa)
        ops["a"] = a.submit_transaction(ta, eversion_t(1, 1),
                                        lambda: None)
        tb = PGTransaction()
        tb.write(oid("zb"), 0, pa)
        ops["b"] = b.submit_transaction(tb, eversion_t(1, 1),
                                        lambda: None)
    assert ops["a"].state == "failed" and ops["a"].error is not None
    assert ops["b"].state == "failed" and ops["b"].error is not None
    for be in (a, b):
        assert len(be.extent_cache) == 0
        assert not be._projected
        assert not be._sim_chunk and not be._sim_refs
    # the queue is not wedged: later writes launch and commit
    assert write_one(a, "za2", pa, 2) == [1]
    assert write_one(b, "zb2", pa, 2) == [1]
    np.testing.assert_array_equal(a.read(oid("za2"), 0, 512), pa)


def test_finalizer_steals_launch_past_blocked_worker():
    """A bound ticket's result() must not wait behind ANOTHER key's
    slow launch in the flush/window worker's sequential loop — the
    finalizer steals its own batch's still-unclaimed launch (one
    batch's multi-second compile stalls only that batch)."""
    import threading
    q = ECLaunchQueue(window_us=WIN_NEVER)
    slow = REG.factory("jerasure", {"k": "4", "m": "2"})
    fast = REG.factory("jerasure", {"k": "2", "m": "1"})
    entered, release, slow_done = (threading.Event() for _ in range(3))
    orig = slow.encode_chunks

    def blocking(chunks):
        entered.set()
        release.wait(10)
        slow_done.set()
        return orig(chunks)
    slow.encode_chunks = blocking
    slow_in = np.ones((4, 256), dtype=np.uint8)
    t_slow = q.submit_chunks(slow, slow_in)     # popped (and launched)
    big = (np.arange(2 * 256, dtype=np.uint32) % 251).astype(np.uint8)
    big = big.reshape(2, 256)
    t_fast = q.submit_chunks(fast, big)         # ...second
    flusher = threading.Thread(target=q.flush, daemon=True)
    flusher.start()
    assert entered.wait(5)      # worker is stuck inside slow's launch
    par = np.asarray(t_fast.result())
    assert not slow_done.is_set(), \
        "fast ticket's result waited behind the blocked worker"
    np.testing.assert_array_equal(
        par, np.asarray(fast.encode_chunks(big)))
    release.set()
    flusher.join(10)
    np.testing.assert_array_equal(np.asarray(t_slow.result()),
                                  np.asarray(orig(slow_in)))
    assert q.status()["launches"] == 2


def test_cancel_withdraws_pending_submission():
    q = ECLaunchQueue(window_us=WIN_NEVER)
    codec = REG.factory("jerasure", {"k": "4", "m": "2"})
    t = q.submit_chunks(codec, np.ones((4, 256), dtype=np.uint8))
    assert q.status()["pending_submissions"] == 1
    t.cancel()
    assert q.status()["pending_submissions"] == 0
    with pytest.raises(LaunchQueueError):
        t.result()
    assert q.status()["launches"] == 0


# -- observability -----------------------------------------------------------

def test_queue_counters_and_latency_histogram():
    q = ECLaunchQueue(window_us=WIN_NEVER, max_bytes=1 << 20)
    a = make_backend(0, q, "jerasure")
    b = make_backend(1, q, "jerasure")
    p = np.ones(512, dtype=np.uint8)
    with a.pipeline(), b.pipeline():
        for v in range(2):
            txn = PGTransaction()
            txn.write(oid(f"s{v}"), 0, p)
            a.submit_transaction(txn, eversion_t(1, v + 1),
                                 lambda: None)
        txn = PGTransaction()
        txn.write(oid("t"), 0, p)
        b.submit_transaction(txn, eversion_t(1, 1), lambda: None)
    st = q.status()
    assert st["launches"] >= 1
    assert st["coalesced_runs"] >= 3
    assert st["avg_runs_per_launch"] > 1.0
    assert 0 < st["occupancy_pct_avg"] <= 100.0
    dump = q.perf.dump()
    assert dump["ec_host_launches"] == st["launches"]
    assert dump["ec_host_launch_runs"] == st["coalesced_runs"]
    lat = q.perf.dump_latencies()
    assert lat["lat_ec_batch_wait"]["count"] == st["submissions"]
    # the owning backends attribute their routed drains
    assert a.perf.dump()["ec_host_queue_drains"] >= 2
    assert b.perf.dump()["ec_host_queue_drains"] >= 1


def test_codec_signature_batches_only_provable_twins():
    j1 = REG.factory("jerasure", {"k": "4", "m": "2"})
    j2 = REG.factory("jerasure", {"k": "4", "m": "2"})
    j3 = REG.factory("jerasure", {"k": "6", "m": "2"})
    assert codec_signature(j1) == codec_signature(j2)
    assert codec_signature(j1) != codec_signature(j3)
    x1 = REG.factory("jax", {"k": "4", "m": "2"})
    x2 = REG.factory("jax", {"k": "4", "m": "2"})
    assert codec_signature(x1) == codec_signature(x2)
    # plugin-typed: jax never coalesces with a CPU plugin even at
    # equal geometry (launch capabilities differ within a batch)
    assert codec_signature(x1) != codec_signature(j1)
    # a minimal-density technique encodes via bitmatrix packets (its
    # matrix stays None) — instance identity only, never cross-instance
    l1 = REG.factory("jerasure", {"k": "4", "m": "2",
                                  "technique": "liberation"})
    l2 = REG.factory("jerasure", {"k": "4", "m": "2",
                                  "technique": "liberation"})
    assert codec_signature(l1) != codec_signature(l2)
    assert codec_signature(l1) == codec_signature(l1)
    # exposing a matrix is not proof the encode uses it: without an
    # explicit matrix_determines_encode declaration the fallback
    # refuses to batch across instances
    class MatNoDecl:
        matrix = j1.matrix
        def get_data_chunk_count(self): return 4
        def get_coding_chunk_count(self): return 2
    assert codec_signature(MatNoDecl()) != codec_signature(MatNoDecl())


# -- mixed-width split (ops/bitsliced.py) ------------------------------------

def test_mixed_width_batch_keeps_hier_kernel_interpret():
    """A cross-PG super-batch mixing a hier-eligible run with a small
    one must split into two launches (big runs keep the headline
    kernel) and demux back bit-exact — not demote everything to the
    flat tile."""
    import jax.numpy as jnp

    from ceph_tpu.common import crc32c as C
    from ceph_tpu.ec import gf
    from ceph_tpu.ops import bitsliced as bs
    from ceph_tpu.ops import crc32c_linear as cl
    k, m = 4, 2
    tile, wb = 4096, 128
    mat = gf.cauchy_rs_matrix(k, m)[k:]
    bitmat = jnp.asarray(bs.interleave_bitmatrix(mat), dtype=jnp.int8)
    bitmat32 = jnp.asarray(bs._w32_bitmat(mat), dtype=jnp.int8)
    rng = np.random.default_rng(21)
    widths = [tile * 2, 600, tile + 513]
    runs = [rng.integers(0, 256, (k, w), dtype=np.uint8)
            for w in widths]
    handle = bs.gf_encode_extents_with_crc_submit(
        bitmat, bitmat32, runs, m, use_w32=True, force_xla=False,
        interpret=True, tile=tile, wb=wb, combine="kernel")
    assert "split" in handle
    assert handle["path"].startswith("hier_acc")
    results = bs.gf_encode_extents_with_crc_finalize(handle)
    assert len(results) == len(runs)
    for run, (par, l, tail, body) in zip(runs, results):
        np.testing.assert_array_equal(
            np.asarray(par), gf.gf_matvec(mat, run))
        allsh = np.concatenate([run, np.asarray(par)], axis=0)
        for s in range(k + m):
            got = cl.fold_run_crc(int(l[s]), body, 0xFFFFFFFF,
                                  tail[s].tobytes())
            assert got == C.crc32c(allsh[s].tobytes(), 0xFFFFFFFF), \
                f"shard {s}"


# -- the one route -----------------------------------------------------------

class _DownShards(LocalShardBackend):
    """Shards in `down` fail their reads at once (a known-down holder)."""
    down: frozenset = frozenset()

    def sub_read(self, shard, oid, off, length, on_done):
        if shard in self.down:
            on_done(shard, None)
            return
        super().sub_read(shard, oid, off, length, on_done)


@pytest.mark.parametrize("plugin,k,m,append,degraded,recover", [
    ("jax", 2, 1, "x", "d", "d"),          # jitted, fused appends
    ("jerasure", 2, 1, "c", "d", "d"),     # host-synchronous
    # sub-chunked: a degraded read is the codec's own per-object
    # decode (no launch to route); one lost shard is a repair plan
    ("clay", 4, 2, "c", None, "r"),
])
def test_backend_without_queue_argument_launches_through_host_queue(
        plugin, k, m, append, degraded, recover):
    """The ONE route from an EC op to the device: a backend built with
    no `launch_queue` argument holds the host's queue, and its append,
    overwrite, degraded read, degraded overwrite and recovery each show
    up there as a launch of the expected kind — a direct plugin call creeping back
    into ECBackend leaves a count where it was and fails here."""
    from ceph_tpu.osd.ec_transaction import shard_oid
    from ceph_tpu.store.object_store import Transaction
    chunk = 1024
    be = make_backend(0, None, plugin, k=k, m=m, chunk=chunk,
                      shards_cls=_DownShards)
    host = ECLaunchQueue.host_instance()
    assert be._launch_queue is host

    def counts():
        st = host.status()
        enc = st["launches"] - st["decode_launches"] \
            - st["repair_launches"]
        drains = be.perf.dump()
        return {"x": drains["ec_fused_kernel_drains"]
                + drains["ec_fused_fallback_drains"],
                "c": drains["ec_plain_drains"], "enc": enc,
                "d": st["decode_launches"], "r": st["repair_launches"]}

    def rose(before, kind):
        after = counts()
        assert after[kind] > before[kind], (kind, before, after)
        if kind in "xc":
            assert after["enc"] > before["enc"], (before, after)

    rng = np.random.default_rng(30)
    payload = rng.integers(0, 256, k * chunk, dtype=np.uint8)
    o = oid("route")
    c0 = counts()
    assert write_one(be, "route", payload) == [1]          # append
    rose(c0, append)
    c0 = counts()
    patch = rng.integers(0, 256, 30, dtype=np.uint8)
    txn = PGTransaction()
    txn.write(o, 10, patch)                                # overwrite
    done = []
    be.submit_transaction(txn, eversion_t(1, 2), lambda: done.append(1))
    assert done == [1]
    rose(c0, "c")
    payload[10:40] = patch
    c0 = counts()
    be.shards.down = frozenset({0})                        # degraded read
    np.testing.assert_array_equal(be.read(o, 0, payload.size), payload)
    if degraded is not None:
        rose(c0, degraded)
    c0 = counts()
    patch = rng.integers(0, 256, 30, dtype=np.uint8)
    txn = PGTransaction()
    txn.write(o, 100, patch)           # degraded overwrite: its pre-read
    be.submit_transaction(txn, eversion_t(1, 3), lambda: done.append(2))
    assert done == [1, 2]
    if degraded is not None:
        rose(c0, degraded)
    rose(c0, "c")
    payload[100:130] = patch
    be.shards.down = frozenset()
    np.testing.assert_array_equal(be.read(o, 0, payload.size), payload)
    goid = shard_oid(o, 1)                                 # recovery
    cid = be.shards.cids[1]
    lost = be.shards.store.read(cid, goid).copy()
    t = Transaction()
    t.remove(goid)
    be.shards.store.queue_transactions(cid, [t])
    c0 = counts()
    pushed = {}
    be.recover_shard(o, [1], lambda s, data, h: pushed.__setitem__(
        s, np.asarray(data).copy()))
    rose(c0, recover)
    np.testing.assert_array_equal(pushed[1], lost)


# -- deployment wiring -------------------------------------------------------

def test_cluster_default_wiring_and_asok(tmp_path):
    """Every EC PG of every OSD in the host process routes drains
    through ONE queue, `launch queue
    status` (asok, incl. the ceph_cli three-word fold) surfaces the
    occupancy counters, and lat_ec_batch_wait reaches
    dump_latencies."""
    from ceph_tpu.parallel.launch_queue import ECLaunchQueue
    from ceph_tpu.tools.vstart import Cluster
    ECLaunchQueue.reset_host()
    with Cluster(n_osds=4, asok_dir=str(tmp_path)) as c:
        client = c.client()
        client.set_ec_profile("lq21", {
            "plugin": "jerasure", "k": "2", "m": "1",
            "stripe_unit": "1024"})
        client.create_pool("lqpool", "erasure",
                           erasure_code_profile="lq21", pg_num=4)
        io = client.open_ioctx("lqpool")
        for i in range(6):
            io.write_full(f"q{i}", bytes([i + 1]) * 3000)
        for i in range(6):
            assert io.read(f"q{i}", 3000) == bytes([i + 1]) * 3000
        queue = ECLaunchQueue.host_get()
        assert queue is not None
        assert queue.status()["launches"] >= 1
        sts = [osd._asok_launch_queue_status({}) for osd in c.osds]
        assert all(st["queue"]["launches"] >= 1 for st in sts)
        assert any(sum(st["pg_queue_drains"].values()) > 0
                   for st in sts)
        # the queue's perf set (incl. the wait histogram) registers
        # into exactly ONE daemon's collection per host — every
        # daemon re-exporting the shared singleton would make
        # sum-across-daemons read n_daemons x the real counts
        with_set = [osd for osd in c.osds
                    if "ec_host_queue" in osd.cct.perf.dump_latencies()]
        assert len(with_set) == 1
        lat = with_set[0].cct.perf.dump_latencies()
        assert "lat_ec_batch_wait" in lat["ec_host_queue"]
        # ceph_cli daemon mode folds the three-word prefix
        from ceph_tpu.tools import ceph_cli
        rc = ceph_cli.daemon_command(
            [c.osds[0].cct.asok.path, "launch", "queue", "status"])
        assert rc == 0
