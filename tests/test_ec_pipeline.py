"""EC storage pipeline tests.

Reference analogs: src/test/osd/TestECBackend.cc (stripe math),
src/test/osd/test_ec_transaction.cc (WritePlan extents), plus pipeline
end-to-end on MemStore (standalone-test role, no cluster).
"""

import numpy as np
import pytest

from ceph_tpu.ec import ErasureCodePluginRegistry
from ceph_tpu.osd import ec_transaction as ect
from ceph_tpu.osd import ec_util
from ceph_tpu.osd.ec_backend import ECBackend, LocalShardBackend
from ceph_tpu.osd.ec_transaction import PGTransaction
from ceph_tpu.osd.ec_util import HashInfo, StripeInfo
from ceph_tpu.osd.types import eversion_t, hobject_t, pg_t
from ceph_tpu.store import MemStore

REG = ErasureCodePluginRegistry.instance()


def make_backend(k=4, m=2, chunk=64, plugin="jerasure", queue=None):
    codec = REG.factory(plugin, {"k": str(k), "m": str(m)})
    sinfo = StripeInfo(stripe_width=k * chunk, chunk_size=chunk)
    store = MemStore()
    store.mount()
    shards = LocalShardBackend(store, pg_t(1, 0), k + m)
    return ECBackend(codec, sinfo, shards, launch_queue=queue), store


def oid(name):
    return hobject_t(pool=1, name=name)


# -- stripe math (reference TestECBackend.cc:22) ----------------------------

def test_stripe_info_math():
    s = StripeInfo(stripe_width=4096, chunk_size=1024)
    assert s.k == 4
    assert s.logical_to_prev_stripe_offset(5000) == 4096
    assert s.logical_to_next_stripe_offset(5000) == 8192
    assert s.logical_to_prev_chunk_offset(5000) == 1024
    assert s.logical_to_next_chunk_offset(5000) == 2048
    assert s.aligned_logical_offset_to_chunk_offset(8192) == 2048
    assert s.aligned_chunk_offset_to_logical_offset(2048) == 8192
    assert s.offset_len_to_stripe_bounds(5000, 100) == (4096, 4096)
    assert s.offset_len_to_stripe_bounds(4095, 2) == (0, 8192)


# -- write plan (reference test_ec_transaction.cc:29-85) --------------------

def plan_for(writes, size=0, k=4, chunk=64):
    sinfo = StripeInfo(k * chunk, chunk)
    txn = PGTransaction()
    o = oid("x")
    for off, ln in writes:
        txn.write(o, off, np.zeros(ln, dtype=np.uint8))
    return ect.get_write_plan(
        sinfo, txn, lambda _: HashInfo.make(6), lambda _: size), o, sinfo


def test_plan_aligned_append_no_reads():
    plan, o, s = plan_for([(0, 256)])
    assert plan.to_read == {}
    assert plan.will_write[o] == [ect.Extent(0, 256)]


def test_plan_partial_write_rounds_to_stripe():
    plan, o, s = plan_for([(10, 20)])
    assert plan.will_write[o] == [ect.Extent(0, 256)]
    assert plan.to_read == {}  # no existing data -> nothing to read


def test_plan_partial_overwrite_reads_stripe():
    plan, o, s = plan_for([(10, 20)], size=512)
    assert plan.will_write[o] == [ect.Extent(0, 256)]
    assert plan.to_read[o] == [ect.Extent(0, 256)]


def test_plan_separated_writes_merge_and_read():
    # two writes in distinct stripes of an existing object
    plan, o, s = plan_for([(0, 10), (600, 10)], size=1024)
    assert plan.will_write[o] == [ect.Extent(0, 256), ect.Extent(512, 256)]
    assert plan.to_read[o] == [ect.Extent(0, 256), ect.Extent(512, 256)]


def test_plan_tail_partial_stripe():
    # write covering stripe 0 fully and stripe 1 partially, object larger
    plan, o, s = plan_for([(0, 300)], size=1024)
    assert plan.will_write[o] == [ect.Extent(0, 512)]
    assert plan.to_read[o] == [ect.Extent(256, 256)]


# -- pipeline end-to-end -----------------------------------------------------

def commit(backend, txn, version):
    done = []
    backend.submit_transaction(txn, eversion_t(1, version), lambda: done.append(1))
    assert done == [1], "commit did not complete synchronously on MemStore"


def test_write_read_roundtrip():
    backend, _ = make_backend()
    o = oid("obj1")
    rng = np.random.default_rng(0)
    payload = rng.integers(0, 256, 1000, dtype=np.uint8)
    txn = PGTransaction()
    txn.write(o, 0, payload)
    commit(backend, txn, 1)
    got = backend.read(o, 0, 1000)
    np.testing.assert_array_equal(got, payload)


def test_rmw_partial_overwrite():
    backend, _ = make_backend()
    o = oid("obj2")
    base = np.arange(512, dtype=np.uint8) % 251
    txn = PGTransaction()
    txn.write(o, 0, base)
    commit(backend, txn, 1)
    # partial overwrite inside stripe 1 triggers RMW pre-read
    patch = np.full(30, 0xAB, dtype=np.uint8)
    txn2 = PGTransaction()
    txn2.write(o, 300, patch)
    commit(backend, txn2, 2)
    expect = base.copy()
    expect[300:330] = patch
    np.testing.assert_array_equal(backend.read(o, 0, 512), expect)


def test_unaligned_read():
    backend, _ = make_backend()
    o = oid("obj3")
    payload = ((np.arange(700) * 7) % 256).astype(np.uint8)
    txn = PGTransaction()
    txn.write(o, 0, payload)
    commit(backend, txn, 1)
    got = backend.read(o, 123, 400)
    np.testing.assert_array_equal(got, payload[123:523])


def test_batched_launch_coalesces_ops():
    """Several ops submitted while reads stall encode in one launch."""
    backend, _ = make_backend()
    ops = []
    with backend.batch():
        for i in range(6):
            txn = PGTransaction()
            txn.write(oid(f"b{i}"), 0,
                      np.full(256, i, dtype=np.uint8))
            op = backend.submit_transaction(
                txn, eversion_t(1, i + 1), lambda: None)
            ops.append(op)
    assert backend.completed == 6
    # all six extents coalesced into ONE codec launch
    assert backend.batched_extents == 6
    assert backend.batched_launches == 1
    # and the data still reads back correctly
    for i in range(6):
        got = backend.read(oid(f"b{i}"), 0, 256)
        np.testing.assert_array_equal(got, np.full(256, i, dtype=np.uint8))


def test_shard_contents_match_codec():
    """What lands in each shard store is exactly the codec's output."""
    backend, store = make_backend(k=4, m=2, chunk=64)
    o = oid("obj4")
    rng = np.random.default_rng(1)
    payload = rng.integers(0, 256, 512, dtype=np.uint8)
    txn = PGTransaction()
    txn.write(o, 0, payload)
    commit(backend, txn, 1)
    shards = ec_util.encode(backend.sinfo, backend.ec_impl, payload)
    for s in range(6):
        got = store.read(backend.shards.cids[s],
                         ect.shard_oid(o, s))
        np.testing.assert_array_equal(got, shards[s], err_msg=f"shard {s}")


def test_hinfo_crc_written_and_valid():
    from ceph_tpu.common import crc32c as C
    backend, store = make_backend()
    o = oid("obj5")
    payload = np.arange(512, dtype=np.uint8).astype(np.uint8)
    txn = PGTransaction()
    txn.write(o, 0, payload)
    commit(backend, txn, 1)
    hinfo = backend.shards.get_hinfo(0, o)
    assert hinfo.total_chunk_size == 128
    shards = ec_util.encode(backend.sinfo, backend.ec_impl, payload)
    for s in range(6):
        assert hinfo.get_chunk_hash(s) == C.crc32c(
            shards[s].tobytes(), 0xFFFFFFFF)


def test_recovery_rebuilds_lost_shards():
    backend, store = make_backend()
    o = oid("obj6")
    rng = np.random.default_rng(2)
    payload = rng.integers(0, 256, 1024, dtype=np.uint8)
    txn = PGTransaction()
    txn.write(o, 0, payload)
    commit(backend, txn, 1)
    # lose shards 1 and 4
    ref = {}
    for s in (1, 4):
        cid = backend.shards.cids[s]
        goid = ect.shard_oid(o, s)
        ref[s] = store.read(cid, goid).copy()
        t = __import__("ceph_tpu.store.object_store",
                       fromlist=["Transaction"]).Transaction()
        t.remove(goid)
        store.queue_transactions(cid, [t])
    pushed = {}
    backend.recover_shard(o, [1, 4],
                          lambda s, data, hinfo: pushed.__setitem__(s, data))
    for s in (1, 4):
        np.testing.assert_array_equal(pushed[s], ref[s])


def test_recovery_crc_detects_corruption():
    from ceph_tpu.ec.interface import ErasureCodeError
    backend, store = make_backend()
    o = oid("obj7")
    payload = np.zeros(1024, dtype=np.uint8)
    txn = PGTransaction()
    txn.write(o, 0, payload)
    commit(backend, txn, 1)
    # corrupt shard 2 silently, then try to "recover" shard 1 from it
    cid = backend.shards.cids[2]
    goid = ect.shard_oid(o, 2)
    t = __import__("ceph_tpu.store.object_store",
                   fromlist=["Transaction"]).Transaction()
    t.write(goid, 0, np.full(10, 0xEE, dtype=np.uint8))
    store.queue_transactions(cid, [t])
    cid1 = backend.shards.cids[1]
    t2 = __import__("ceph_tpu.store.object_store",
                    fromlist=["Transaction"]).Transaction()
    t2.remove(ect.shard_oid(o, 1))
    store.queue_transactions(cid1, [t2])
    with pytest.raises(ErasureCodeError):
        backend.recover_shard(o, [1], lambda *a: None)


def test_delete_and_truncate():
    backend, store = make_backend()
    o = oid("obj8")
    txn = PGTransaction()
    txn.write(o, 0, np.ones(512, dtype=np.uint8))
    commit(backend, txn, 1)
    t2 = PGTransaction()
    t2.truncate(o, 256)
    commit(backend, t2, 2)
    assert backend._get_size(o) == 256
    t3 = PGTransaction()
    t3.delete(o)
    commit(backend, t3, 3)
    assert backend._get_size(o) == 0


def test_pipeline_with_jax_codec():
    """The whole pipeline through the TPU (XLA-on-CPU here) codec."""
    backend, _ = make_backend(plugin="jax")
    o = oid("objj")
    rng = np.random.default_rng(3)
    payload = rng.integers(0, 256, 2048, dtype=np.uint8)
    txn = PGTransaction()
    txn.write(o, 0, payload)
    commit(backend, txn, 1)
    np.testing.assert_array_equal(backend.read(o, 0, 2048), payload)
    patch = rng.integers(0, 256, 100, dtype=np.uint8)
    t2 = PGTransaction()
    t2.write(o, 1000, patch)
    commit(backend, t2, 2)
    expect = payload.copy()
    expect[1000:1100] = patch
    np.testing.assert_array_equal(backend.read(o, 0, 2048), expect)


def test_pg_log_rollback_bounds():
    from ceph_tpu.osd.pg_log import PGLog, LogEntry, LogOp
    log = PGLog()
    for v in range(1, 6):
        log.add(LogEntry(eversion_t(1, v), oid("x")))
    log.roll_forward_to(eversion_t(1, 3))
    assert log.rollforward_to == eversion_t(1, 3)
    undone = log.rollback_to(eversion_t(1, 3))
    assert [e.version.version for e in undone] == [5, 4]
    assert log.head == eversion_t(1, 3)
    with pytest.raises(AssertionError):
        log.rollback_to(eversion_t(1, 2))


def test_fused_crc_pipeline_matches_host_crc():
    """jax-codec pipeline uses the fused parity+crc launch for appends;
    resulting hinfo must equal the host-computed crc convention."""
    from ceph_tpu.common import crc32c as C
    backend, store = make_backend(plugin="jax")
    o = oid("objfused")
    rng = np.random.default_rng(7)
    p1 = rng.integers(0, 256, 512, dtype=np.uint8)
    txn = PGTransaction()
    txn.write(o, 0, p1)
    commit(backend, txn, 1)
    # second append continues the cumulative crc with fused seeds
    p2 = rng.integers(0, 256, 256, dtype=np.uint8)
    t2 = PGTransaction()
    t2.write(o, 512, p2)
    commit(backend, t2, 2)
    hinfo = backend.shards.get_hinfo(0, o)
    whole = np.concatenate([p1, p2])
    shards = ec_util.encode(backend.sinfo, backend.ec_impl, whole)
    for s in range(6):
        want = C.crc32c(shards[s].tobytes(), 0xFFFFFFFF)
        assert hinfo.get_chunk_hash(s) == want, f"shard {s}"
    np.testing.assert_array_equal(backend.read(o, 0, 768), whole)
    # kernel-path provenance (ISSUE 11): fused drains ran, and the
    # backend attributed them — on this CPU run the submit resolves to
    # the XLA twin, counted as a fallback (hier counters stay 0)
    assert backend.fused_path == "xla"
    perf = backend.perf.dump()
    assert perf["ec_fused_fallback_drains"] >= 2
    assert perf["ec_fused_kernel_drains"] == 0


def test_fused_crc_covers_batched_multi_op_drain():
    """Round-1 Weak #1 fix: a batched MULTI-op drain (several objects +
    chained same-object appends) must still run through the fused
    parity+crc launch — one launch, correct chained hinfo crcs."""
    from ceph_tpu.common import crc32c as C
    backend, _ = make_backend(plugin="jax")
    o1, o2 = oid("fmulti1"), oid("fmulti2")
    rng = np.random.default_rng(23)
    pa = rng.integers(0, 256, 512, dtype=np.uint8)
    pb = rng.integers(0, 256, 256, dtype=np.uint8)
    pc = rng.integers(0, 256, 384, dtype=np.uint8)
    with backend.batch():
        t1 = PGTransaction()
        t1.write(o1, 0, pa)
        backend.submit_transaction(t1, eversion_t(1, 1), lambda: None)
        t2 = PGTransaction()                      # chained append on o1
        t2.write(o1, 512, pb)
        backend.submit_transaction(t2, eversion_t(1, 2), lambda: None)
        t3 = PGTransaction()                      # second object
        t3.write(o2, 0, pc)
        backend.submit_transaction(t3, eversion_t(1, 3), lambda: None)
    # all three extents were appends -> one fused launch, no plain pass
    assert backend.batched_extents == 3
    assert backend.batched_launches == 1
    whole1 = np.concatenate([pa, pb])
    np.testing.assert_array_equal(backend.read(o1, 0, 768), whole1)
    np.testing.assert_array_equal(backend.read(o2, 0, 384), pc)
    pc_padded = np.concatenate(          # pipeline pads partial stripes
        [pc, np.zeros(512 - 384, dtype=np.uint8)])
    for o, data in ((o1, whole1), (o2, pc_padded)):
        hinfo = backend.shards.get_hinfo(0, o)
        shards = ec_util.encode(backend.sinfo, backend.ec_impl, data)
        for s in range(6):
            assert hinfo.get_chunk_hash(s) == C.crc32c(
                shards[s].tobytes(), 0xFFFFFFFF), f"{o} shard {s}"


def test_batched_overlapping_writes_same_object():
    """Two ops on the same object in one batch window: the second must
    see the first's bytes (ExtentCache + projected hinfo chaining,
    reference ExtentCache reserve/present + projected sizes)."""
    backend, _ = make_backend()
    o = oid("overlap")
    rng = np.random.default_rng(20)
    base = rng.integers(0, 256, 512, dtype=np.uint8)
    patch = rng.integers(0, 256, 40, dtype=np.uint8)
    acks = []
    with backend.batch():
        t1 = PGTransaction()
        t1.write(o, 0, base)
        backend.submit_transaction(t1, eversion_t(1, 1),
                                   lambda: acks.append(1))
        # partial-stripe overwrite of data written by t1, same window
        t2 = PGTransaction()
        t2.write(o, 100, patch)
        backend.submit_transaction(t2, eversion_t(1, 2),
                                   lambda: acks.append(2))
    assert acks == [1, 2]
    expect = base.copy()
    expect[100:140] = patch
    np.testing.assert_array_equal(backend.read(o, 0, 512), expect)
    assert len(backend.extent_cache) == 0      # all released
    assert not backend._projected


# -- dispatch-ahead pipeline (docs/PIPELINE.md) ------------------------------

def test_pipeline_window_acks_in_submit_order():
    """depth=2 window: drains pile up on the device (observed in-flight
    hits the cap), completion stays in submit order, and the window
    exit flushes everything — extent cache and projections drain to
    zero."""
    backend, _ = make_backend()
    assert backend.dispatch_depth == 2
    acks = []
    seen_depth = 0
    rng = np.random.default_rng(30)
    payloads = [rng.integers(0, 256, 512, dtype=np.uint8)
                for _ in range(5)]
    with backend.pipeline():
        for i, p in enumerate(payloads):
            txn = PGTransaction()
            txn.write(oid(f"pw{i}"), 0, p)
            backend.submit_transaction(txn, eversion_t(1, i + 1),
                                       lambda i=i: acks.append(i))
            seen_depth = max(seen_depth, len(backend._inflight))
        assert backend._inflight          # still in flight mid-window
    assert seen_depth == 2                # the cap was reached and held
    assert acks == [0, 1, 2, 3, 4]        # submit order
    assert not backend._inflight
    for i, p in enumerate(payloads):
        np.testing.assert_array_equal(backend.read(oid(f"pw{i}"), 0, 512), p)
    assert len(backend.extent_cache) == 0
    assert not backend._projected
    assert not backend._sim_chunk and not backend._sim_refs


def test_pipeline_overlapping_writes_same_object():
    """Overlapping writes to ONE object across in-flight drains: the
    second op's assembly must see the first's pinned (uncommitted)
    bytes, acks stay in submit order, and everything releases."""
    backend, _ = make_backend()
    o = oid("pover")
    rng = np.random.default_rng(31)
    base = rng.integers(0, 256, 512, dtype=np.uint8)
    patch = rng.integers(0, 256, 40, dtype=np.uint8)
    acks = []
    with backend.pipeline():
        t1 = PGTransaction()
        t1.write(o, 0, base)
        backend.submit_transaction(t1, eversion_t(1, 1),
                                   lambda: acks.append(1))
        # drain 1 is STILL in flight when this assembles
        assert backend._inflight
        t2 = PGTransaction()
        t2.write(o, 100, patch)
        backend.submit_transaction(t2, eversion_t(1, 2),
                                   lambda: acks.append(2))
    assert acks == [1, 2]
    expect = base.copy()
    expect[100:140] = patch
    np.testing.assert_array_equal(backend.read(o, 0, 512), expect)
    assert len(backend.extent_cache) == 0
    assert not backend._projected


def test_pipeline_appends_chain_hinfo_across_inflight_drains():
    """Chained appends in separate in-flight drains (fused jax path):
    the cumulative crc chain must match the host convention even
    though drain N+1 launches before drain N materializes."""
    from ceph_tpu.common import crc32c as C
    backend, _ = make_backend(plugin="jax")
    o = oid("pchain")
    rng = np.random.default_rng(32)
    parts = [rng.integers(0, 256, 256, dtype=np.uint8)
             for _ in range(3)]
    with backend.pipeline():
        for i, p in enumerate(parts):
            txn = PGTransaction()
            txn.write(o, 256 * i, p)
            backend.submit_transaction(txn, eversion_t(1, i + 1),
                                       lambda: None)
    whole = np.concatenate(parts)
    np.testing.assert_array_equal(backend.read(o, 0, 768), whole)
    hinfo = backend.shards.get_hinfo(0, o)
    shards = ec_util.encode(backend.sinfo, backend.ec_impl, whole)
    for s in range(6):
        assert hinfo.get_chunk_hash(s) == C.crc32c(
            shards[s].tobytes(), 0xFFFFFFFF), f"shard {s}"
    assert len(backend.extent_cache) == 0
    assert not backend._sim_chunk


class _FailingShards(LocalShardBackend):
    """Raises on the sub-write of one (object, shard) once."""

    def __init__(self, *a, **kw):
        super().__init__(*a, **kw)
        self.fail_on = None     # (oid_name, shard)

    def sub_write(self, shard, txn, on_commit, **kw):
        if self.fail_on is not None and shard == self.fail_on[1] and \
                any(self.fail_on[0] in str(g) for g in txn.ops):
            self.fail_on = None
            raise IOError("injected sub-write failure")
        return super().sub_write(shard, txn, on_commit, **kw)


def test_pipeline_subwrite_failure_drains_cleanly():
    """A mid-pipeline sub-write failure must not wedge the queues: the
    failed op acks with its error attached, later ops commit, and the
    extent cache / projections return to zero (failed ops release
    their pins — stale assembled bytes must never satisfy a later
    drain)."""
    codec = REG.factory("jerasure", {"k": "4", "m": "2"})
    sinfo = ec_util.StripeInfo(4 * 64, 64)
    store = MemStore()
    store.mount()
    shards = _FailingShards(store, pg_t(1, 0), 6)
    backend = ECBackend(codec, sinfo, shards)
    shards.fail_on = ("pf1", 5)           # parity shard of the 2nd op
    rng = np.random.default_rng(33)
    payloads = [rng.integers(0, 256, 512, dtype=np.uint8)
                for _ in range(3)]
    acks = []
    ops = []
    with backend.pipeline():
        for i, p in enumerate(payloads):
            txn = PGTransaction()
            txn.write(oid(f"pf{i}"), 0, p)
            ops.append(backend.submit_transaction(
                txn, eversion_t(1, i + 1), lambda i=i: acks.append(i)))
    assert acks == [0, 1, 2]              # nothing wedged, order kept
    assert ops[1].state == "failed" and ops[1].error is not None
    assert ops[0].state == "done" and ops[2].state == "done"
    assert not backend.waiting_reads and not backend.waiting_commit
    assert len(backend.extent_cache) == 0
    assert not backend._projected
    # the pipeline still works after the failure
    t = PGTransaction()
    t.write(oid("pf3"), 0, payloads[0])
    done = []
    backend.submit_transaction(t, eversion_t(1, 4), lambda: done.append(1))
    assert done == [1]
    np.testing.assert_array_equal(backend.read(oid("pf3"), 0, 512),
                                  payloads[0])


def test_pipeline_encode_failure_aborts_cleanly():
    """A device finalize failure aborts exactly the ops that rode the
    failing launch, through the in-order finish queue: error attached,
    pins and projections (incl. the cross-drain _sim_chunk refs) fully
    released, acks in order — and the next launch of the same backend
    succeeds and reads back.  Both ops of the pipeline window ride ONE
    launch (the queue's window never fires on its own here: the first
    finalize flushes both submissions), so both fail."""
    from ceph_tpu.parallel.launch_queue import ECLaunchQueue
    q = ECLaunchQueue(window_us=60_000_000.0)
    backend, _ = make_backend(plugin="jax", queue=q)
    orig = backend.ec_impl.encode_extents_with_crc_finalize
    boom = {"armed": True}

    def failing(handle):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected finalize failure")
        return orig(handle)

    backend.ec_impl.encode_extents_with_crc_finalize = failing
    rng = np.random.default_rng(35)
    payloads = [rng.integers(0, 256, 512, dtype=np.uint8)
                for _ in range(3)]
    acks = []
    ops = []

    def submit(i):
        txn = PGTransaction()
        txn.write(oid(f"ef{i}"), 0, payloads[i])
        ops.append(backend.submit_transaction(
            txn, eversion_t(1, i + 1), lambda: acks.append(i)))

    try:
        with backend.pipeline():
            submit(0)
            submit(1)
        assert q.status()["launches"] == 1
        for op in ops:
            assert op.state == "failed" and \
                "injected finalize failure" in str(op.error)
        assert backend.perf.dump()["ec_drain_errors"] == 2
        assert len(backend.extent_cache) == 0
        assert not backend._projected
        assert not backend._sim_chunk and not backend._sim_refs
        submit(2)
        assert acks == [0, 1, 2]
        assert q.status()["launches"] == 2
        assert ops[2].state == "done" and ops[2].error is None
        np.testing.assert_array_equal(
            backend.read(oid("ef2"), 0, 512), payloads[2])
        for i in (0, 1):
            assert not backend.exists(oid(f"ef{i}"))
        assert len(backend.extent_cache) == 0
        assert not backend._projected
        assert not backend._sim_chunk and not backend._sim_refs
    finally:
        q.close()


def _mesh_pipeline_backend(k=4, m=2, chunk=64):
    from ceph_tpu.parallel.mesh import DistributedStripeCodec, make_mesh
    mc = DistributedStripeCodec(k, m, make_mesh(2, 2))
    codec = REG.factory("jax", {"k": str(k), "m": str(m)})
    store = MemStore()
    store.mount()
    shards = LocalShardBackend(store, pg_t(1, 0), k + m)
    return ECBackend(codec, ec_util.StripeInfo(k * chunk, chunk),
                     shards, mesh_codec=mc), mc


def test_pipeline_mesh_finalize_failure_falls_back():
    """Satellite (ISSUE 10): a mesh encode_flat_finalize failure at
    depth 2 must _abort_op the drain's ops, release their pinned
    extents (zero balance), and leave every SUBSEQUENT drain on the
    single-chip fallback plane — the mesh never wedges the queue."""
    backend, mc = _mesh_pipeline_backend()
    orig = mc.encode_flat_finalize
    boom = {"armed": True}

    def failing(handle):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected mesh finalize failure")
        return orig(handle)

    mc.encode_flat_finalize = failing
    rng = np.random.default_rng(40)
    payloads = [rng.integers(0, 256, 512, dtype=np.uint8)
                for _ in range(4)]
    acks = []
    ops = []
    with backend.pipeline():
        for i, p in enumerate(payloads):
            txn = PGTransaction()
            txn.write(oid(f"mf{i}"), 0, p)
            ops.append(backend.submit_transaction(
                txn, eversion_t(1, i + 1), lambda i=i: acks.append(i)))
    assert acks == [0, 1, 2, 3]           # order kept, nothing wedged
    assert ops[0].state == "failed" and ops[0].error is not None
    # the mesh plane fell back for good; later drains took the
    # single-chip path and committed
    assert backend.mesh_codec is None
    assert "disabled after failure" in backend.mesh_error
    assert backend.mesh_status()["active"] is False
    for i in (1, 2, 3):
        assert ops[i].state == "done", ops[i].error
        np.testing.assert_array_equal(
            backend.read(oid(f"mf{i}"), 0, 512), payloads[i])
    # zero-balance: pins, projections, and cross-drain refs all freed
    assert len(backend.extent_cache) == 0
    assert not backend._projected
    assert not backend._sim_chunk and not backend._sim_refs
    # the pipeline still serves new ops on the fallback plane
    t = PGTransaction()
    t.write(oid("mf_post"), 0, payloads[0])
    done = []
    backend.submit_transaction(t, eversion_t(1, 5),
                               lambda: done.append(1))
    assert done == [1]
    np.testing.assert_array_equal(backend.read(oid("mf_post"), 0, 512),
                                  payloads[0])


def test_pipeline_mesh_submit_failure_falls_back():
    """A mesh launch (submit-half) failure aborts the staging drain's
    ops in order and flips the backend to the fallback plane — same
    containment as the finalize case, caught one stage earlier."""
    backend, mc = _mesh_pipeline_backend()
    boom = {"armed": True}
    orig = mc.encode_flat_submit

    def failing(chunks):
        if boom["armed"]:
            boom["armed"] = False
            raise RuntimeError("injected mesh submit failure")
        return orig(chunks)

    mc.encode_flat_submit = failing
    rng = np.random.default_rng(41)
    payloads = [rng.integers(0, 256, 512, dtype=np.uint8)
                for _ in range(3)]
    acks = []
    ops = []
    with backend.pipeline():
        for i, p in enumerate(payloads):
            txn = PGTransaction()
            txn.write(oid(f"ms{i}"), 0, p)
            ops.append(backend.submit_transaction(
                txn, eversion_t(1, i + 1), lambda i=i: acks.append(i)))
    assert acks == [0, 1, 2]
    assert ops[0].state == "failed" and ops[0].error is not None
    assert backend.mesh_codec is None
    for i in (1, 2):
        assert ops[i].state == "done", ops[i].error
        np.testing.assert_array_equal(
            backend.read(oid(f"ms{i}"), 0, 512), payloads[i])
    assert len(backend.extent_cache) == 0
    assert not backend._projected
    assert not backend._sim_chunk and not backend._sim_refs


def test_mesh_drain_matches_single_chip_fused_hashes():
    """Satellite: a multi-chip (CPU-mesh) drain must produce the same
    cumulative shard hashes as the single-chip fused path — the mesh
    rides the plain parity path whose host crc fold is now the
    vectorized single-pass-per-drain (crc32c_rows)."""
    from ceph_tpu.common import crc32c as C
    from ceph_tpu.parallel.mesh import DistributedStripeCodec, make_mesh
    mesh = make_mesh(4, 2)
    mc = DistributedStripeCodec(4, 2, mesh)
    codec = REG.factory("jax", {"k": "4", "m": "2"})
    sinfo = ec_util.StripeInfo(4 * 64, 64)
    store = MemStore()
    store.mount()
    shards = LocalShardBackend(store, pg_t(1, 0), 6)
    bmesh = ECBackend(codec, sinfo, shards, mesh_codec=mc)
    bfused, _ = make_backend(plugin="jax")
    rng = np.random.default_rng(34)
    pa = rng.integers(0, 256, 512, dtype=np.uint8)
    pb = rng.integers(0, 256, 256, dtype=np.uint8)
    pc = rng.integers(0, 256, 384, dtype=np.uint8)
    o1, o2 = oid("mesh1"), oid("mesh2")
    for b in (bmesh, bfused):
        with b.batch():                   # ONE multi-run drain
            t1 = PGTransaction()
            t1.write(o1, 0, pa)
            b.submit_transaction(t1, eversion_t(1, 1), lambda: None)
            t2 = PGTransaction()          # chained append on o1
            t2.write(o1, 512, pb)
            b.submit_transaction(t2, eversion_t(1, 2), lambda: None)
            t3 = PGTransaction()          # second object
            t3.write(o2, 0, pc)
            b.submit_transaction(t3, eversion_t(1, 3), lambda: None)
    for o, ln in ((o1, 768), (o2, 384)):
        hm = bmesh.shards.get_hinfo(0, o)
        hf = bfused.shards.get_hinfo(0, o)
        assert hm.cumulative_shard_hashes == hf.cumulative_shard_hashes, o
        assert hm.total_chunk_size == hf.total_chunk_size
        np.testing.assert_array_equal(bmesh.read(o, 0, ln),
                                      bfused.read(o, 0, ln))
    # and both equal the host convention
    whole = np.concatenate([pa, pb])
    enc = ec_util.encode(bmesh.sinfo, bmesh.ec_impl, whole)
    hm = bmesh.shards.get_hinfo(0, o1)
    for s in range(6):
        assert hm.get_chunk_hash(s) == C.crc32c(
            enc[s].tobytes(), 0xFFFFFFFF), f"shard {s}"


def test_batched_appends_same_object_chain_hinfo():
    """Consecutive appends in one window chain the cumulative crc."""
    from ceph_tpu.common import crc32c as C
    backend, _ = make_backend()
    o = oid("chain")
    rng = np.random.default_rng(21)
    p1 = rng.integers(0, 256, 256, dtype=np.uint8)
    p2 = rng.integers(0, 256, 256, dtype=np.uint8)
    with backend.batch():
        t1 = PGTransaction()
        t1.write(o, 0, p1)
        backend.submit_transaction(t1, eversion_t(1, 1), lambda: None)
        t2 = PGTransaction()
        t2.write(o, 256, p2)
        backend.submit_transaction(t2, eversion_t(1, 2), lambda: None)
    whole = np.concatenate([p1, p2])
    np.testing.assert_array_equal(backend.read(o, 0, 512), whole)
    hinfo = backend.shards.get_hinfo(0, o)
    shards = ec_util.encode(backend.sinfo, backend.ec_impl, whole)
    for s in range(6):
        assert hinfo.get_chunk_hash(s) == C.crc32c(
            shards[s].tobytes(), 0xFFFFFFFF), f"shard {s}"
