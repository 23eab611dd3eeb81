"""Compile-stall kill switch (ISSUE 16): boot-time bucket prewarm,
the persistent compile cache, and their end-to-end guarantee — with
prewarm + cache on, the runtime write path NEVER sees a first-seen
jit bucket, so `ec_compile_stalls` stays 0 and COMPILE_STORM cannot
fire even across an OSD kill/revive storm.

What must hold: the PrewarmPlan's predicted buckets are EXACTLY the
buckets a depth-2 pipelined write storm later launches (exactness by
construction — the plan executes the real plugin entry points); a
second in-process "boot" against the same persistent cache dir
re-traces but never re-compiles (ec_prewarm_cache_hits > 0, zero
stalls); a zero budget truncates the plan but never blocks the boot;
and a prewarmed cluster survives kill/revive churn with armed stall
injection at zero stalls and no COMPILE_STORM, its first launches
ledgered as cache hits.
"""

import os
import subprocess
import sys
import time

import jax
import numpy as np

from ceph_tpu.ec import ErasureCodePluginRegistry
from ceph_tpu.ops import bitsliced as bs
from ceph_tpu.ops import compile_cache, prewarm
from ceph_tpu.ops.profiler import DeviceProfiler, device_profiler
from ceph_tpu.osd.ec_backend import ECBackend, LocalShardBackend
from ceph_tpu.osd.ec_transaction import PGTransaction
from ceph_tpu.osd.ec_util import StripeInfo
from ceph_tpu.osd.types import eversion_t, hobject_t, pg_t
from ceph_tpu.parallel.launch_queue import ECLaunchQueue
from ceph_tpu.store import MemStore

REG = ErasureCodePluginRegistry.instance()
ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def oid(name):
    return hobject_t(pool=1, name=name)


def make_codec(k=2, m=1):
    return REG.factory("jax", {"k": str(k), "m": str(m),
                               "technique": "cauchy"})


def make_backend(queue, codec, chunk=64):
    store = MemStore()
    store.mount()
    shards = LocalShardBackend(store, pg_t(1, 0),
                               codec.get_chunk_count())
    return ECBackend(codec,
                     StripeInfo(codec.get_data_chunk_count() * chunk,
                                chunk),
                     shards, launch_queue=queue, perf_name="ec.1.0")


def _reset_all():
    DeviceProfiler.reset_host()
    ECLaunchQueue.reset_host()
    prewarm.reset_for_tests()


def _storm(codec, n=4):
    """Depth-2 pipelined write storm through the launch queue — the
    exact shape the flight recorder's stitching test uses."""
    q = ECLaunchQueue(window_us=60_000_000.0)
    be = make_backend(q, codec)
    rng = np.random.default_rng(16)
    done = []
    with be.pipeline():
        for i in range(n):
            txn = PGTransaction()
            txn.write(oid(f"pw{i}"), 0,
                      rng.integers(0, 256, 512, dtype=np.uint8))
            be.submit_transaction(txn, eversion_t(1, i + 1),
                                  lambda: done.append(1))
    q.close()
    assert len(done) == n
    return done


# -- exactness: plan == what the queue launches -----------------------------

def test_plan_covers_depth2_write_storm_exactly():
    """planned_buckets() (pure prediction, no compile) must equal the
    buckets run() actually seeds, and a depth-2 pipelined write storm
    afterwards must land ONLY on prewarmed buckets: every record a
    cache hit, zero stalls even with the stall injection armed (a
    single cold bucket would both sleep and count — deterministic)."""
    _reset_all()
    try:
        codec = make_codec()
        host = device_profiler()
        plan = prewarm.PrewarmPlan(codec, profiler=host)
        predicted = set(plan.planned_buckets())
        st = plan.run()
        assert st["done"] == st["planned"] and not st["truncated"]
        seeded = set(st["buckets"])
        assert seeded == predicted          # prediction == execution
        # arm the injection AFTER prewarm: any first-seen runtime
        # bucket now sleeps 0.5s and counts a stall
        host.inject_stall_s = 0.5
        host.stall_s = 0.25
        _storm(codec)
        launched = {r["bucket"] for r in host.profile()["recent"]}
        assert launched, "storm produced no launches"
        assert launched <= seeded, (
            f"cold buckets under storm: {launched - seeded}")
        assert host.compile_stalls == 0
        for r in host.profile()["recent"]:
            assert r["cache_hit"], r    # first launch of a warm bucket
            assert not r["compiled"]
    finally:
        _reset_all()


# -- persistent cache round-trip across an in-process restart ---------------

def test_persistent_cache_roundtrip_restart():
    """Boot 1 compiles to (or already finds its programs in) the
    persistent cache directory the environment placed (conftest:
    JAX_COMPILATION_CACHE_DIR); a simulated daemon restart (cleared
    jit caches + reset singletons) re-runs the prewarm and hits the
    persistent cache: ec_prewarm_cache_hits > 0 and zero compile
    stalls on the second boot's write path."""
    _reset_all()
    small = dict(widths=[2048, 4096], run_counts=[1, 2],
                 plain_widths=[2048], decode_widths=[2048])
    try:
        # cold process for boot 1 too: earlier tests may have compiled
        # these very programs in-memory, which would let boot 1 skip
        # the persistent cache — and nothing on disk can't be hit on
        # boot 2
        jax.clear_caches()
        bs.aot_reset_for_tests()
        assert compile_cache.enable() == \
            os.environ["JAX_COMPILATION_CACHE_DIR"]
        codec = make_codec()
        host = device_profiler()
        st1 = prewarm.run_once(codec, profiler=host, budget_s=60.0,
                               **small)
        assert st1["done"] == st1["planned"]
        assert st1["persistent_cache"]["enabled"]
        assert prewarm.run_once(codec)["reused"]   # later booters
        # -- the restart: new process state, same cache dir ---------
        jax.clear_caches()
        bs.aot_reset_for_tests()
        _reset_all()
        codec2 = make_codec()
        host2 = device_profiler()
        st2 = prewarm.run_once(codec2, profiler=host2, budget_s=60.0,
                               **small)
        assert st2["done"] == st2["planned"]
        assert st2["cache_hits"] > 0, st2
        assert host2.prewarm_cache_hits > 0
        assert host2.perf.dump()["ec_prewarm_cache_hits"] > 0
        # second boot's runtime write path: warm by seed, no stalls
        host2.inject_stall_s = 0.5
        _storm(codec2, n=2)
        assert host2.compile_stalls == 0
        assert host2.perf.dump()["ec_compile_stalls"] == 0
    finally:
        jax.clear_caches()
        bs.aot_reset_for_tests()
        _reset_all()


# -- budget cutoff: prewarm is never a boot dependency ----------------------

def test_budget_cutoff_leaves_daemon_bootable():
    """budget_s=0 truncates the plan before the first entry, and a
    cluster booted that way still comes up and serves writes — the
    asok reports the truncation instead of the boot hanging."""
    from ceph_tpu.tools.vstart import Cluster
    _reset_all()
    try:
        plan = prewarm.PrewarmPlan(make_codec(), budget_s=0.0)
        st = plan.run()
        assert st["truncated"]
        assert st["done"] == 0 and st["skipped"] == st["planned"]

        with Cluster(n_osds=2, prewarm=True,
                     conf={"osd_ec_prewarm_budget_s": 0.0}) as c:
            client = c.client()
            client.create_pool("bp", pg_num=4)
            io = client.open_ioctx("bp")
            io.write_full("b0", b"x" * 1000)
            assert io.read("b0", 1000, 0) == b"x" * 1000
            status = c.osds[0]._asok_prewarm_status({})
            assert status["enabled"]
            assert status["boot"]["truncated"]
            assert status["boot"]["done"] == 0
    finally:
        _reset_all()


# -- kill/revive storm: zero stalls, no COMPILE_STORM -----------------------

def test_kill_revive_storm_zero_stalls():
    """The headline gate, in miniature: a prewarmed EC cluster with
    the stall injection ARMED takes writes, loses an OSD, writes
    degraded, revives it (recovery decodes), writes again — and the
    ledger shows zero compile stalls, the mon never raises
    COMPILE_STORM, and the prewarmed buckets' first launches are
    ledgered as cache hits."""
    from ceph_tpu.tools.vstart import Cluster
    _reset_all()
    try:
        conf = {
            # daemon prewarm derives its codec from this profile; the
            # pool below MUST match it (bucket keys carry geometry
            # only through shapes, not codec identity)
            # k=2 m=2: min_size is k+1=3, so one lost OSD still
            # admits (degraded) writes — the storm's whole point
            "osd_pool_default_erasure_code_profile":
                "plugin=jax technique=cauchy k=2 m=2 stripe_unit=1024",
            "osd_ec_inject_compile_stall": 0.5,
            "osd_ec_prewarm_budget_s": 60.0,
        }
        with Cluster(n_osds=4, prewarm=True, conf=conf) as c:
            host = device_profiler()
            assert any(e.get("prewarmed")
                       for e in host._buckets.values()), \
                "boot prewarm seeded nothing"
            client = c.client()
            client.set_ec_profile("pw22", {
                "plugin": "jax", "k": "2", "m": "2",
                "technique": "cauchy", "stripe_unit": "1024"})
            client.create_pool("pwpool", "erasure",
                               erasure_code_profile="pw22", pg_num=4)
            io = client.open_ioctx("pwpool")
            payload = bytes(range(256)) * 16            # 4096 -> w2048
            for i in range(4):
                io.write_full(f"k{i}", payload)
            c.kill_osd(2)
            c.mark_osd_down(2)
            for i in range(4, 7):                       # degraded
                io.write_full(f"k{i}", payload)
            c.revive_osd(2)                             # recovery path
            deadline = time.time() + 30.0
            while time.time() < deadline:
                if all(io.read(f"k{i}", 4096, 0) == payload
                       for i in range(7)):
                    break
                time.sleep(0.2)
            for i in range(7, 9):                       # post-revive
                io.write_full(f"k{i}", payload)
            assert host.profile()["launches"] >= 1
            assert host.compile_stalls == 0, \
                host.compile_ledger()["buckets"]
            assert any(r["cache_hit"]
                       for r in host.profile()["recent"])
            _rc, health = c.mon.handle_command({"prefix": "health"})
            assert "COMPILE_STORM" not in health["checks"]
            # revived daemon reused the process-level prewarm: its
            # boot was not delayed by a second plan run
            st = c.osds[2]._asok_prewarm_status({})
            assert st["boot"].get("reused") or st["boot"].get("done")
    finally:
        _reset_all()


# -- cache placement: from outside, or one fixed in-checkout path -----------

_PLACEMENT_PROBE = (
    "import jax\n"
    "from ceph_tpu.ops import compile_cache\n"
    "d = compile_cache.enable()\n"
    "assert jax.config.jax_compilation_cache_dir == d\n"
    "assert compile_cache.status()['dir'] == d\n"
    "print(d)\n")


def _fresh_interpreter_cache_dir(env_dir):
    """compile_cache.enable() in a FRESH interpreter (jax reads the
    variable at import), from the repo root; returns the directory."""
    env = {k: v for k, v in os.environ.items()
           if k != "JAX_COMPILATION_CACHE_DIR"}
    if env_dir is not None:
        env["JAX_COMPILATION_CACHE_DIR"] = env_dir
    out = subprocess.run([sys.executable, "-c", _PLACEMENT_PROBE],
                         cwd=ROOT, env=env, capture_output=True,
                         text=True, timeout=120)
    assert out.returncode == 0, out.stderr
    return out.stdout.strip().splitlines()[-1]


def test_cache_dir_comes_from_environment(tmp_path):
    """JAX_COMPILATION_CACHE_DIR set: enable() leaves jax's configured
    directory equal to it (and sets no other)."""
    want = str(tmp_path / "placed")
    assert _fresh_interpreter_cache_dir(want) == want
    assert os.path.isdir(want)


def test_cache_dir_unset_is_fixed_in_checkout_path():
    """Unset: the one fixed directory inside the checkout, identical
    across two fresh interpreters (a directory that moves never
    hits)."""
    a = _fresh_interpreter_cache_dir(None)
    b = _fresh_interpreter_cache_dir(None)
    assert a == b == str(compile_cache.CHECKOUT_DIR)
    assert a == os.path.join(ROOT, ".jax_cache")
