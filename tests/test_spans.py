"""Spans (common/spans.py), op phases (common/tracked_op.py
PHASE_ANCHORS), the objecter's perf set and the launch queue's
transfer counters: arithmetic on an injected clock, then the whole
write path on a 3-OSD k2m1 thread cluster."""

import time

import pytest

from ceph_tpu.common import spans
from ceph_tpu.common.tracked_op import (PHASE_ANCHORS, OpTracker,
                                        TraceContext)


class FakeClock:
    """perf_counter_ns and thread_time_ns under the test's hand: CPU
    advances at `cpu_rate` of the wall."""

    def __init__(self, cpu_rate: float = 0.5):
        self.wall = 1_000
        self.cpu_rate = cpu_rate
        self.cpu_reads = 0

    def tick(self, ns: int) -> None:
        self.wall += ns

    def wall_ns(self) -> int:
        return self.wall

    def cpu_ns(self) -> int:
        self.cpu_reads += 1
        return int(self.wall * self.cpu_rate)


@pytest.fixture()
def clock(monkeypatch):
    c = FakeClock()
    monkeypatch.setattr(spans, "_wall_ns", c.wall_ns)
    monkeypatch.setattr(spans, "_cpu_ns", c.cpu_ns)
    monkeypatch.setattr(spans, "CPU_EVERY", 1)
    spans.reset()
    yield c
    spans.reset()


# (program, {name: (self wall ns, count)}): a program is a nested list
# of (name, own ns before children, children, own ns after children)
_LEAF = ("a", 100, [], 0)
_NESTED = ("a", 10, [("b", 30, [("c", 5, [], 0)], 7)], 20)
_SIBLINGS = ("a", 1, [("b", 10, [], 0), ("b", 20, [], 0),
                      ("c", 40, [], 0)], 2)
_SELF_NESTED = ("a", 3, [("a", 4, [], 0)], 5)


def _play(clock, node):
    name, before, children, after = node
    with spans.span(name):
        clock.tick(before)
        for child in children:
            _play(clock, child)
        clock.tick(after)


@pytest.mark.parametrize("program,want", [
    (_LEAF, {"a": (100, 1)}),
    (_NESTED, {"a": (30, 1), "b": (37, 1), "c": (5, 1)}),
    (_SIBLINGS, {"a": (3, 1), "b": (30, 2), "c": (40, 1)}),
    (_SELF_NESTED, {"a": (12, 2)}),
], ids=["leaf", "nested", "siblings", "self_nested"])
def test_self_time_arithmetic(clock, program, want):
    _play(clock, program)
    got = spans.table()
    assert set(got) == set(want)
    for name, (wall_ns, n) in want.items():
        wall_s, cpu_s, count = got[name]
        assert count == n
        assert wall_s == pytest.approx(wall_ns * 1e-9, abs=1e-12)
        # the fake CPU runs at half the wall, children subtracted alike
        assert cpu_s == pytest.approx(wall_ns * 0.5e-9, abs=2e-9)
    # the selves partition the root's whole duration
    total = sum(w for w, _, _ in got.values())
    assert total == pytest.approx(
        sum(w for w, _ in want.values()) * 1e-9, abs=1e-12)


def test_thread_cpu_is_sampled_by_whole_trees(clock, monkeypatch):
    """Every 4th top-level span of a name reads the CPU clock, with
    everything inside it; the estimate scales the sampled sum."""
    monkeypatch.setattr(spans, "CPU_EVERY", 4)
    for i in range(8):
        with spans.span("root"):
            clock.tick(100)
            with spans.span("leaf"):
                clock.tick(20)
    with spans.span("other"):           # its own count: the first is read
        clock.tick(10)
    # roots 0 and 4 and their leaves, and `other`: two reads each
    assert clock.cpu_reads == 2 * (2 + 2 + 1)
    got = spans.table()
    assert got["root"][2] == got["leaf"][2] == 8
    # identical spans: the scaled estimate is the exact total
    assert got["root"][1] == pytest.approx(8 * 100 * 0.5e-9, abs=4e-9)
    assert got["leaf"][1] == pytest.approx(8 * 20 * 0.5e-9, abs=4e-9)
    assert got["root"][0] == pytest.approx(8 * 100e-9)   # wall: all 8


def test_whole_duration_is_kept_for_the_hook(clock):
    with spans.span("outer") as sp:
        clock.tick(10)
        with spans.span("inner"):
            clock.tick(90)
    assert sp.wall_ns == 100 and sp.wall_s == pytest.approx(1e-7)


@pytest.mark.parametrize("form", ["with", "pair"])
def test_off_is_two_clock_reads_and_nothing_else(clock, form):
    """A span whose recorder is off keeps `wall_ns` for the hook that
    takes its sample from it, and touches neither table, stack nor
    CPU clock; an ON span inside it is nobody's child."""
    if form == "with":
        with spans.span("ec.assemble", False, pgid="1.0") as sp:
            clock.tick(40)
            with spans.span("inner"):
                clock.tick(2)
    else:
        sp = spans.begin("ec.assemble", False, pgid="1.0")
        clock.tick(40)
        with spans.span("inner"):
            clock.tick(2)
        spans.end(sp)
    assert sp.wall_ns == 42
    assert set(spans.table()) == {"inner"}
    assert clock.cpu_reads == 2         # inner's own


@pytest.mark.parametrize("form", ["with", "pair"])
def test_a_raising_body_still_closes(clock, form):
    with pytest.raises(RuntimeError):
        if form == "with":
            with spans.span("boom"):
                clock.tick(7)
                raise RuntimeError("x")
        else:
            sp = spans.begin("boom")
            try:
                clock.tick(7)
                raise RuntimeError("x")
            finally:
                spans.end(sp)
                spans.end(sp)       # idempotent
    assert spans.table()["boom"][2] == 1
    # the stack is clean: a later span is nobody's child
    with spans.span("after"):
        clock.tick(5)
    assert spans.table()["after"][0] == pytest.approx(5e-9)
    assert spans.table()["boom"][0] == pytest.approx(7e-9)


def test_a_pair_whose_end_was_skipped_goes_with_its_parent(clock):
    with spans.span("parent"):
        clock.tick(1)
        spans.begin("leaked")           # never ended
        clock.tick(2)
    with spans.span("next"):
        clock.tick(4)
    got = spans.table()
    assert "leaked" not in got
    assert got["parent"][0] == pytest.approx(3e-9)
    assert got["next"][0] == pytest.approx(4e-9)


def test_no_profiler_session_nothing_for_jax_and_the_table_counts():
    from jax.profiler import TraceAnnotation
    assert not TraceAnnotation.is_enabled()
    spans.reset()
    with spans.span("quiet", launch=7) as sp:
        pass
    assert sp.ann is None               # no TraceMe was even built
    assert spans.table()["quiet"][2] == 1
    dump = spans.host_spans().dump()
    assert dump["quiet_n"] == 1 and dump["quiet_wall"] >= 0.0
    assert dump["process_cpu_s"] > 0.0
    assert spans.host_spans().schema()["quiet_cpu"] == "time"
    spans.reset()


def test_a_profiler_session_gets_the_row(tmp_path):
    import glob

    import jax
    from jax.profiler import ProfileData
    spans.reset()
    with spans.span("before"):
        pass
    assert not spans.tracing_now
    jax.profiler.start_trace(str(tmp_path))
    try:
        time.sleep(0.006)           # jax is asked again after 5 ms
        with spans.span("lq.launch", launch=41, pgid=object()):
            time.sleep(0.002)
        assert spans.tracing_now    # what the trace-only sites read
        ann = spans.annotation("msgr.send", type="MOSDOp")
        time.sleep(0.001)
        ann.__exit__(None, None, None)
    finally:
        jax.profiler.stop_trace()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    names = [ev.name for plane in ProfileData.from_file(path).planes
             for line in plane.lines for ev in line.events]
    assert "lq.launch" in names and "msgr.send" in names
    assert spans.table()["lq.launch"][2] == 1
    assert "msgr.send" not in spans.table()     # a trace row only
    time.sleep(0.006)
    with spans.span("after"):
        pass
    assert not spans.tracing_now
    spans.reset()


# -- op phases ---------------------------------------------------------------

def _osd_op(events, initiated_at, completed_at, origin=None):
    trk = OpTracker(perf=None)
    top = trk.create("osd_op", "t", TraceContext(
        "t" * 16, "s" * 8, None, origin) if origin is not None else None)
    top.initiated_at = initiated_at
    if origin is None:
        top.events.clear()
    for ts, name in events:
        top.mark_event(name, ts)
    top.completed_at = completed_at
    return top


_FULL = [(10.2, "msgr_dispatch"), (10.31, "msgr_recv_lag"),
         (10.32, "queued"), (10.4, "dequeued"),
         (10.45, "msgr_send(osd.1)"), (10.7, "ec_encode_launch"),
         (10.9, "launch(3)"), (10.9, "ec_encode_materialize"),
         (10.95, "sub_write_sent"), (11.0, "msgr_send(osd.2)"),
         (11.2, "sub_write_ack(1)"), (11.4, "sub_write_ack(0)"),
         (11.4, "commit"), (11.45, "reply_sent")]


@pytest.mark.parametrize("events,origin,want", [
    # the whole write timeline, interleaved msgr_send events and all
    (_FULL, 10.0, [("wire_in", 0.3), ("queue_wait", 0.1),
                   ("prepare", 0.3), ("encode", 0.2),
                   ("fanout_commit", 0.6)]),
    # no client trace: the timeline starts at the frame's recv_stamp
    (_FULL, None, [("wire_in", 0.1), ("queue_wait", 0.1),
                   ("prepare", 0.3), ("encode", 0.2),
                   ("fanout_commit", 0.6)]),
    # a read never encodes: the tail takes what follows `dequeued`
    ([(10.2, "msgr_dispatch"), (10.32, "queued"), (10.4, "dequeued"),
      (11.0, "reply_sent")], 10.0,
     [("wire_in", 0.3), ("queue_wait", 0.1), ("fanout_commit", 1.1)]),
    # an origin stamped by a clock ahead of ours clamps to 0
    (_FULL, 10.35, [("wire_in", 0.0), ("queue_wait", 0.1),
                    ("prepare", 0.3), ("encode", 0.2),
                    ("fanout_commit", 0.6)]),
], ids=["write", "untraced", "read", "skewed_origin"])
def test_phases_partition_the_timeline(events, origin, want):
    top = _osd_op(events, initiated_at=10.3, completed_at=11.5,
                  origin=origin)
    got = top.phase_durations()
    assert [p for p, _ in got] == [p for p, _ in want]
    for (_, dt), (_, w) in zip(got, want):
        assert dt == pytest.approx(w, abs=1e-9)
    # after wire_in the phases are the op's duration, exactly
    assert sum(dt for p, dt in got if p != "wire_in") == \
        pytest.approx(top.duration(), abs=1e-9)


def test_phase_histograms_are_fed_at_unregister():
    from ceph_tpu.common.perf_counters import PerfCountersBuilder
    perf = PerfCountersBuilder("optracker.t").create_perf_counters()
    trk = OpTracker(perf=perf)
    top = trk.create("ec_sub_write", "x")
    time.sleep(0.002)
    top.mark_event("sub_op_applied")
    trk.unregister(top, 0)
    other = trk.create("recovery", "no anchors declared")
    trk.unregister(other, 0)
    dump = perf.dump()
    apply_ = dump["lat_phase_ec_sub_write_apply"]
    assert apply_["count"] == 1
    assert 0.002 <= apply_["sum"] <= \
        dump["lat_total_ec_sub_write"]["sum"] + 1e-6
    # one series a declared phase, and none for what nothing reads
    assert [k for k in dump if k.startswith("lat_phase_")] == \
        ["lat_phase_ec_sub_write_apply"]
    assert PHASE_ANCHORS["osd_op"][-1][1] is None


# -- the write path on a live cluster ----------------------------------------

# (per-message work on the reactor threads — msgr.send, msgr.decode,
# inline handlers — is trace-only and accounted by thread: below)
SPAN_NAMES = [
    "msgr.dispatch.MOSDECSubOpWrite", "msgr.dispatch.MPGStats",
    "ec.on_commit", "osd.op_prepare", "ec.assemble", "ec.complete",
    "lq.launch",
    "ec.h2d", "ec.dispatch", "lq.finalize", "ec.d2h_wait", "ec.fanout",
    "osd.sub_write_apply", "store.commit", "osd.tick.heartbeat",
    "osd.tick.pgstats", "osd.tick.optrack",
]


@pytest.fixture(scope="module")
def written_cluster(tmp_path_factory):
    """3 OSDs, k2m1 on the jax plugin; one 4 MiB and one 4 KiB
    write_full, then one 20 KiB one (6 flat tiles: the pow2 bucket
    pads it to 8)."""
    from ceph_tpu.ops.profiler import DeviceProfiler
    from ceph_tpu.parallel.launch_queue import ECLaunchQueue
    from ceph_tpu.tools.vstart import Cluster
    ECLaunchQueue.reset_host()
    DeviceProfiler.reset_host()
    spans.reset()
    try:
        asok_dir = str(tmp_path_factory.mktemp("asok"))
        with Cluster(n_osds=3, heartbeat_interval=0.2,
                     asok_dir=asok_dir) as c:
            client = c.client()
            client.set_ec_profile("sp21", {
                "plugin": "jax", "k": "2", "m": "1",
                "technique": "cauchy", "stripe_unit": "4096"})
            client.create_pool("sppool", "erasure",
                               erasure_code_profile="sp21", pg_num=4)
            c.wait_active_clean(timeout=60)
            io = client.open_ioctx("sppool")
            queue = ECLaunchQueue.host_get()
            io.write_full("big", bytes(range(256)) * (4 << 12))
            io.write_full("small", b"s" * 4096)
            two = {"client": client.perf_dump(),
                   "queue": queue.perf.dump()}
            io.write_full("odd", b"o" * (20 << 10))
            deadline = time.time() + 15
            while time.time() < deadline and not all(
                    n in spans.table() for n in SPAN_NAMES):
                time.sleep(0.1)
            yield {"cluster": c, "client": client, "two": two,
                   "queue": queue.perf.dump(), "spans": spans.table(),
                   "asok_dir": asok_dir}
    finally:
        ECLaunchQueue.reset_host()
        DeviceProfiler.reset_host()
        spans.reset()


@pytest.mark.parametrize("name", SPAN_NAMES)
def test_every_span_of_the_write_path_counts(written_cluster, name):
    wall, cpu, n = written_cluster["spans"][name]
    assert n > 0 and wall >= 0.0 and cpu >= 0.0


def test_reactor_cpu_is_accounted_by_thread(written_cluster):
    dump = spans.host_spans().dump()
    assert dump["msgr.reactor_cpu"] > 0.0       # boot alone costs some
    assert dump["msgr.reactor_cpu"] <= dump["process_cpu_s"]
    assert "msgr.send_n" not in dump and "msgr.decode_n" not in dump
    # live threads only, so not monotonic: a gauge
    assert spans.host_spans().schema()["msgr.reactor_cpu"] == "gauge"
    # executor continuations carry their caller's layer, never the
    # wire's, and never a function's repr
    assert not [k for k in dump if k.startswith("msgr.dispatch.")
                and not k.split(".")[2].startswith("M")]


def test_the_exposition_of_an_osd_host_parses(written_cluster):
    """Every perf key of every set of every daemon — dotted span
    names, `+` in a fused path — comes out of the exporter as a legal
    prometheus name."""
    import re

    from ceph_tpu.tools.metrics_exporter import collect, prom_name
    text = collect(written_cluster["asok_dir"])
    sample = re.compile(
        r"^[a-zA-Z_:][a-zA-Z0-9_:]*(\{[^{}]*\})? [-+0-9.eEinfa]+$")
    typed = re.compile(r"^# TYPE [a-zA-Z_:][a-zA-Z0-9_:]* "
                       r"(counter|gauge|histogram|untyped)$")
    lines = text.splitlines()
    bad = [ln for ln in lines if not (
        sample.match(ln) or typed.match(ln) or ln.startswith("# HELP "))]
    assert not bad, bad[:5]
    assert any(ln.startswith("ceph_tpu_lq_launch_cpu{") for ln in lines)
    assert any(ln.startswith("ceph_tpu_ec_drains_by_path_")
               for ln in lines)
    assert prom_name("msgr.dispatch.<x>+y_wall") == \
        "ceph_tpu_msgr_dispatch__x__y_wall"


def test_host_spans_set_has_one_owner(written_cluster):
    c = written_cluster["cluster"]
    owners = [o for o in c.osds if "host_spans" in o.cct.perf.dump()]
    assert len(owners) == 1
    dump = spans.host_spans().dump()
    assert dump["lq.launch_n"] >= 3 and "process_cpu_s" in dump


def test_phase_sum_equals_total_per_op(written_cluster):
    c = written_cluster["cluster"]
    seen = 0
    for osd in c.osds:
        for op in osd.op_tracker.dump_historic_ops()["ops"]:
            if op["type"] != "osd_op" or "writefull" not in \
                    op["description"]:
                continue
            top = next(t for t in osd.op_tracker.get_historic(
                op["trace_id"]) if t.op_type == "osd_op")
            phases = dict(top.phase_durations())
            assert set(phases) == {"wire_in", "queue_wait", "prepare",
                                   "encode", "fanout_commit"}
            inside = sum(v for k, v in phases.items() if k != "wire_in")
            assert inside == pytest.approx(top.duration(), abs=1e-6)
            assert sum(phases.values()) == pytest.approx(
                top.completed_at - top.events[0][0], abs=1e-6)
            seen += 1
        dump = osd.cct.perf.dump()[f"optracker.osd.{osd.osd_id}"]
        if "lat_total_osd_op" in dump:
            inside = sum(dump[f"lat_phase_osd_op_{p}"]["sum"] for p in
                         ("queue_wait", "prepare", "encode",
                          "fanout_commit"))
            n = dump["lat_total_osd_op"]["count"]
            assert inside == pytest.approx(
                dump["lat_total_osd_op"]["sum"], abs=1e-6 * n)
    assert seen == 3


def test_padded_bytes_from_the_shapes(written_cluster):
    two, q = written_cluster["two"]["queue"], written_cluster["queue"]
    # k=2 rows; 4 MiB -> 2 MiB per shard = 1024 flat tiles of 2 KiB
    # (a power of two); 4 KiB -> one 8 KiB stripe = 2 tiles per shard
    assert two["ec_host_launch_bytes"] == (4 << 20) + 8192
    assert two["ec_host_launch_padded_bytes"] == (4 << 20) + 8192
    # 20 KiB -> 3 stripes = 12 KiB per shard = 6 tiles -> bucket of 8
    assert q["ec_host_launch_bytes"] - two["ec_host_launch_bytes"] \
        == 2 * 6 * 2048
    assert q["ec_host_launch_padded_bytes"] \
        - two["ec_host_launch_padded_bytes"] == 2 * 8 * 2048
    # staged words up, and a constant only in the launch that uploaded
    # it (another test of this process may have); parity + crc bits down
    assert q["ec_h2d_bytes"] == \
        q["ec_host_launch_padded_bytes"] + q["ec_h2d_const_bytes"]
    assert q["ec_const_cache_hits"] + q["ec_const_cache_misses"] > 0
    assert q["ec_d2h_bytes"] >= q["ec_host_launch_padded_bytes"] // 2


def test_client_perf_dump(written_cluster):
    obj = written_cluster["two"]["client"]["objecter"]
    assert obj["op_send"] == obj["op_reply"] == 2
    assert obj["op_resend"] == obj["op_timeout"] == 0
    assert obj["lat_op"]["count"] == 2
    assert obj["lat_reply_leg"]["count"] == 2
    assert 0.0 <= obj["lat_reply_leg"]["sum"] < obj["lat_op"]["sum"]


def test_drains_counted_by_path(written_cluster):
    c = written_cluster["cluster"]
    by_path = {}
    for osd in c.osds:
        for name, counters in osd.cct.perf.dump().items():
            if name.startswith("ec."):
                for key, val in counters.items():
                    if key.startswith("ec_drains_by_path."):
                        by_path[key] = by_path.get(key, 0) + val
    # the CPU's twin of the fused kernel; on a TPU the same counter
    # reads hier_acc / hier_lsub / w32_flat
    assert by_path == {"ec_drains_by_path.xla": 3}


def test_compile_seconds_count_hits_and_misses():
    import jax
    import jax.numpy as jnp
    from ceph_tpu.ops import compile_cache
    compile_cache.enable()
    before = compile_cache.counters()

    def f(x):
        return (x * 3 + 1).sum()
    jax.jit(f)(jnp.arange(1031))            # a miss: compiled, stored
    mid = compile_cache.counters()
    jax.clear_caches()
    jax.jit(f)(jnp.arange(1031))            # the same program: a hit
    after = compile_cache.counters()
    assert mid["requests"] > before["requests"]
    assert after["hits"] > mid["hits"]
    assert after["requests"] > mid["requests"]
    # both kinds of request add their seconds
    assert mid["compile_s"] >= before["compile_s"]
    assert after["compile_s"] >= mid["compile_s"]


def test_recorders_off_means_no_span_work(written_cluster):
    """Each span is on when the recorder of its layer is: with the op
    tracker, the device profiler and the wire ledger off, a write adds
    nothing to the table (and still succeeds)."""
    from ceph_tpu.msg.msgr_ledger import msgr_ledger
    from ceph_tpu.ops.profiler import device_profiler
    c = written_cluster["cluster"]
    recorders = [o.op_tracker for o in c.osds] + \
        [device_profiler(), msgr_ledger()]
    for r in recorders:
        r.enabled = False
    try:
        time.sleep(0.5)                 # what was open closes
        before = {k: v[2] for k, v in spans.table().items()}
        io = written_cluster["client"].open_ioctx("sppool")
        io.write_full("quiet", b"q" * 8192)
        assert io.read("quiet", 8192) == b"q" * 8192
        time.sleep(0.5)
        assert {k: v[2] for k, v in spans.table().items()} == before
    finally:
        for r in recorders:
            r.enabled = True
