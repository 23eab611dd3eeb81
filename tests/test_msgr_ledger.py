"""Wire-plane flight recorder tests (ISSUE 20, docs/TRACING.md "Wire
plane"): the per-process MsgrLedger, its per-messenger/per-peer
accounting, the reactor-lag probe and dispatch-queue timing, the
aggregation path up to the mon (MPGStats `msgr` block +
MSGR_REACTOR_LAG health), and the trace-stitch events that let
slow-op blame name the wire.

What must hold: the off path records nothing after one attribute
check; per-peer tables and by-type maps stay bounded; the
dispatch-queue wait/run histograms advance under a deliberately
blocked dispatcher and the depth gauge returns to zero; reconnects
and replayed frames are counted across a wire kill/revive; `_run_sync`
expiries ride the conf'd ms_sync_timeout and count instead of only
raising; `messenger status`/`conn profile` round-trip over the asok
(both ceph_cli folds); the exporter emits ceph_tpu_msgr_* gauges; an
injected lag event reaches the mon as MSGR_REACTOR_LAG; and a slow
send under an injected dispatch stall names msgr_send(peer) on the op
timeline.
"""

import asyncio
import threading
import time
from concurrent.futures import TimeoutError as FuturesTimeout

import numpy as np
import pytest

from ceph_tpu.msg import messages as M
from ceph_tpu.msg.messenger import Messenger
from ceph_tpu.msg.msgr_ledger import (OTHER_TYPE, TYPE_CAP, MsgrLedger,
                                      msgr_ledger)


def _wait(pred, timeout=30.0, step=0.05):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(step)
    return False


# -- ledger core -------------------------------------------------------------

def test_disabled_null_path_records_nothing():
    """enabled=False: the messenger hooks gate on ONE attribute check
    and never reach the stats object; the ledger's own entry points
    that carry their own gate (note_reactor_lag) no-op; the monward
    block stays None and the bench percentiles stay unpopulated."""
    led = MsgrLedger(enabled=False)
    st = led.register_messenger("osd.9")
    # the messenger-side shape: every hook is behind this gate
    if led.enabled:
        st.note_send("osd.1", "MOSDOp", 100, 1)
    led.note_reactor_lag(0, 5.0, interval=0.25)   # self-gated
    assert led.pgstats_block() is None
    assert led.status()["enabled"] is False
    t = st.totals()
    assert t["msgs_out"] == 0 and t["bytes_out"] == 0
    assert t["peers"] == 0
    assert led.lag_events_total == 0
    b = led.bench_summary()
    assert b["qwait_ms_p50"] is None
    assert b["reactor_lag_ms_p50"] is None
    assert b["dispatches"] == 0


_RX_KEYS = ("msgr_rx_reads", "msgr_large_bodies",
            "msgr_large_body_reads", "msgr_large_body_bytes")


@pytest.mark.parametrize("enabled", [True, False], ids=["on", "off"])
def test_receive_counters_move_by_exact_amounts(enabled):
    """A known exchange handed to a receiver read by read: three
    pings and the head of a 300 KiB frame in one read, its body in
    two more, a ping, then a 100 KiB frame whole in one read —
    `msgr_rx_reads` counts every read, a large body the reads that
    landed in IT (the scratch read that carried its prefix included),
    and with the ledger off nothing moves."""
    from ceph_tpu.msg.message import Message
    from ceph_tpu.msg.messenger import FrameReceiver
    from ceph_tpu.osd.types import hobject_t, pg_t, spg_t

    def op(seq, size):
        return M.MOSDOp(spg_t(pg_t(1, 2), 0), hobject_t(1, f"o{seq}"),
                        [["write", 0, size]], bytes(size)).encode(seq)

    pings = b"".join(M.MOSDPing(from_osd=i).encode(i + 1)
                     for i in range(3))
    big, mid = op(4, 300 << 10), op(6, 100 << 10)
    hsize = Message.HEADER_SIZE
    reads = [pings + big[:hsize + 1000],        # scratch, prefix 1000
             big[hsize + 1000:200_000],         # into the body
             big[200_000:],                     # the body's last read
             M.MOSDPing(from_osd=9).encode(5),  # scratch again
             mid]                               # whole in the scratch
    led = MsgrLedger(enabled=enabled)

    async def main():
        rx = FrameReceiver(led)

        class Transport:
            def pause_reading(self): pass
            def resume_reading(self): pass

        rx.connection_made(Transport())
        for chunk in reads:
            buf = rx.get_buffer(-1)
            assert len(buf) >= len(chunk)
            buf[:len(chunk)] = chunk
            del buf
            rx.buffer_updated(len(chunk))
        return [(await rx.next_frame())[1] for _ in range(6)]

    assert asyncio.run(main()) == [1, 2, 3, 4, 5, 6]
    d = led.perf.dump()
    if enabled:
        assert [d[k] for k in _RX_KEYS] == [
            5, 2, 3 + 1,
            (len(big) - hsize) + (len(mid) - hsize)]
    else:
        assert [d[k] for k in _RX_KEYS] == [0, 0, 0, 0]


def test_per_type_counters_and_peer_ring_bound():
    """Per-peer rows: by-type maps count each message type, the
    by-type table overflows into "other" past TYPE_CAP, the per-peer
    table evicts oldest past peer_cap, and the send-queue high-water
    cascades peer -> messenger -> perf gauge."""
    led = MsgrLedger(peer_cap=4)
    st = led.register_messenger("osd.0")
    for i in range(6):                      # 6 peers, cap 4
        st.note_send(f"osd.{i + 1}", "MOSDPing", 50, i)
    rows = st.conn_rows()
    assert len(rows) == 4                   # oldest two evicted
    assert {r["peer"] for r in rows} == {"osd.3", "osd.4",
                                         "osd.5", "osd.6"}
    # by-type counting + TYPE_CAP overflow on one peer
    for i in range(TYPE_CAP + 5):
        st.note_send("osd.3", f"MType{i}", 10, 0)
    st.note_recv("osd.3", "MOSDOpReply", 64)
    row = next(r for r in st.conn_rows() if r["peer"] == "osd.3")
    assert row["out_types"]["MOSDPing"] == 1
    assert row["out_types"][OTHER_TYPE] >= 5
    assert len(row["out_types"]) <= TYPE_CAP + 1
    assert row["in_types"] == {"MOSDOpReply": 1}
    assert row["msgs_in"] == 1 and row["bytes_in"] == 64
    # hwm cascade: peer 'osd.6' saw depth 5
    st.note_send("osd.6", "MOSDPing", 50, 9)
    assert st.sendq_hwm == 9
    assert st.perf.dump()["msgr_sendq_hwm"] == 9
    t = st.totals()
    assert t["msgs_out"] == 6 + TYPE_CAP + 5 + 1
    assert t["peers"] == 4
    # set_peer_cap trims live tables through the ledger
    led.set_peer_cap(2)
    assert len(st.conn_rows()) == 2


def test_reactor_lag_probe_event_rule_and_window():
    """The tick-lag rule: every probe moves the histogram and worst
    gauge, but only a probe a FULL interval late counts an event and
    enters the monward window; the pgstats block is None until then
    and carries worst lag/reactor + the conf'd warn threshold after."""
    led = MsgrLedger(probe_interval=0.25, warn_s=1.0)
    led.note_reactor_lag(0, 0.01, interval=0.25)   # healthy
    assert led.lag_events_total == 0
    assert led.pgstats_block() is None              # no EVENT yet
    lat = led.perf.dump_latencies()
    assert lat["lat_msgr_reactor_lag"]["count"] == 1
    led.note_reactor_lag(1, 2.5, interval=0.25)     # starved
    assert led.lag_events_total == 1
    assert led.perf.dump()["msgr_reactor_lag_events"] == 1
    assert led.perf.dump()["msgr_reactor_lag_worst"] >= 2.5
    blk = led.pgstats_block()
    assert blk is not None
    assert blk["worst_lag_s"] == 2.5
    assert blk["worst_reactor"] == 1
    assert blk["lag_events"] == 1
    assert blk["warn_s"] == 1.0
    # quiescent window: the block repr is stable (keepalive dedup)
    assert led.pgstats_block() == blk
    st = led.status()
    assert st["reactors"]["count"] == 2
    assert st["reactors"]["lag_events"] == 1
    assert st["window"] == blk


# -- dispatch-queue timing under a blocked dispatcher ------------------------

def test_dispatch_wait_histograms_under_blocked_dispatcher():
    """Three clients land ops on a server whose dispatcher is blocked:
    the depth gauge climbs past 1 (concurrent handlers wedged in the
    executor), qwait and run-time histograms advance once per message,
    run time shows the block, and depth returns to zero after."""
    MsgrLedger.reset_host()
    server = clients = None
    try:
        ev = threading.Event()
        got = []
        server = Messenger("server")

        def blocked(conn, msg):
            got.append(msg)
            ev.wait(10.0)
        server.add_dispatcher(blocked)
        addr = server.bind(("127.0.0.1", 0))
        led = server.ledger
        assert led is msgr_ledger()
        clients = [Messenger(f"cli{i}") for i in range(3)]
        for i, cli in enumerate(clients):
            cli.connect(addr).send_message(M.MOSDPing(from_osd=i))
        # all three handlers wedge concurrently (separate connections)
        assert _wait(lambda: led._dispatch_pending >= 3, timeout=15.0)
        st = led.status()
        assert st["dispatch"]["pending"] >= 3
        assert st["dispatch"]["hwm"] >= 2
        time.sleep(0.1)                      # measurable run time
        ev.set()
        assert _wait(lambda: led.dispatches_total >= 3, timeout=15.0)
        assert _wait(lambda: led._dispatch_pending == 0, timeout=15.0)
        assert len(got) == 3
        lat = led.perf.dump_latencies()
        assert lat["lat_msgr_qwait"]["count"] >= 3
        assert lat["lat_msgr_dispatch"]["count"] >= 3
        # the blocked handlers' run time is visible in the histogram
        assert lat["lat_msgr_dispatch"]["p99"] >= 0.05
        assert led.perf.dump()["msgr_dispatch_queued"] == 0
        b = led.bench_summary()
        assert b["qwait_ms_p50"] is not None
        assert b["dispatch_ms_p99"] is not None
        assert b["dispatches"] >= 3
    finally:
        for m in (clients or []):
            m.shutdown()
        if server is not None:
            server.shutdown()
        MsgrLedger.reset_host()


# -- reconnect / replay accounting across a wire kill ------------------------

async def _abort_wire(conn):
    conn.session.drop_wire()


async def _abort_wire_and_send(conn, msg):
    # one reactor step: the frame finds the wire gone before the
    # read loop does, so Connection._send re-dials, not _reconnect
    conn.session.drop_wire()
    await conn._send(msg)


@pytest.mark.parametrize("redial_by", ["read_loop", "send"])
def test_reconnect_and_replay_counted_across_wire_kill(redial_by):
    """Hard-abort the LIVE wire mid-burst (the lossless-session test
    shape): delivery stays exactly-once AND the ledger counts the
    reconnect round and the replayed unacked frames, per peer and in
    the messenger totals — whichever of the two re-dials: the read
    loop through _reconnect, or the next frame's _send (a round that
    went uncounted until PR 36).  The kill waits for a delivery: an
    abort while the first dial is still in flight kills nothing."""
    MsgrLedger.reset_host()
    server = client = None
    try:
        got = []
        server = Messenger("server")
        server.add_dispatcher(lambda conn, msg: got.append(msg.from_osd))
        addr = server.bind(("127.0.0.1", 0))
        client = Messenger("client")
        conn = client.connect(addr)
        for i in range(30):
            if i == 16 and redial_by == "send":
                assert _wait(lambda: len(got) >= 1, timeout=15.0)
                client._run_sync(_abort_wire_and_send(
                    conn, M.MOSDPing(from_osd=i)))
                continue
            conn.send_message(M.MOSDPing(from_osd=i))
            if i == 15 and redial_by == "read_loop":
                assert _wait(lambda: len(got) >= 1, timeout=15.0)
                client._run_sync(_abort_wire(conn))
        assert _wait(lambda: len(got) >= 30, timeout=15.0)
        assert got == list(range(30))        # still exactly-once
        t = client.stats.totals()
        assert t["reconnects"] == 1          # the round, counted once
        assert t["replay_frames"] >= 1
        assert t["msgs_out"] == 30
        row = next(r for r in client.stats.conn_rows()
                   if r["peer"] == "server")
        assert row["reconnects"] == 1
        assert row["replay_frames"] >= 1
        assert row["msgs_out"] == 30
        assert row["out_types"]["MOSDPing"] == 30
        assert row["sendq_hwm"] >= 1
    finally:
        if client is not None:
            client.shutdown()
        if server is not None:
            server.shutdown()
        MsgrLedger.reset_host()


# -- ms_sync_timeout ---------------------------------------------------------

def test_run_sync_timeout_conf_and_counted():
    """The sync bridge's timeout is the conf'd ms_sync_timeout (not a
    hardcoded 30 s): an expiry still raises — callers must see the
    fault — but is counted in msgr_sync_timeouts first."""
    MsgrLedger.reset_host()
    m = None
    try:
        m = Messenger("synccli")
        m.sync_timeout = 0.2
        with pytest.raises(FuturesTimeout):
            m._run_sync(asyncio.sleep(5.0))
        assert m.stats.totals()["sync_timeouts"] == 1
        assert m.stats.perf.dump()["msgr_sync_timeouts"] == 1
        # an explicit per-call timeout still overrides the conf
        t0 = time.perf_counter()
        with pytest.raises(FuturesTimeout):
            m._run_sync(asyncio.sleep(5.0), timeout=0.05)
        assert time.perf_counter() - t0 < 2.0
        assert m.stats.totals()["sync_timeouts"] == 2
        # disabled ledger: the expiry still raises, nothing counts
        m.ledger.enabled = False
        with pytest.raises(FuturesTimeout):
            m._run_sync(asyncio.sleep(5.0), timeout=0.05)
        assert m.stats.totals()["sync_timeouts"] == 2
    finally:
        if m is not None:
            m.ledger.enabled = True
            m.shutdown()
        MsgrLedger.reset_host()


# -- ms_async_op_threads -----------------------------------------------------

def test_configure_pool_sizes_reactors():
    """ms_async_op_threads sizes the NEXT pool creation (startup
    semantics).  A subclass with shadowed pool state stands in for a
    fresh process — the main pool (already running) must keep its
    size, which is exactly the documented live-resize rule."""
    class PoolIso(Messenger):
        _loops = []
        _loop_threads = []
        _executor = None
        _next_loop = 0
        _loop_lock = threading.Lock()
        REACTORS = Messenger.REACTORS

    PoolIso.configure_pool(3)
    assert PoolIso.REACTORS == 3
    m = PoolIso("iso")
    try:
        assert len(PoolIso._loops) == 3
        assert Messenger._loops is not PoolIso._loops
        # 0/None keep the configured size (auto fallback untouched)
        PoolIso.configure_pool(0)
        PoolIso.configure_pool(None)
        assert PoolIso.REACTORS == 3
    finally:
        m.shutdown()
        for loop in PoolIso._loops:
            try:
                loop.call_soon_threadsafe(loop.stop)
            except RuntimeError:
                pass


# -- trace stitching ---------------------------------------------------------

def test_slow_send_names_peer_on_op_timeline():
    """An injected dispatch stall delays the frame write; the
    msgr_send(peer) stamp lands AFTER the stall, so stage_durations
    blames the wire stage — "0.3 s in the send path to server" — the
    way device blame already says first_compile(bucket)."""
    from ceph_tpu.common.tracked_op import OpTracker
    MsgrLedger.reset_host()
    server = client = None
    try:
        got = []
        server = Messenger("server")
        server.add_dispatcher(lambda conn, msg: got.append(msg))
        addr = server.bind(("127.0.0.1", 0))
        client = Messenger("client")
        client.inject_dispatch_stall = 0.3
        tracker = OpTracker(enabled=True)
        top = tracker.create("osd_op", "stitched write")
        top.mark_event("queued")
        msg = M.MOSDPing(from_osd=7)
        msg._top = top
        client.connect(addr).send_message(msg)
        assert _wait(lambda: len(got) >= 1, timeout=15.0)
        assert _wait(lambda: any(n == "msgr_send(server)"
                                 for _ts, n in top.events),
                     timeout=10.0)
        stages = dict(top.stage_durations())
        assert stages["msgr_send(server)"] >= 0.25
        # blame picks the wire stage — the acceptance shape
        tracker.complaint_time = 0.05
        tracker.unregister(top, 0)
        assert top.slow
        assert top.blamed_stage == "msgr_send(server)"
        dump = tracker.dump_historic_slow_ops()
        assert any(op.get("blamed_stage") == "msgr_send(server)"
                   for op in dump["ops"])
    finally:
        if client is not None:
            client.shutdown()
        if server is not None:
            server.shutdown()
        MsgrLedger.reset_host()


# -- mon health (unit) -------------------------------------------------------

def test_msgr_reactor_lag_health_unit():
    """The mon's health check, fabricated reports: a `msgr` block
    whose worst_lag_s exceeds its shipped warn_s raises
    MSGR_REACTOR_LAG naming the worst daemon and reactor; under
    threshold stays quiet (the ride-the-report rule — no mon conf)."""
    from ceph_tpu.tools.vstart import Cluster
    with Cluster(n_osds=2) as c:
        mon = c.mon
        base = {"degraded_pgs": 0, "misplaced": 0, "unfound": 0,
                "recovering": 0, "epoch": 1, "pools": {},
                "ts": time.time()}
        with mon.lock:
            mon.pg_stat_reports[0] = dict(
                base, msgr={"window_s": 60.0, "lag_events": 3,
                            "worst_lag_s": 4.2, "worst_reactor": 2,
                            "warn_s": 1.0})
            mon.pg_stat_reports[1] = dict(base)
        _rc, health = mon.handle_command({"prefix": "health"})
        lag = health["checks"].get("MSGR_REACTOR_LAG")
        assert lag is not None
        assert "osd.0" in lag["summary"]
        assert "reactor 2" in lag["summary"]
        assert "4.2" in lag["summary"]
        assert "3 lag events" in lag["detail"][0]
        assert health["status"] == "HEALTH_WARN"
        # under its own threshold: quiet
        with mon.lock:
            mon.pg_stat_reports[0] = dict(
                base, msgr={"window_s": 60.0, "lag_events": 1,
                            "worst_lag_s": 0.6, "worst_reactor": 0,
                            "warn_s": 1.0})
        _rc, health = mon.handle_command({"prefix": "health"})
        assert "MSGR_REACTOR_LAG" not in health["checks"]


# -- cluster: asok + exporter + MPGStats + health round-trip -----------------

def test_cluster_asok_exporter_and_health_roundtrip(tmp_path):
    """Live 4-OSD cluster: exactly one daemon owns the shared ledger
    perf set, `messenger status`/`conn profile` round-trip over the
    asok (including both ceph_cli daemon-mode folds), the exporter
    emits per-daemon ceph_tpu_msgr_* gauges, and an injected reactor
    lag event rides MPGStats to the mon and raises MSGR_REACTOR_LAG
    naming this daemon."""
    from ceph_tpu.tools import ceph_cli
    from ceph_tpu.tools.metrics_exporter import collect
    from ceph_tpu.tools.vstart import Cluster
    MsgrLedger.reset_host()
    try:
        with Cluster(n_osds=4, asok_dir=str(tmp_path)) as c:
            client = c.client()
            client.create_pool("wirepool", "replicated", size=2,
                               pg_num=8)
            io = client.open_ioctx("wirepool")
            rng = np.random.default_rng(20)
            for i in range(8):
                io.write_full(f"w{i}",
                              rng.integers(0, 256, 2000,
                                           dtype=np.uint8).tobytes())
            # the pool predates this ledger (process-wide): re-arm the
            # probes on the current host ledger like a fresh process
            msgr_ledger().attach_reactors(Messenger._loops)
            # exactly one OSD owns the shared perf set
            owners = [o for o in c.osds if o._msgr_reporter]
            assert len(owners) == 1
            perf_owners = [o for o in c.osds
                           if "msgr_ledger" in o.cct.perf.dump()]
            assert perf_owners == owners
            # every daemon registers its own messenger counter set
            for o in c.osds:
                assert o.cct.perf.dump()["msgr"]["msgr_msgs_out"] > 0

            # asok handlers on every daemon
            st = c.osds[1]._asok_messenger_status({})
            assert st["enabled"] and st["osd"] == 1
            assert st["daemon"]["msgs_out"] > 0
            assert st["dispatch"]["total"] > 0
            cp = c.osds[2]._asok_conn_profile({})
            assert cp["osd"] == 2
            rows = cp["messengers"][c.osds[2].messenger.entity]
            assert rows and rows[0]["bytes_out"] + rows[0]["bytes_in"] > 0
            assert any(r["peer"] == "mon" for r in rows)
            capped = c.osds[2]._asok_conn_profile({"last": 2})
            assert len(capped["messengers"][
                c.osds[2].messenger.entity]) <= 2
            # ceph_cli daemon mode folds both two-word prefixes
            asok = str(tmp_path / "osd.0.asok")
            for words in (["messenger", "status"],
                          ["messenger_status"],
                          ["conn", "profile"], ["conn_profile"]):
                assert ceph_cli.daemon_command([asok] + words) == 0, \
                    words

            # reactor probes feed the histogram on the live pool
            led = msgr_ledger()
            assert _wait(
                lambda: led.perf.dump_latencies()[
                    "lat_msgr_reactor_lag"]["count"] > 0,
                timeout=15.0)
            assert led.status()["reactors"]["count"] > 0

            # exporter: per-daemon wire gauges from the msgr perf set
            text = collect(str(tmp_path))
            assert "ceph_tpu_msgr_msgs_out" in text
            assert "ceph_tpu_msgr_bytes_in" in text

            # injected lag event -> MPGStats msgr block -> mon health
            reporter = owners[0]
            reporter.messenger.ledger.note_reactor_lag(
                1, 5.0, interval=0.25)
            blk = reporter._compile_pg_stats().get("msgr")
            assert blk is not None and blk["worst_lag_s"] == 5.0

            def mon_warns():
                _rc, health = c.mon.handle_command({"prefix": "health"})
                return "MSGR_REACTOR_LAG" in health["checks"]
            assert _wait(mon_warns, timeout=30.0)
            _rc, health = c.mon.handle_command({"prefix": "health"})
            lag = health["checks"]["MSGR_REACTOR_LAG"]
            assert f"osd.{reporter.osd_id}" in lag["summary"]
            assert "reactor 1" in lag["summary"]
    finally:
        MsgrLedger.reset_host()


def test_cluster_injected_stall_slow_op_names_wire(tmp_path):
    """The acceptance e2e: ms_inject_dispatch_stall on the primary of
    an EC pool delays the sub-write frame writes; a client write
    latches slow and its dump names the wire stage — the blamed stage
    is msgr_send(osd.N) with the peer on the timeline."""
    from ceph_tpu.tools.vstart import Cluster
    MsgrLedger.reset_host()
    try:
        with Cluster(n_osds=4, asok_dir=str(tmp_path)) as c:
            client = c.client()
            client.set_ec_profile("ws21", {
                "plugin": "jax", "k": "2", "m": "1",
                "technique": "cauchy", "stripe_unit": "1024"})
            client.create_pool("wspool", "erasure",
                               erasure_code_profile="ws21", pg_num=4)
            io = client.open_ioctx("wspool")
            # warm the SAME object: the overwrite path then skips the
            # pre-encode shard read, so the stalled sub_write send is
            # the one dominant interval on the timeline
            io.write_full("ws0", b"w" * 3000)
            pgid = c.mon.osdmap.object_to_pg(
                c.mon.osdmap.lookup_pool("wspool").id, "ws0")
            _, _, _, primary = c.mon.osdmap.pg_to_up_acting_osds(pgid)
            osd = c.osds[primary]
            osd.cct.conf.set("ms_inject_dispatch_stall", "0.4")
            osd.cct.conf.set("osd_op_complaint_time", "0.2")
            assert osd.messenger.inject_dispatch_stall == \
                pytest.approx(0.4)                 # observer applied
            try:
                io.write_full("ws0", b"x" * 3000)
            finally:
                osd.cct.conf.set("ms_inject_dispatch_stall", "0.0")
                osd.cct.conf.set("osd_op_complaint_time", "30.0")

            def wire_blamed():
                dump = osd.op_tracker.dump_historic_slow_ops()
                return any(
                    str(op.get("blamed_stage", "")).startswith(
                        "msgr_send(")
                    for op in dump["ops"])
            assert _wait(wire_blamed, timeout=20.0)
            dump = osd.op_tracker.dump_historic_slow_ops()
            op = next(o for o in dump["ops"]
                      if str(o.get("blamed_stage", "")).startswith(
                          "msgr_send("))
            assert any(e["event"].startswith("msgr_send(osd.")
                       for e in op["events"])
    finally:
        MsgrLedger.reset_host()
