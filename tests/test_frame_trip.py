"""A frame's one-way trip as a partition, the reactors' loops as an
account, frames by message kind (ISSUE 36, docs/TRACING.md "A frame's
trip", "Reactor loops").

What must hold: the eight phases of a sampled frame add up to its
`send called -> handler start` interval exactly, whatever wraps the
frame and wherever its handler runs; the sampling rule gives every
message kind of a cycling session its share and picks the same frames
at both ends; the flight table is bounded and a frame whose sender
left no stamp counts as unpaired, never guessed; a reactor's seconds
are asleep or running and nothing else; frames by kind add up to the
frames written; and with `ms_ledger` false nothing of this moves and
no clock is read.
"""

import collections
import sys
import threading
import time

import pytest

from ceph_tpu.msg import messages as M
from ceph_tpu.msg import msgr_ledger as ledger_mod
from ceph_tpu.msg.messenger import Messenger
from ceph_tpu.msg.msgr_ledger import (FLIGHT_CAP, FRAME_PHASES, FrameTx,
                                      MsgrLedger, ReactorSelector,
                                      frame_sampled, sample_offset)
from ceph_tpu.osd.types import hobject_t, pg_t, spg_t


def _wait(pred, timeout=30.0, step=0.005):
    deadline = time.time() + timeout
    while time.time() < deadline:
        if pred():
            return True
        time.sleep(step)
    return False


class FakeClock:
    """Strictly increasing, in uneven steps, remembering who read it:
    (value, name of the calling function)."""

    def __init__(self):
        self.t = 1_000_000
        self.log = []
        self._lock = threading.Lock()

    def __call__(self) -> int:
        with self._lock:
            self.t += 1_000 + 37 * (len(self.log) % 11)
            self.log.append((self.t, sys._getframe(1).f_code.co_name))
            return self.t


@pytest.fixture
def fresh_ledger():
    MsgrLedger.reset_host()
    yield MsgrLedger.host_instance()
    MsgrLedger.reset_host()


def _mosdop(i: int, size: int = 8192):
    payload = bytes([i % 251]) * size       # compressible
    return M.MOSDOp(spg_t(pg_t(1, 1), 0), hobject_t(1, f"o{i}"),
                    [["write", 0, size]], payload)


def _pair(wrap: str, inline: bool, far: MsgrLedger | None = None):
    """(server, client, conn, handled): a one-way pair, plain or with
    every frame compressed or encrypted; the server's handler runs
    inline on its reactor or on the dispatch executor.  `far`: a
    ledger of its own for the server, as a peer in another process
    has.  The wire is up on return: one MOSDPing has been delivered
    (a session's first frame leaves in the dial's replay and is not
    timed, whatever the rule says of it)."""
    kw = {}
    if wrap == "secure":
        pytest.importorskip("cryptography")
        from ceph_tpu.auth import CephxAuth
        sk = b"\x36" * 16
        kw = {"server": {"auth": CephxAuth("osd.0", service_key=sk),
                         "secure": True},
              "client": {"auth": CephxAuth("osd.1", service_key=sk),
                         "secure": True}}
    handled = []
    server = Messenger("osd.0", **kw.get("server", {}))
    if far is not None:
        server.ledger = far
        server.stats = far.register_messenger(server.entity)
    server.add_dispatcher(lambda conn, msg: handled.append(
        threading.current_thread().name))
    if inline:
        server.fast_dispatch = lambda msg: True
    client = Messenger("osd.1", **kw.get("client", {}))
    if wrap == "compressed":
        server.compress_algo = client.compress_algo = "zlib"
    addr = server.bind(("127.0.0.1", 0))
    conn = client.connect(addr)
    conn.send_message(M.MOSDPing(from_osd=0))
    assert _wait(lambda: handled)
    handled.clear()
    return server, client, conn, handled


def _kind(led: MsgrLedger, kind: str) -> dict:
    return led.frame_rows().get(kind) or {
        "n": 0, "rx_n": 0, "transit_n": 0,
        "ns": dict.fromkeys(FRAME_PHASES + ("drain",), 0)}


# -- (a) the partition --------------------------------------------------------

@pytest.mark.parametrize("inline", [True, False],
                         ids=["inline", "executor"])
@pytest.mark.parametrize("wrap", ["plain", "compressed", "secure"])
def test_phases_partition_send_called_to_handler_start(
        fresh_ledger, wrap, inline):
    led = fresh_ledger
    clock = led.now_ns = FakeClock()
    server, client, conn, handled = _pair(wrap, inline)
    try:
        lo = None
        for i in range(64):
            lo = len(clock.log)
            conn.send_message(_mosdop(i))
            assert _wait(lambda: len(handled) > i)
            if _kind(led, "MOSDOp")["rx_n"]:
                break
        row = _kind(led, "MOSDOp")
        assert (row["n"], row["rx_n"], row["transit_n"]) == (1, 1, 1)
        assert handled[-1].startswith(
            "msgr-reactor-" if inline else "msgr-dispatch")
        if wrap == "compressed":
            assert conn.session.compressed_out >= 1
        if wrap == "secure":
            assert conn.session.secure
        # the two ends of the trip, as the clock handed them out
        reads = clock.log[lo:]
        t_call = [t for t, who in reads if who == "send_message"]
        t_handler = [t for t, who in reads if who == "frame_delivered"]
        assert len(t_call) == 1 and len(t_handler) == 1
        ns = row["ns"]
        assert sum(ns[p] for p in FRAME_PHASES) == \
            t_handler[0] - t_call[0]
        # every anchor was read after the one before it; where the
        # two threads meet the split follows who came first: the
        # header can be in the receiver's hands before the sender is
        # back from its write (`write` then ends there, transit 0),
        # and the sender's stamp can overtake a read that had ended
        for phase in FRAME_PHASES:
            if phase in ("transit", "body_read"):
                assert ns[phase] >= 0, (phase, ns)
            else:
                assert ns[phase] > 0, (phase, ns)
        # beside the partition: drain() returned after the write
        assert _wait(lambda: _kind(led, "MOSDOp")["ns"]["drain"] > 0)
        # the histograms took the same samples (the sender's: this
        # frame's alone; the receiver's may hold the priming ping's)
        dump = led.perf.dump()
        for phase in FRAME_PHASES[:5]:
            h = dump[f"lat_frame_{phase}"]
            assert h["count"] == 1
            assert h["sum"] == pytest.approx(ns[phase] * 1e-9)
        assert dump["lat_frame_drain"]["count"] == 1
        assert dump["frame_ns.MOSDOp.hop"] == ns["hop"]
        assert dump["frame_n.MOSDOp"] == 1
        assert not led._flight              # the slot was claimed
    finally:
        client.shutdown()
        server.shutdown()


@pytest.mark.parametrize("inline", [True, False],
                         ids=["inline", "executor"])
@pytest.mark.parametrize("size", [60 << 10, 1 << 20],
                         ids=["through_the_scratch", "in_place"])
def test_phases_partition_with_the_stamps_taken_in_the_receiver(
        fresh_ledger, size, inline):
    """The receiver reads the clock for `t_head` where it has parsed
    the header and for `t_body` where the last body byte has landed —
    in its scratch, or in the body's own buffer after reads of their
    own: the eight phases still add up to the trip, and every sample
    finds its sender's slot."""
    led = fresh_ledger
    clock = led.now_ns = FakeClock()
    server, client, conn, handled = _pair("plain", inline)
    # (the priming ping left in the dial's replay, unstamped by its
    # sender: the rule may have picked it, and it then found no slot)
    unpaired0 = led.perf.dump()["msgr_frame_samples_unpaired"]
    try:
        lo = None
        for i in range(64):
            lo = len(clock.log)
            conn.send_message(_mosdop(i, size))
            assert _wait(lambda: len(handled) > i)
            if _kind(led, "MOSDOp")["rx_n"]:
                break
        assert _wait(lambda: _kind(led, "MOSDOp")["n"] == 1)
        row = _kind(led, "MOSDOp")
        assert (row["n"], row["rx_n"], row["transit_n"]) == (1, 1, 1)
        reads = clock.log[lo:]
        who = [w for _, w in reads]
        t_call = [t for t, w in reads if w == "send_message"]
        t_handler = [t for t, w in reads if w == "frame_delivered"]
        ns = row["ns"]
        assert sum(ns[p] for p in FRAME_PHASES) == \
            t_handler[0] - t_call[0]
        assert all(ns[p] >= 0 for p in FRAME_PHASES), ns
        # where the two receive stamps were read
        assert who.count("_stamp_head") == 1
        landed = "_large_done" if size > (64 << 10) else "_cut_frames"
        assert who.count(landed) == 1
        assert who.index("_stamp_head") < who.index(landed)
        d = led.perf.dump()
        assert d["msgr_frame_samples_unpaired"] == unpaired0
        assert d["msgr_large_bodies"] == \
            (i + 1 if size > (64 << 10) else 0)
    finally:
        client.shutdown()
        server.shutdown()


def test_send_batch_reads_the_hop_start_once_for_the_batch(fresh_ledger):
    led = fresh_ledger
    clock = led.now_ns = FakeClock()
    server, client, conn, handled = _pair("plain", inline=True)
    try:
        n = 48
        client.send_batch([(conn, _mosdop(i, 64)) for i in range(n)])
        assert _wait(lambda: len(handled) >= n)
        assert sum(1 for _, who in clock.log
                   if who == "send_batch") == 1
        row = _kind(led, "MOSDOp")
        assert row["n"] == row["rx_n"] == row["transit_n"] >= 1
        # each later frame of the batch waited for the ones before it
        assert row["ns"]["hop"] > 0
    finally:
        client.shutdown()
        server.shutdown()


# -- (b) the sampling rule ----------------------------------------------------

@pytest.mark.parametrize("kinds", [2, 3, 4, 16])
@pytest.mark.parametrize("nonce", ["", "a3f0c4d2e1b7", "0123456789ab",
                                   "ffffffffffff"])
def test_every_kind_of_a_cycling_session_gets_its_share(kinds, nonce):
    """4,096 frames of a session that cycles through `kinds` message
    kinds: each kind gets 1/16 of its frames sampled, give or take a
    third (`seq % 16` would give one kind all and the others none)."""
    off = sample_offset(nonce)
    picked = collections.Counter(
        seq % kinds for seq in range(1, 4097)
        if frame_sampled(seq, off))
    want = 4096 / kinds / 16
    for kind in range(kinds):
        assert abs(picked[kind] - want) <= want / 3, (kind, picked)
    assert abs(sum(picked.values()) - 256) <= 2


def test_both_ends_pick_the_same_frames_of_an_alternating_session(
        fresh_ledger):
    """A session that strictly alternates two kinds, 4,096 frames
    through real messengers: sender and receiver sample the same
    frames (every sample pairs), and each kind gets its share."""
    led = fresh_ledger
    server, client, conn, handled = _pair("plain", inline=True)
    try:
        ping0 = _kind(led, "MOSDPing")      # the priming frame's half
        unpaired0 = led.perf.dump()["msgr_frame_samples_unpaired"]
        n = 4096
        for i in range(n):
            conn.send_message(M.MOSDPing(from_osd=i) if i % 2
                              else _mosdop(i, 64))
        assert _wait(lambda: len(handled) >= n, 60)
        ping, op = _kind(led, "MOSDPing"), _kind(led, "MOSDOp")
        for row, base in ((ping, ping0["rx_n"]), (op, 0)):
            assert row["n"] == row["rx_n"] - base == row["transit_n"]
            assert abs(row["n"] - 128) <= 128 / 3, (ping, op)
        assert led.perf.dump()["msgr_frame_samples_unpaired"] == \
            unpaired0
        assert not led._flight
    finally:
        client.shutdown()
        server.shutdown()


# -- (c) the flight table -----------------------------------------------------

def test_flight_table_is_bounded_and_counts_what_falls_out():
    led = MsgrLedger()
    for seq in range(FLIGHT_CAP + 10):
        tx = FrameTx("MOSDOp", ("nonce", True, seq), 1, 2, 3)
        tx.t_enc = 4
        led.frame_depart(tx)
        led.frame_sent(tx)
    assert len(led._flight) == FLIGHT_CAP
    assert led.perf.dump()["msgr_frame_stamps_evicted"] == 10
    # the oldest went: its receiver finds nothing and says so
    slot, t_arr = led.frame_claim(("nonce", True, 0), 10)
    assert slot is None and t_arr == 10
    led.frame_delivered(slot, "MOSDOp", t_arr, 20, 30)
    d = led.perf.dump()
    assert d["msgr_frame_samples_unpaired"] == 1
    assert d["lat_frame_transit"]["count"] == 0
    assert d["lat_frame_body_read"]["count"] == 1
    assert d["frame_rx_n.MOSDOp"] == 1
    assert d["frame_transit_n.MOSDOp"] == 0
    assert len(led._flight) == FLIGHT_CAP


@pytest.mark.parametrize("order,want", [
    # the write returned at 50, the header arrived at 70
    ("sender_first", {"write": 50 - 4, "transit": 20, "body_read": 10,
                      "decode": 10}),
    # the receiver had header (70), body and claim before the sender
    # came back at 100: `write` ends at the arrival, no transit
    ("receiver_first", {"write": 70 - 4, "transit": 0, "body_read": 10,
                        "decode": 10}),
    # the sender came back at 75, between the header's arrival (70)
    # and the claim: it counted up to 75, the receiver starts there
    ("sender_overtook", {"write": 75 - 4, "transit": 0, "body_read": 5,
                         "decode": 10}),
])
def test_where_the_two_ends_meet_the_split_is_exact(order, want):
    """Whoever comes first, the phases stay non-negative and add up
    to handler start - send called."""
    clock = iter({"sender_first": [50, 95],
                  "receiver_first": [95, 100],
                  "sender_overtook": [75, 95]}[order])
    led = MsgrLedger()
    led.now_ns = lambda: next(clock)
    tx = FrameTx("MOSDOp", ("nonce", True, 7), 1, 2, 3)
    tx.t_enc = 4
    led.frame_depart(tx)
    if order != "receiver_first":
        led.frame_sent(tx)
    slot, t_arr = led.frame_claim(tx.key, 70)
    led.frame_delivered(slot, "MOSDOp", t_arr, 80, 90)    # at 95
    if order == "receiver_first":
        led.frame_sent(tx)                                # at 100
    row = led.frame_rows()["MOSDOp"]
    assert (row["n"], row["rx_n"], row["transit_n"]) == (1, 1, 1)
    ns = row["ns"]
    assert {p: ns[p] for p in want} == want
    assert min(ns.values()) >= 0
    assert sum(ns[p] for p in FRAME_PHASES) == 95 - 1
    assert led.perf.dump()["msgr_frame_samples_unpaired"] == 0
    assert not led._flight


def test_a_peer_with_another_ledger_records_no_transit(fresh_ledger):
    """A receiver that does not share the sender's table — as a peer
    in another process does not — records its own three phases, no
    transit, and counts the frame unpaired; the sender's stamps wait
    in its bounded table."""
    led = fresh_ledger
    far = MsgrLedger()
    server, client, conn, handled = _pair("plain", inline=True, far=far)
    try:
        n = 256
        for i in range(n):
            conn.send_message(_mosdop(i, 64))
        assert _wait(lambda: len(handled) >= n)
        near, there = _kind(led, "MOSDOp"), _kind(far, "MOSDOp")
        assert near["n"] == there["rx_n"] >= 8
        assert near["rx_n"] == 0 and there["n"] == 0
        assert there["transit_n"] == 0 and there["ns"]["transit"] == 0
        assert there["ns"]["decode"] > 0
        ping = _kind(far, "MOSDPing")["rx_n"]       # the priming frame
        assert far.perf.dump()["msgr_frame_samples_unpaired"] == \
            there["rx_n"] + ping
        assert far.perf.dump()["lat_frame_transit"]["count"] == 0
        assert len(led._flight) == near["n"]
    finally:
        client.shutdown()
        server.shutdown()


# -- (d) reactor loops, frames by kind ----------------------------------------

def test_reactor_selector_accounts_every_second_once(fresh_ledger):
    """One selector with an injected clock: a poll costs no reading, a
    sleep two; asleep + running is everything since the loop started;
    the interval in progress is added to the side the loop is on."""
    ticks = iter(range(100, 10_000, 100))
    sel = ReactorSelector()
    sel._clock = lambda: next(ticks)
    try:
        sel.loop_started()                  # 100
        sel.select(0)                       # a poll
        assert (sel.iterations, sel.sleeps) == (1, 0)
        sel.select(0.001)                   # 200 .. 300
        assert (sel.iterations, sel.sleeps) == (2, 1)
        assert (sel.run_ns, sel.select_ns) == (100, 100)
        assert sel.account() == pytest.approx((100e-9, 200e-9))  # at 400
        sel.select(0.001)                   # 500 .. 600
        assert (sel.run_ns, sel.select_ns) == (300, 200)
        fresh_ledger.enabled = False
        sel.select(0.001)
        sel.select(0)
        assert (sel.iterations, sel.sleeps) == (3, 2)
        assert (sel.run_ns, sel.select_ns) == (300, 200)
        # the account has a gap, and resumes at the next stamp
        fresh_ledger.enabled = True
        sel.select(0.001)                   # 700 .. 800
        assert (sel.run_ns, sel.select_ns) == (300, 300)
    finally:
        sel.close()


def test_reactor_rows_and_frames_by_kind_after_a_cluster_write(
        fresh_ledger):
    from ceph_tpu.tools.vstart import Cluster
    led = fresh_ledger
    # a ledger made after the pool was: the meters are the pool's
    Messenger._ensure_pool()
    assert len(MsgrLedger.reactor_meters) == len(Messenger._loops)
    with Cluster(n_osds=3) as c:
        client = c.client()
        client.create_pool("fp", "replicated", pg_num=4)
        io = client.open_ioctx("fp")
        for i in range(8):
            io.write_full(f"obj{i}", b"x" * 4096)
        owner = next(o for o in c.osds if o._msgr_reporter)
        live = owner.cct.perf.dump()["msgr_ledger"]
        status = owner._asok_messenger_status({})
    for i in range(len(Messenger._loops)):
        for key in ("reactor_wall_s", "reactor_select_s",
                    "reactor_cpu_s", "reactor_sleeps",
                    "reactor_iterations"):
            assert f"{key}.{i}" in live
        assert 0 <= live[f"reactor_select_s.{i}"] <= \
            live[f"reactor_wall_s.{i}"]
        assert live[f"reactor_sleeps.{i}"] <= \
            live[f"reactor_iterations.{i}"]
    rows = status["reactors"]["loops"]
    assert [r["reactor"] for r in rows] == \
        list(range(len(Messenger._loops)))
    for r in rows:
        assert r["select_s"] + r["running_s"] == \
            pytest.approx(r["wall_s"], abs=2e-6)
        assert r["sleeps"] <= r["iterations"]
        assert 0 <= r["stalled_s"] <= r["running_s"]
    assert sum(r["sleeps"] for r in rows) > 0
    assert "MOSDOp" in status["frames"]["out_by_type"]
    # the cluster is down: nothing is written between these reads
    d = led.perf.dump()
    by_kind = {k.split(".", 1)[1]: v for k, v in d.items()
               if k.startswith("msgr_frames_out_by_type.")}
    assert by_kind.pop("CTRL_ACK") == d["msgr_acks_out"]
    assert by_kind.pop("CTRL_HELLO") > 0
    assert sum(by_kind.values()) == d["msgr_frames_out"] > 0
    for kind in ("MOSDOp", "MOSDOpReply"):
        assert by_kind.get(kind, 0) >= 8, by_kind


# -- (f) the ledger off -------------------------------------------------------

def test_ledger_off_reads_no_clock_and_moves_no_key(fresh_ledger):
    led = fresh_ledger
    led.enabled = False
    clock = led.now_ns = FakeClock()

    def new_keys():
        return {k: v for k, v in led.perf.dump().items()
                if k.startswith(("lat_frame_", "frame_", "reactor_",
                                 "msgr_frames_out_by_type.",
                                 "msgr_frame_", "msgr_rx_",
                                 "msgr_large_"))}

    server, client, conn, handled = _pair("plain", inline=False)
    try:
        before = new_keys()
        for i in range(64):
            conn.send_message(_mosdop(i, 64))
        client.send_batch([(conn, _mosdop(i, 64)) for i in range(8)])
        conn.send_message(_mosdop(99, 1 << 20))  # a body in place
        assert _wait(lambda: len(handled) >= 73)
        assert clock.log == []
        assert new_keys() == before
        assert not any(k.startswith("reactor_") for k in before)
        assert not led._flight and not led.frame_rows()
    finally:
        client.shutdown()
        server.shutdown()


def test_rule_constants_are_what_the_docs_say():
    assert ledger_mod.SAMPLE_ONE_IN == 16
    assert FRAME_PHASES == ("hop", "sendlock", "encode", "write",
                            "transit", "body_read", "decode",
                            "to_handler")


# -- one frame, followed through a profiler trace -----------------------------

def test_trace_rows_carry_type_and_seq_at_both_ends(fresh_ledger,
                                                    tmp_path):
    """`msgr.send` (both rows), `msgr.decode` and the inline
    `msgr.dispatch.<Type>` row of one frame share its type and seq, so
    the frame can be followed from the sender's reactor to the
    receiver's in the host plane of a profiler trace."""
    import glob

    import jax
    from jax.profiler import ProfileData

    from ceph_tpu.common import spans
    server, client, conn, handled = _pair("plain", inline=True)
    try:
        jax.profiler.start_trace(str(tmp_path))
        try:
            time.sleep(0.006)
            with spans.span("ask_jax_again"):           # sets tracing_now
                pass
            assert spans.tracing_now
            for i in range(1, 4):
                conn.send_message(M.MOSDPing(from_osd=i))
            assert _wait(lambda: len(handled) >= 3)
        finally:
            jax.profiler.stop_trace()
    finally:
        client.shutdown()
        server.shutdown()
        spans.reset()
    (path,) = glob.glob(str(tmp_path / "plugins/profile/*/*.xplane.pb"))
    rows = collections.defaultdict(list)
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for ev in line.events:
                if ev.name.startswith("msgr."):
                    rows[ev.name].append(dict(ev.stats))
    for seq in (2, 3, 4):
        sends = [r for r in rows["msgr.send"] if r.get("seq") == seq]
        assert len(sends) == 2                  # encode, socket write
        assert {r["type"] for r in sends} == {"MOSDPing"}
        (dec,) = [r for r in rows["msgr.decode"]
                  if r.get("seq") == seq]
        assert dec["type"] == "MOSDPing"
        assert [r for r in rows["msgr.dispatch.MOSDPing"]
                if r.get("seq") == seq]
