"""Wire-plane flight recorder (docs/TRACING.md "Wire plane").

The device plane attributes every launch (ops/profiler.py), the
control plane every PG transition (osd/pg_ledger.py); this is the
same discipline applied to the layer that connects them — the async
messenger.  PR 14's notes report the shared reactor pool's RT
intermittently exceeding 10 s right after boot with no counter that
names why; ROADMAP item 4 (recovery fan-out at 128-256 OSDs) needs
per-peer wire accounting to be diagnosable at all.  The reference
instruments exactly this layer (AsyncMessenger worker + DispatchQueue
perf counters, Throttle accounting); this module re-expresses that
surface on the asyncio reactor pool:

* **Per-connection ledger** — every frame sent/received lands in a
  bounded per-peer table (oldest peer evicted, ring-style): msgs and
  bytes in/out by message TYPE (bounded by-type dicts, overflow under
  "other"), send-queue depth high-water (len(sess.unacked) at send),
  reconnects, replayed frames, compressed/encrypted wire bytes.
  Surfaced by the `messenger status` / `conn profile` asoks on every
  daemon (tools/ceph_cli.py daemon mode).

* **Reactor health** — a per-reactor loop-lag probe: a callback
  rescheduling itself every ms_reactor_lag_interval seconds measures
  scheduled-vs-actual fire time (the OSD heartbeat tick-lag detector's
  rule: the gauge moves every tick, an EVENT counts only when the
  probe fired a FULL extra interval late).  Lag samples feed
  `lat_msgr_reactor_lag`; events enter a bounded window that ships
  monward.  The dispatch executor is timed the same way: submit->run
  wait in `lat_msgr_qwait`, handler run in `lat_msgr_dispatch`, both
  on the shared DEFAULT_LAT_BUCKETS axis so `dump_latencies`, the
  exporter's percentile gauges and the load harness pick them up
  unchanged — "reactor starved" vs "dispatcher slow" vs "peer slow"
  becomes attributable.

* **Trace stitching** — the send path stamps `msgr_send(peer)` onto
  tracked ops riding a frame (msg._top), and the OSD ingest path
  stamps `msgr_recv_lag`, so slow-op blame can say "5.1 s in the send
  queue to osd.7" the way it already says "waited on first-compile of
  bucket X" (Dapper-style stitching, Sigelman et al. 2010; tail
  blame, Dean & Barroso 2013).

* **Aggregation upward** — pgstats_block() rides MPGStats to the mon
  (MSGR_REACTOR_LAG health warning naming the worst daemon/reactor),
  bench_summary() embeds in cluster_bench --scale rows beside
  recovery_blame, and the per-messenger counter set registers into
  each daemon's perf collection for ceph_tpu_msgr_* exporter gauges.

* **A frame's trip** — one data frame in SAMPLE_ONE_IN, chosen by a
  rule of its (session, seq) that sender and receiver evaluate alike
  (`frame_sampled`: nothing travels), has its one-way trip cut into
  FRAME_PHASES on one clock: `send_message` called -> on the reactor
  (`hop`) -> session send lock held (`sendlock`) -> encoded and
  retained (`encode`) -> handed to the transport (`write`) -> header
  parsed by the receiver (`transit`) -> body landed (`body_read`) ->
  decoded (`decode`) -> the handler's first line (`to_handler`).  The
  phases partition the trip; each is a `lat_frame_<phase>` histogram
  and, by message type, a dotted key of the set
  (`frame_ns.<Type>.<phase>`).  The two ends meet in a bounded
  in-process table (`frame_depart` / `frame_claim`); a receiver that
  finds no slot there (the peer is another process) records no
  transit and counts `msgr_frame_samples_unpaired`.

* **Reactor loops** — the pool's loops run on a selector that times
  itself (`ReactorSelector`): per reactor, seconds asleep in `select`,
  seconds running between two sleeps, wake-ups, iterations, and at
  dump time the thread's CPU from /proc.  running - cpu is the time a
  reactor had work and was not on a CPU (waiting for the GIL, or
  preempted).

* **Always on, null when off** — enabled by default (conf ms_ledger);
  disabled, every entry point returns after ONE attribute check and
  allocates nothing (the NULL_TRACKED rule).  What the on path costs
  is measured on the chip (PERF.md section 6).

Perf-owner rule: the process-wide ledger's perf set (reactor lag +
dispatch histograms — the reactors and executor are shared by every
in-process daemon) registers into exactly ONE daemon's collection via
the `_perf_registered` attribute check (the DeviceProfiler pattern);
that daemon ships the monward block.  Each Messenger's OWN counter
set (MsgrStats) is per-instance, so every daemon exports its own wire
totals without n_daemons-fold inflation.
"""

from __future__ import annotations

import collections
import selectors
import threading
import time
import zlib

from ..common import spans
from ..common.perf_counters import PerfCounters, PerfCountersBuilder

# per-peer by-type maps are bounded: past this many distinct message
# type names, further types count under "other" (a fuzzer or a newer
# peer's unknown types must not grow the table)
TYPE_CAP = 32
OTHER_TYPE = "other"


# A data frame's one-way trip, cut where the clock is read (module
# doc); in this order the phases partition it.
FRAME_PHASES = ("hop", "sendlock", "encode", "write",
                "transit", "body_read", "decode", "to_handler")
_TX_PHASES = FRAME_PHASES[:4]        # the sender's own
_RX_PHASES = FRAME_PHASES[5:]        # the receiver's own
# a by-type row: [sender halves, receiver halves, transits, ns per
# phase..., drain ns] — drain (write end -> `drain()` returned: how
# long the session's send lock stays held past the write) lies beside
# the partition, not in it
_ROW_PHASE0 = 3
_ROW_TRANSIT = _ROW_PHASE0 + len(_TX_PHASES)
_ROW_DRAIN = _ROW_PHASE0 + len(FRAME_PHASES)
# one data frame in this many is timed
SAMPLE_ONE_IN = 16
# write-end stamps kept for receivers that have not come for them yet
FLIGHT_CAP = 1024


def frame_sampled(seq: int, off: int = 0) -> bool:
    """Is frame `seq` of a session (whose `sample_offset` is `off`)
    one of the SAMPLE_ONE_IN that are timed?  Both ends ask this of
    the seq the frame carries, so they agree with nothing on the wire.
    A multiplicative (Weyl) hash of seq, not `seq % 16`: a session
    that alternates two message kinds — or cycles through 3, 4 or 16 —
    still gives each kind its share (tests/test_frame_trip.py)."""
    return ((seq + off) * 0x6A09E667) & 0xFFFFFFFF < 0x10000000


def sample_offset(nonce: str) -> int:
    """Where in the rule's cycle a session starts: from its nonce,
    which both ends hold, so that the k+m sessions of one fan-out do
    not all pick the same op's frames."""
    return zlib.crc32(nonce.encode())


class FrameTx:
    """The sender's stamps of one sampled frame, made by
    `Connection._seal` once the frame has its seq: message type, the
    flight-table key (session nonce, sender is the connector, seq) and
    the clock at `send_message` called, the send queue reached the
    frame, send lock held (the same reading for a frame written in
    place), encode done; `entry` is the flight-table slot that takes
    the write-end stamp."""

    __slots__ = ("mtype", "key", "t_call", "t_in", "t_lock", "t_enc",
                 "entry")

    def __init__(self, mtype: str, key: tuple, t_call: int, t_in: int,
                 t_lock: int):
        self.mtype = mtype
        self.key = key
        self.t_call = t_call
        self.t_in = t_in
        self.t_lock = t_lock
        self.t_enc = 0
        self.entry = None


def trip_means(row: dict) -> dict:
    """One `MsgrLedger.frame_rows` row (or the difference of two) as
    mean microseconds per phase, each over the halves that recorded
    it: the sender's, the receiver's, for `transit` those that met."""
    over = dict.fromkeys(_TX_PHASES + ("drain",), row["n"])
    over.update(dict.fromkeys(_RX_PHASES, row["rx_n"]))
    over["transit"] = row["transit_n"]
    return {"n": row["n"], "rx_n": row["rx_n"],
            "transit_n": row["transit_n"],
            "mean_us": {p: round(ns / over[p] / 1e3, 3)
                        for p, ns in row["ns"].items() if over[p] > 0}}


class _LedgerCounters(PerfCounters):
    """The ledger's set plus the rows it renders only when dumped
    (by message type, by reactor): the hot path adds to plain lists
    and never builds a key string."""

    rows = None     # (the plain dump) -> {key: number}; the ledger's

    def dump(self) -> dict:
        out = super().dump()
        if self.rows is not None:
            out.update(self.rows(out))
        return out

    def schema(self) -> dict:
        out = super().schema()
        if self.rows is not None:
            for key, val in self.rows(super().dump()).items():
                out[key] = "gauge" if isinstance(val, float) else "u64"
        return out


class ReactorSelector(selectors.DefaultSelector):
    """The selector of one reactor loop, timing itself: every second
    since the loop started is either asleep in `select` or running
    (callbacks, coroutine steps, polls that could not block), so
    `select_ns + run_ns` is the loop's wall time and nothing is a
    difference.  Two clock reads and a few adds per sleep, none for a
    poll; while the process's ledger is off, one check and nothing
    counted (the account then has a gap and resumes at the next
    stamp).  `account()` is read from other threads at dump time."""

    _clock = staticmethod(time.perf_counter_ns)

    def __init__(self):
        super().__init__()
        self.native_id = 0      # the loop thread's, for /proc
        self.select_ns = 0      # asleep in select
        self.run_ns = 0         # between two sleeps
        self.sleeps = 0         # selects that were allowed to block
        self.iterations = 0     # every select, polls too
        self._last_ns = 0       # end of the last counted interval
        self._asleep_since = 0  # nonzero while blocked in select

    def loop_started(self) -> None:
        """Called by the loop's own thread before `run_forever`."""
        self.native_id = threading.get_native_id()
        self._last_ns = self._clock()

    def select(self, timeout=None):
        led = MsgrLedger._host
        if led is None or not led.enabled:
            self._last_ns = 0
            return super().select(timeout)
        self.iterations += 1
        if timeout is not None and timeout <= 0:
            return super().select(timeout)
        t0 = self._clock()
        if self._last_ns:
            self.run_ns += t0 - self._last_ns
        self._asleep_since = t0
        try:
            return super().select(timeout)
        finally:
            t1 = self._last_ns = self._clock()
            self._asleep_since = 0
            self.select_ns += t1 - t0
            self.sleeps += 1

    def account(self) -> tuple[float, float]:
        """(seconds asleep, seconds running) up to now: the interval
        in progress is added to the side the loop is on."""
        asleep, running = self.select_ns, self.run_ns
        since, last, now = self._asleep_since, self._last_ns, \
            self._clock()
        if since:
            asleep += max(0, now - since)
        elif last:
            running += max(0, now - last)
        return asleep * 1e-9, running * 1e-9


def _build_ledger_perf(name: str = "msgr_ledger"):
    """The process-shared set: reactor + dispatch-executor health
    (registered into ONE daemon per process — see module doc)."""
    b = _ledger_perf_builder(name)
    return _LedgerCounters(b.name, b._counters)


def _ledger_perf_builder(name: str) -> PerfCountersBuilder:
    b = (PerfCountersBuilder(name)
         .add_u64_counter("msgr_dispatches",
                          "handler runs completed through the "
                          "shared dispatch executor")
         .add_u64_counter("msgr_reactor_lag_events",
                          "reactor lag probes that fired a FULL "
                          "extra interval late (the tick-lag rule)")
         .add_u64_counter("msgr_frames_out",
                          "data frames handed to a transport "
                          "(every messenger of the process; "
                          "replayed frames count again)")
         .add_u64_counter("msgr_socket_writes",
                          "calls that handed bytes to a transport: "
                          "data frames, stand-alone acks, replays")
         .add_u64_counter("msgr_acks_out",
                          "CTRL_ACK frames written on their own")
         .add_u64_counter("msgr_acks_piggybacked",
                          "CTRL_ACK frames that rode a data "
                          "frame's write")
         .add_u64_counter("msgr_sends_inline",
                          "data frames a reactor's send-queue flush "
                          "wrote in place (Connection._send_now)")
         .add_u64_counter("msgr_sends_slow",
                          "data frames the flush handed to the "
                          "coroutine send path (Connection._send)")
         .add_u64_counter("msgr_send_wakes",
                          "signals to a reactor's send queue from "
                          "another thread (call_soon_threadsafe)")
         .add_u64_counter("msgr_rx_reads",
                          "reads that brought bytes from a socket "
                          "(FrameReceiver.buffer_updated calls; every "
                          "messenger of the process)")
         .add_u64_counter("msgr_large_bodies",
                          "frame bodies longer than JOIN_UP_TO, "
                          "received in place in a buffer of their own")
         .add_u64_counter("msgr_large_body_reads",
                          "reads that landed in such a body, the one "
                          "that carried its prefix behind the header "
                          "included")
         .add_u64_counter("msgr_large_body_bytes",
                          "bytes of those bodies (meta + data + crc)")
         .add_u64_counter("msgr_frame_samples_unpaired",
                          "sampled frames delivered whose sender "
                          "left no write-end stamp here (another "
                          "process, a replay): no transit recorded")
         .add_u64_counter("msgr_frame_stamps_evicted",
                          "write-end stamps that fell out of the "
                          "flight table unclaimed (peer in another "
                          "process, dead wire, filtered frame)")
         .add_gauge("msgr_dispatch_queued",
                    "dispatch-executor submissions currently "
                    "queued or running")
         .add_gauge("msgr_dispatch_queued_hwm",
                    "high-water of msgr_dispatch_queued")
         .add_gauge("msgr_reactor_lag_worst",
                    "worst last-probe loop lag across reactors "
                    "(seconds)")
         .add_histogram("lat_msgr_reactor_lag",
                        "per-probe reactor loop lag "
                        "(scheduled vs actual fire time)")
         .add_histogram("lat_msgr_qwait",
                        "dispatch-executor queue wait "
                        "(submit -> handler start)")
         .add_histogram("lat_msgr_dispatch",
                        "dispatch handler run time"))
    for phase, what in zip(FRAME_PHASES, (
            "send_message called -> the reactor's send queue has "
            "reached the frame",
            "-> the session's send lock held (0 for a frame written "
            "in place)",
            "-> encode_parts + record_out done",
            "-> the transport took the frame (_write_once returned)",
            "-> the receiver has parsed the header (in-process "
            "peers only)",
            "-> the body's last byte has landed",
            "-> Message.decode done (unwrap included)",
            "-> the handler's first line (inline or on the "
            "executor)")):
        b.add_histogram(f"lat_frame_{phase}",
                        f"sampled data frames, 1 in {SAMPLE_ONE_IN}: "
                        + what)
    return b.add_histogram("lat_frame_drain",
                           "sampled data frames: write end -> "
                           "writer.drain() returned (beside the "
                           "phases, not one of them)")


def _build_msgr_perf(name: str = "msgr"):
    """One Messenger instance's counter set — registered into ITS
    daemon's collection (per-daemon ceph_tpu_msgr_* exporter gauges)."""
    return (PerfCountersBuilder(name)
            .add_u64_counter("msgr_msgs_out", "messages sent")
            .add_u64_counter("msgr_msgs_in", "messages received")
            .add_u64_counter("msgr_bytes_out", "frame bytes sent")
            .add_u64_counter("msgr_bytes_in", "frame bytes received")
            .add_u64_counter("msgr_reconnects",
                             "reconnect rounds entered after a wire "
                             "fault")
            .add_u64_counter("msgr_replay_frames",
                             "retained frames replayed to a resumed "
                             "session")
            .add_u64_counter("msgr_sync_timeouts",
                             "_run_sync bridge calls that expired "
                             "(conf ms_sync_timeout)")
            .add_u64_counter("msgr_compress_bytes",
                             "wire bytes written through the "
                             "compression wrap")
            .add_u64_counter("msgr_encrypt_bytes",
                             "wire bytes written through the AES-GCM "
                             "wrap")
            .add_gauge("msgr_sendq_hwm",
                       "send-queue (unacked window) depth high-water "
                       "across peers")
            .create_perf_counters())


def _type_inc(table: dict, mtype: str, by: int = 1) -> None:
    n = table.get(mtype)
    if n is None and len(table) >= TYPE_CAP:
        mtype = OTHER_TYPE
        n = table.get(mtype)
    table[mtype] = (n or 0) + by


class ConnStats:
    """One peer's wire accounting (bounded table entry, see module
    doc).  Mutated with plain attribute updates under the GIL, like
    perf counters — the hot-path writers are single updates."""

    __slots__ = ("peer", "msgs_out", "msgs_in", "bytes_out", "bytes_in",
                 "out_types", "in_types", "sendq_hwm", "reconnects",
                 "replay_frames", "compress_bytes", "encrypt_bytes",
                 "first_ts", "last_ts")

    def __init__(self, peer: str):
        self.peer = peer
        self.msgs_out = 0
        self.msgs_in = 0
        self.bytes_out = 0
        self.bytes_in = 0
        self.out_types: dict[str, int] = {}
        self.in_types: dict[str, int] = {}
        self.sendq_hwm = 0
        self.reconnects = 0
        self.replay_frames = 0
        self.compress_bytes = 0
        self.encrypt_bytes = 0
        self.first_ts = time.time()
        self.last_ts = self.first_ts

    def to_dict(self) -> dict:
        return {
            "peer": self.peer,
            "msgs_out": self.msgs_out,
            "msgs_in": self.msgs_in,
            "bytes_out": self.bytes_out,
            "bytes_in": self.bytes_in,
            "out_types": dict(self.out_types),
            "in_types": dict(self.in_types),
            "sendq_hwm": self.sendq_hwm,
            "reconnects": self.reconnects,
            "replay_frames": self.replay_frames,
            "compress_bytes": self.compress_bytes,
            "encrypt_bytes": self.encrypt_bytes,
            "first_ts": round(self.first_ts, 3),
            "last_ts": round(self.last_ts, 3),
        }


class MsgrStats:
    """One Messenger's ledger slice: its own perf set plus the bounded
    per-peer table.  Every entry point is called BEHIND the ledger's
    enabled check (the messenger hooks gate on it), so there is no
    second gate here."""

    def __init__(self, name: str, ledger: "MsgrLedger", perf=None,
                 peer_cap: int = 256):
        self.name = name
        self.ledger = ledger
        self.perf = perf if perf is not None else _build_msgr_perf()
        self.peer_cap = max(1, int(peer_cap))
        self._lock = threading.Lock()
        # insertion-ordered, oldest evicted past peer_cap: the bounded
        # per-peer "ring" (a churny client swarm must not grow it)
        self._peers: collections.OrderedDict[str, ConnStats] = \
            collections.OrderedDict()
        self.sendq_hwm = 0
        self.sync_timeouts = 0
        # which loop of the pool the messenger is pinned to (it sets
        # this once it has picked one)
        self.reactor: int | None = None

    def _peer(self, key: str) -> ConnStats:
        p = self._peers.get(key)
        if p is None:
            with self._lock:
                p = self._peers.get(key)
                if p is None:
                    p = ConnStats(key)
                    self._peers[key] = p
                    while len(self._peers) > self.peer_cap:
                        self._peers.popitem(last=False)
        return p

    # -- hot-path entry points ----------------------------------------------

    def note_send(self, peer: str, mtype: str, nbytes: int,
                  sendq_depth: int) -> None:
        p = self._peer(peer)
        p.msgs_out += 1
        p.bytes_out += nbytes
        _type_inc(p.out_types, mtype)
        p.last_ts = time.time()
        if sendq_depth > p.sendq_hwm:
            p.sendq_hwm = sendq_depth
            if sendq_depth > self.sendq_hwm:
                self.sendq_hwm = sendq_depth
                self.perf.set("msgr_sendq_hwm", sendq_depth)
        self.perf.inc("msgr_msgs_out")
        self.perf.inc("msgr_bytes_out", nbytes)

    def note_recv(self, peer: str, mtype: str, nbytes: int) -> None:
        p = self._peer(peer)
        p.msgs_in += 1
        p.bytes_in += nbytes
        _type_inc(p.in_types, mtype)
        p.last_ts = time.time()
        self.perf.inc("msgr_msgs_in")
        self.perf.inc("msgr_bytes_in", nbytes)

    def note_wrapped(self, peer: str, nbytes: int, compressed: bool,
                     encrypted: bool) -> None:
        p = self._peer(peer)
        if compressed:
            p.compress_bytes += nbytes
            self.perf.inc("msgr_compress_bytes", nbytes)
        if encrypted:
            p.encrypt_bytes += nbytes
            self.perf.inc("msgr_encrypt_bytes", nbytes)

    def note_reconnect(self, peer: str) -> None:
        p = self._peer(peer)
        p.reconnects += 1
        p.last_ts = time.time()
        self.perf.inc("msgr_reconnects")

    def note_replay(self, peer: str, frames: int) -> None:
        p = self._peer(peer)
        p.replay_frames += frames
        p.last_ts = time.time()
        self.perf.inc("msgr_replay_frames", frames)

    def note_sync_timeout(self) -> None:
        self.sync_timeouts += 1
        self.perf.inc("msgr_sync_timeouts")

    # -- surfaces ------------------------------------------------------------

    def totals(self) -> dict:
        d = self.perf.dump()
        return {
            "msgs_out": d["msgr_msgs_out"],
            "msgs_in": d["msgr_msgs_in"],
            "bytes_out": d["msgr_bytes_out"],
            "bytes_in": d["msgr_bytes_in"],
            "reconnects": d["msgr_reconnects"],
            "replay_frames": d["msgr_replay_frames"],
            "sync_timeouts": d["msgr_sync_timeouts"],
            "compress_bytes": d["msgr_compress_bytes"],
            "encrypt_bytes": d["msgr_encrypt_bytes"],
            "sendq_hwm": self.sendq_hwm,
            "peers": len(self._peers),
            "reactor": self.reactor,
        }

    def conn_rows(self) -> list[dict]:
        """Per-peer rows, busiest (bytes out+in) first."""
        with self._lock:
            peers = list(self._peers.values())
        rows = [p.to_dict() for p in peers]
        rows.sort(key=lambda r: -(r["bytes_out"] + r["bytes_in"]))
        return rows

    def set_peer_cap(self, cap: int) -> None:
        self.peer_cap = max(1, int(cap))
        with self._lock:
            while len(self._peers) > self.peer_cap:
                self._peers.popitem(last=False)


class MsgrLedger:
    """Per-process wire-plane ledger (module doc): owns the shared
    reactor/dispatch health state and the registry of per-messenger
    MsgrStats slices."""

    _host: "MsgrLedger | None" = None
    _host_lock = threading.Lock()
    # registered messengers kept (short-lived CLI clients churn; the
    # eviction only drops the LEDGER's reference — the messenger keeps
    # its own stats object working)
    MESSENGER_CAP = 128
    # the reactor pool's selectors, in reactor order: process-wide as
    # the pool is, so a ledger made after `reset_host` still sees them
    reactor_meters: list[ReactorSelector] = []

    def __init__(self, perf=None, enabled: bool = True,
                 peer_cap: int = 256, probe_interval: float = 0.25,
                 warn_s: float = 1.0, window_s: float = 60.0):
        self.enabled = enabled
        self.peer_cap = max(1, int(peer_cap))
        self.probe_interval = float(probe_interval)
        # monward threshold (conf ms_reactor_lag_warn_s) rides the
        # report so the mon needs no config (the COMPILE_STORM rule)
        self.warn_s = float(warn_s)
        self.window_s = float(window_s)
        self.perf = perf if perf is not None else _build_ledger_perf()
        if isinstance(self.perf, _LedgerCounters):
            self.perf.rows = self._dump_rows
        self._lock = threading.Lock()
        self._messengers: collections.OrderedDict[str, MsgrStats] = \
            collections.OrderedDict()
        # a frame's trip (module doc).  The clock of every stamp: an
        # attribute so a test can inject one; perf_counter_ns is the
        # clock `spans` uses, and one process has one
        self.now_ns = time.perf_counter_ns
        # (nonce, sender is connector, seq) -> [write-end stamp,
        # header-arrival stamp], each 0 until it is known; oldest
        # evicted past FLIGHT_CAP
        self._flight: collections.OrderedDict[tuple, list] = \
            collections.OrderedDict()
        # message type -> [sender halves, receiver halves, transits,
        # ns per FRAME_PHASES..., drain ns]; bounded as _type_inc is.
        # Sampled frames only (tens a second), so under a lock: the
        # sums are exact
        self._frame_rows: dict[str, list] = {}
        self._frame_lock = threading.Lock()
        # data frames written by message type (replays count again,
        # as in msgr_frames_out), and HELLO frames
        self._frames_by_type: dict[str, int] = {}
        self._hellos = 0
        # reactor probe state: idx -> (wall ts, last lag); lag events
        # (ts, reactor, lag) in a bounded window deque
        self._reactor_lag: dict[int, tuple[float, float]] = {}
        self._lag_events: collections.deque = \
            collections.deque(maxlen=512)
        self.lag_events_total = 0
        # per-loop probe ownership tokens: re-attaching to a loop (or a
        # recreated pool) replaces the token, so the superseded probe
        # chain dies on its next fire instead of double-counting
        self._probe_tokens: dict[int, object] = {}
        self._dispatch_pending = 0
        self._dispatch_hwm = 0
        self.dispatches_total = 0
        self.created_at = time.time()

    # -- host singleton ------------------------------------------------------

    @classmethod
    def host_instance(cls) -> "MsgrLedger":
        with cls._host_lock:
            if cls._host is None:
                cls._host = cls()
            return cls._host

    @classmethod
    def reset_host(cls) -> None:
        """Tests/benches only: drop the singleton (stats of the old one
        stay readable through any direct references)."""
        with cls._host_lock:
            cls._host = None

    # -- messenger registry --------------------------------------------------

    def register_messenger(self, entity: str,
                           perf=None) -> MsgrStats:
        """A Messenger is born: hand it its ledger slice.  Keyed by
        entity (unique per instance); the registry is bounded."""
        st = MsgrStats(entity, self, perf=perf, peer_cap=self.peer_cap)
        with self._lock:
            self._messengers[entity] = st
            while len(self._messengers) > self.MESSENGER_CAP:
                self._messengers.popitem(last=False)
        return st

    def set_peer_cap(self, cap: int) -> None:
        """conf ms_ledger_peers: applies to registered slices and
        future ones."""
        self.peer_cap = max(1, int(cap))
        with self._lock:
            stats = list(self._messengers.values())
        for st in stats:
            st.set_peer_cap(self.peer_cap)

    # -- dispatch-executor timing (called behind the enabled gate) -----------

    def dispatch_submit(self) -> float:
        """A handler was queued on the shared executor; returns the
        submit stamp the run-side calls thread through."""
        n = self._dispatch_pending + 1
        self._dispatch_pending = n
        self.perf.set("msgr_dispatch_queued", n)
        if n > self._dispatch_hwm:
            self._dispatch_hwm = n
            self.perf.set("msgr_dispatch_queued_hwm", n)
        return time.perf_counter()

    def dispatch_run(self, t_submit: float, span: str, frame=None):
        """The handler started running: close the queue-wait clock and
        open its span (common/spans.py: `msgr.dispatch.<MsgType>` for
        a message handler, the caller's own name for a continuation
        handed to Messenger.submit_dispatch).  `frame`: the arguments
        of `frame_delivered` for a sampled frame, whose `to_handler`
        ends here.  Returns the open span; hand it to dispatch_done
        in a `finally`."""
        if frame is not None:
            self.frame_delivered(*frame)
        sp = spans.begin(span)
        self.perf.hinc("lat_msgr_qwait",
                       max(0.0, sp.t0 * 1e-9 - t_submit))
        return sp

    def dispatch_done(self, sp) -> None:
        """Close the handler's span; its whole duration (children
        included) is the `lat_msgr_dispatch` sample — handler RUN time
        here, not the op tracker's `lat_msgr_dispatch` (that one is an
        osd_op's client-submit -> frame-at-the-primary interval)."""
        sp.end()
        self.perf.hinc("lat_msgr_dispatch", sp.wall_s)
        self.dispatches_total += 1
        self.perf.inc("msgr_dispatches")
        n = self._dispatch_pending - 1
        self._dispatch_pending = n if n > 0 else 0
        self.perf.set("msgr_dispatch_queued", self._dispatch_pending)

    # -- socket writes (called behind the enabled gate) ----------------------

    def note_wire(self, writes: int, mtypes=(), acks: int = 0,
                  rode: int = 0) -> None:
        """`writes` calls handed bytes to a transport; between them
        they carried one data frame per entry of `mtypes` (its message
        type), `acks` acks of their own and `rode` acks ahead of a
        data frame (the counts behind wire_writes_per_frame /
        wire_acks_per_frame / wire_frames_per_op)."""
        inc = self.perf.inc
        inc("msgr_socket_writes", writes)
        if mtypes:
            inc("msgr_frames_out", len(mtypes))
            for mtype in mtypes:
                _type_inc(self._frames_by_type, mtype)
        if acks:
            inc("msgr_acks_out", acks)
        if rode:
            inc("msgr_acks_piggybacked", rode)

    def note_send_path(self, inline: bool) -> None:
        """A send queue's flush wrote a data frame in place, or handed
        it to the coroutine path."""
        self.perf.inc("msgr_sends_inline" if inline else "msgr_sends_slow")

    def note_send_wake(self) -> None:
        """A thread other than the loop's signalled a send queue."""
        self.perf.inc("msgr_send_wakes")

    # -- socket reads (called behind the enabled gate) -----------------------

    def note_rx_read(self) -> None:
        """A read brought bytes from a socket into a receiver."""
        self.perf.inc("msgr_rx_reads")

    def note_large_body(self, reads: int, nbytes: int) -> None:
        """A body of `nbytes`, longer than JOIN_UP_TO, is complete in
        its own buffer after `reads` reads that landed in it (the
        counts behind wire_reads_per_large_body)."""
        inc = self.perf.inc
        inc("msgr_large_bodies")
        inc("msgr_large_body_reads", reads)
        inc("msgr_large_body_bytes", nbytes)

    def note_hello(self) -> None:
        """A CTRL_HELLO frame was written (a dial, an accept's reply,
        a refusal)."""
        self._hellos += 1

    # -- a frame's trip (called behind the enabled gate) ---------------------

    def _frame_row(self, mtype: str) -> list:
        row = self._frame_rows.get(mtype)
        if row is None:
            if len(self._frame_rows) >= TYPE_CAP:
                mtype = OTHER_TYPE
            row = self._frame_rows.setdefault(
                mtype, [0] * (_ROW_DRAIN + 1))
        return row

    def frame_depart(self, tx: FrameTx) -> None:
        """A sampled frame is about to be handed to its transport:
        put its slot `[write returned, header arrived]` into the
        flight table BEFORE the write — under the GIL the receiving
        reactor often has the whole frame, handler started, before
        the sender is back from the system call."""
        tx.entry = entry = [0, 0]
        with self._frame_lock:
            self._flight[tx.key] = entry
            evicted = len(self._flight) - FLIGHT_CAP
            for _ in range(evicted):
                self._flight.popitem(last=False)
        if evicted > 0:
            self.perf.inc("msgr_frame_stamps_evicted", evicted)

    def frame_sent(self, tx: FrameTx) -> int:
        """`_write_once` returned: stamp it (under the table's lock:
        `frame_claim` orders itself against this reading), and record
        the sender's four phases.  `write` ends at this stamp — or at
        the header's arrival, if a receiver of this process has
        claimed the frame already: its trip did not wait for the
        sender's return.  Returns the stamp (where `lat_frame_drain`
        starts)."""
        hinc = self.perf.hinc
        with self._frame_lock:
            t_w = tx.entry[0] = self.now_ns()
            stamps = (tx.t_call, tx.t_in, tx.t_lock, tx.t_enc,
                      tx.entry[1] or t_w)
            row = self._frame_row(tx.mtype)
            row[0] += 1
            for i, phase in enumerate(_TX_PHASES):
                dt = stamps[i + 1] - stamps[i]
                row[_ROW_PHASE0 + i] += dt
                hinc("lat_frame_" + phase, dt * 1e-9)
        return t_w

    def frame_drained(self, tx: FrameTx, t_w: int) -> None:
        dt = self.now_ns() - t_w
        with self._frame_lock:
            self._frame_row(tx.mtype)[_ROW_DRAIN] += dt
            self.perf.hinc("lat_frame_drain", dt * 1e-9)

    def frame_claim(self, key: tuple, t_head: int) -> tuple:
        """The receiver knows which frame it holds (seq read, the
        frame unwrapped): take the sender's slot out of the table.
        Returns (slot or None, t_arr) — `t_arr` is where the
        receiver's share of the trip starts: the header's arrival, or
        the sender's write-end stamp if that is already there and
        later (the sender then counted up to it).  A slot not stamped
        yet is told the arrival, so the sender ends `write` there.
        No slot: the sender is another process, or the stamp fell out
        (a replay, a frame older than FLIGHT_CAP samples)."""
        with self._frame_lock:
            entry = self._flight.pop(key, None)
            if entry is None:
                return None, t_head
            t_w = entry[0]
            if t_w > t_head:
                return entry, t_w
            if not t_w:
                entry[1] = t_head
            return entry, t_head

    def frame_delivered(self, entry, mtype: str, t_arr: int,
                        t_body: int, t_dec: int) -> None:
        """First line of a sampled frame's handler, on whichever
        thread runs it (`entry`, `t_arr`: what `frame_claim` gave):
        record the receiver's phases, and `transit` — write returned
        -> header arrived, 0 where the header came first — if the
        sender is a messenger of this process: never a guess."""
        t_h = self.now_ns()
        hinc = self.perf.hinc
        # where the sender's stamp overtook the header, the reads
        # that ended before it are the sender's time already
        t_body = max(t_body, t_arr)
        with self._frame_lock:
            row = self._frame_row(mtype)
            row[1] += 1
            if entry is not None:
                t_w = entry[0]
                dt = t_arr - t_w if 0 < t_w < t_arr else 0
                row[2] += 1
                row[_ROW_TRANSIT] += dt
                hinc("lat_frame_transit", dt * 1e-9)
            else:
                self.perf.inc("msgr_frame_samples_unpaired")
            stamps = (t_arr, t_body, t_dec, t_h)
            for i, phase in enumerate(_RX_PHASES):
                dt = stamps[i + 1] - stamps[i]
                row[_ROW_TRANSIT + 1 + i] += dt
                hinc("lat_frame_" + phase, dt * 1e-9)

    # -- reactor lag probe ---------------------------------------------------

    def attach_reactors(self, loops, interval: float | None = None,
                        meters=None) -> None:
        """Arm the self-rescheduling lag probe on each reactor loop
        (messenger._ensure_pool calls this right after pool creation).
        Probes keep firing while the ledger is disabled — the off-path
        cost is one attribute check per interval — so re-enabling
        needs no re-arm.  `meters`: the loops' ReactorSelectors, in
        the same order (the per-reactor rows of the set)."""
        if interval is not None:
            self.probe_interval = float(interval)
        if meters is not None:
            MsgrLedger.reactor_meters = list(meters)
        for idx, loop in enumerate(loops):
            token = object()
            self._probe_tokens[id(loop)] = token
            try:
                loop.call_soon_threadsafe(
                    self._arm_probe, loop, idx, token)
            except RuntimeError:
                pass          # loop already closed (teardown race)

    def _arm_probe(self, loop, idx: int, token) -> None:
        interval = max(0.01, float(self.probe_interval))
        expected = loop.time() + interval
        loop.call_later(interval, self._probe_fire, loop, idx, token,
                        expected, interval)

    def _probe_fire(self, loop, idx: int, token, expected: float,
                    interval: float) -> None:
        if self._probe_tokens.get(id(loop)) is not token:
            return            # superseded (pool recreated / re-attach)
        if self.enabled:
            self.note_reactor_lag(idx, loop.time() - expected,
                                  interval)
        self._arm_probe(loop, idx, token)

    def note_reactor_lag(self, reactor: int, lag: float,
                         interval: float | None = None) -> None:
        """One probe observation.  The histogram/gauge move every
        probe; an EVENT (counter + monward window) only when the probe
        fired a FULL extra interval late — the heartbeat tick-lag
        detector's rule, so a loaded-but-healthy reactor does not
        page."""
        if not self.enabled:
            return
        lag = max(0.0, lag)
        now = time.time()
        self._reactor_lag[reactor] = (now, lag)
        self.perf.hinc("lat_msgr_reactor_lag", lag)
        worst = max((l for _, l in self._reactor_lag.values()),
                    default=0.0)
        self.perf.set("msgr_reactor_lag_worst", worst)
        if interval is None:
            interval = self.probe_interval
        if lag >= interval:
            self.lag_events_total += 1
            self.perf.inc("msgr_reactor_lag_events")
            self._lag_events.append((now, reactor, lag))

    # -- rows rendered at dump time ------------------------------------------

    def reactor_rows(self) -> list[dict]:
        """One row per reactor loop, from its selector's account and
        /proc: `running_s` = wall - asleep is measured, not a
        difference; `stalled_s` = running - cpu is the time the loop
        had work and was not on a CPU (waiting for the GIL, or
        preempted: the sockets do not block)."""
        rows = []
        for i, sel in enumerate(MsgrLedger.reactor_meters):
            asleep, running = sel.account()
            cpu = spans.thread_cpu_s(sel.native_id) \
                if sel.native_id else 0.0
            rows.append({
                "reactor": i, "wall_s": asleep + running,
                "select_s": asleep, "running_s": running,
                "cpu_s": cpu, "stalled_s": max(0.0, running - cpu),
                "sleeps": sel.sleeps, "iterations": sel.iterations})
        return rows

    def frame_rows(self) -> dict[str, dict]:
        """{message type: {n (sender halves), rx_n, transit_n,
        ns: {phase: summed ns, "drain": ...}}} of the sampled
        frames."""
        with self._frame_lock:
            rows = {t: list(r) for t, r in self._frame_rows.items()}
        return {t: {"n": r[0], "rx_n": r[1], "transit_n": r[2],
                    "ns": dict(zip(FRAME_PHASES + ("drain",),
                                   r[_ROW_PHASE0:]))}
                for t, r in rows.items()}

    def _dump_rows(self, plain: dict) -> dict:
        """The dotted keys of the set (module doc; the
        `ec_drains_by_path.<path>` precedent): frames written by
        message type with the control frames beside them, the sampled
        trips by type, the reactor loops — the last only while the
        ledger is on (a reactor's CPU moves whether or not its loop
        is accounted)."""
        out = {f"msgr_frames_out_by_type.{t}": n
               for t, n in list(self._frames_by_type.items())}
        out["msgr_frames_out_by_type.CTRL_ACK"] = plain["msgr_acks_out"]
        out["msgr_frames_out_by_type.CTRL_HELLO"] = self._hellos
        for mtype, row in self.frame_rows().items():
            out[f"frame_n.{mtype}"] = row["n"]
            out[f"frame_rx_n.{mtype}"] = row["rx_n"]
            out[f"frame_transit_n.{mtype}"] = row["transit_n"]
            for phase, ns in row["ns"].items():
                out[f"frame_ns.{mtype}.{phase}"] = ns
        if self.enabled:
            for r in self.reactor_rows():
                i = r["reactor"]
                out[f"reactor_wall_s.{i}"] = r["wall_s"]
                out[f"reactor_select_s.{i}"] = r["select_s"]
                out[f"reactor_cpu_s.{i}"] = r["cpu_s"]
                out[f"reactor_sleeps.{i}"] = r["sleeps"]
                out[f"reactor_iterations.{i}"] = r["iterations"]
        return out

    # -- aggregation surfaces ------------------------------------------------

    def _window_events(self) -> list[tuple[float, int, float]]:
        cutoff = time.time() - self.window_s
        return [(ts, r, l) for ts, r, l in list(self._lag_events)
                if ts >= cutoff]

    def pgstats_block(self) -> dict | None:
        """The MPGStats "msgr" block: None unless the lag-event window
        is non-empty, and coarsely rounded, so a healthy daemon's
        report stays bit-identical and the keepalive dedup
        (_pgstats_should_send) keeps working."""
        if not self.enabled:
            return None
        events = self._window_events()
        if not events:
            return None
        worst = max(events, key=lambda e: e[2])
        return {
            "window_s": self.window_s,
            "lag_events": len(events),
            "worst_lag_s": round(worst[2], 2),
            "worst_reactor": worst[1],
            "warn_s": float(self.warn_s),
        }

    def status(self) -> dict:
        """The `messenger status` asok payload."""
        with self._lock:
            msgrs = list(self._messengers.items())
        return {
            "enabled": self.enabled,
            "uptime_s": round(time.time() - self.created_at, 3),
            "reactors": {
                "count": len(self._reactor_lag),
                "probe_interval_s": self.probe_interval,
                "last_lag_s": {str(i): round(lag, 6)
                               for i, (_ts, lag)
                               in sorted(self._reactor_lag.items())},
                "lag_events": self.lag_events_total,
                # each loop's account (docs/TRACING.md "Reactor
                # loops"), seconds rounded to the microsecond
                "loops": [{k: round(v, 6) if isinstance(v, float)
                           else v for k, v in row.items()}
                          for row in self.reactor_rows()],
            },
            # the sampled trips by message type: mean us per phase
            # (docs/TRACING.md "A frame's trip"), and every frame
            # written by type
            "frames": {
                "sample_one_in": SAMPLE_ONE_IN,
                "in_flight": len(self._flight),
                "out_by_type": dict(self._frames_by_type),
                "hellos": self._hellos,
                "trips": {t: trip_means(r)
                          for t, r in self.frame_rows().items()},
            },
            "dispatch": {
                "pending": self._dispatch_pending,
                "hwm": self._dispatch_hwm,
                "total": self.dispatches_total,
            },
            "latencies": self.perf.dump_latencies(),
            "messengers": {name: st.totals() for name, st in msgrs},
            "window": self.pgstats_block(),
        }

    def conn_profile(self, last: int | None = None) -> dict:
        """The `conn profile` asok payload: per-peer rows per
        messenger, busiest first (`last` caps rows per messenger)."""
        with self._lock:
            msgrs = list(self._messengers.items())
        out = {}
        for name, st in msgrs:
            rows = st.conn_rows()
            if last is not None:
                rows = rows[:max(0, int(last))]
            out[name] = rows
        return {"enabled": self.enabled, "messengers": out}

    def bench_summary(self) -> dict:
        """The bench-row provenance block (`msgr_ledger` in
        cluster_bench --scale rows, beside recovery_blame): reactor
        lag + dispatch percentiles, wire totals, top peers."""
        def q(key, quant):
            est = self.perf.quantile(key, quant)
            return round(est[0] * 1e3, 3) if est else None
        with self._lock:
            msgrs = list(self._messengers.values())
        totals = {"msgs_out": 0, "msgs_in": 0, "bytes_out": 0,
                  "bytes_in": 0, "reconnects": 0, "replay_frames": 0,
                  "sync_timeouts": 0}
        peer_bytes: dict[str, int] = {}
        for st in msgrs:
            t = st.totals()
            for k in totals:
                totals[k] += t[k]
            for row in st.conn_rows():
                peer_bytes[row["peer"]] = \
                    peer_bytes.get(row["peer"], 0) + \
                    row["bytes_out"] + row["bytes_in"]
        top_peers = dict(sorted(peer_bytes.items(),
                                key=lambda kv: -kv[1])[:8])
        out = {
            "reactor_lag_ms_p50": q("lat_msgr_reactor_lag", 0.5),
            "reactor_lag_ms_p99": q("lat_msgr_reactor_lag", 0.99),
            "qwait_ms_p50": q("lat_msgr_qwait", 0.5),
            "qwait_ms_p99": q("lat_msgr_qwait", 0.99),
            "dispatch_ms_p50": q("lat_msgr_dispatch", 0.5),
            "dispatch_ms_p99": q("lat_msgr_dispatch", 0.99),
            "lag_events": self.lag_events_total,
            "dispatch_hwm": self._dispatch_hwm,
            "dispatches": self.dispatches_total,
            "peer_bytes": top_peers,
        }
        out.update(totals)
        return out

    def reset(self) -> None:
        """Clear window/table state (benches isolating a phase; the
        perf histograms are monotonic by design and stay)."""
        with self._lock:
            self._messengers.clear()
        with self._frame_lock:
            self._flight.clear()
        self._reactor_lag.clear()
        self._lag_events.clear()
        self.lag_events_total = 0
        self._dispatch_pending = 0
        self._dispatch_hwm = 0
        self.dispatches_total = 0
        self.created_at = time.time()


def msgr_ledger() -> MsgrLedger:
    """The process's wire-plane recorder (built on first use,
    enabled); the common fast path skips the singleton lock."""
    led = MsgrLedger._host
    return led if led is not None else MsgrLedger.host_instance()
