"""Async messenger: reactor, sessions, connections, dispatch.

Re-expresses the reference's AsyncMessenger stack (src/msg/async/
AsyncMessenger.cc, AsyncConnection.cc, Stack.h Worker reactors) and
ProtocolV2's lossless session semantics (src/msg/async/ProtocolV2.cc:
out_seq/in_seq, ack frames, session resume + replay on reconnect):

- Every connection opens with a HELLO frame carrying a stable entity
  identity and the receiver's highest-delivered seq; the server binds
  the TCP stream to a per-entity Session that survives reconnects.
- Senders keep unacked frames; receivers ack delivered seqs; acks trim
  the replay window.  On reconnect the peer's HELLO tells the sender
  what arrived, so replay starts exactly after it and the receive path
  drops any already-seen seq — exactly-once delivery per session.
- Acks ride.  An ack does nothing but trim that window, so it is not
  worth a frame: a session OWES one while in_seq > last_acked, and
  pays by writing the CTRL_ACK ahead of the next data frame to that
  peer, in the same transport call (_put) — no segment, no
  wake-up and no system call of its own at either end.  At the rates
  the cluster runs (a frame every ~100 ms per session) "ack when the
  pipe goes idle" was an ack frame for every frame delivered, half of
  all frames on the wire.  A stand-alone ack (_send_ack) is written
  only when ACK_EVERY_FRAMES or ACK_EVERY_BYTES are owed, when the
  debt is ACK_DELAY_S old (one timer per session, armed while it
  owes), or at once for a replayed duplicate; a HELLO states in_seq
  and so pays too.  (The reference's messages carry an ack_seq in
  their header for the same reason.)
- One write per frame: a frame's parts (head+meta, payload buffers,
  crc) leave in ONE transport call (_write_once: joined when small,
  writelines → one sendmsg when large, so a payload is never copied
  into a frame buffer).  Part by part, TCP_NODELAY made each part a
  segment and the peer's receiver woke for each.
- One pass onto the reactor: send_message appends (conn, msg) to its
  reactor's send queue (_SendQueue) and signals the loop only when
  the queue was empty — one call_soon_threadsafe for a whole fan-out,
  a plain call_soon from the loop's own thread.  The queue's flush,
  ONE callback, writes every frame whose session can take it now in
  place (Connection._send_now: seq, encode, retention, the write — no
  Task, no await); the rest go to the coroutine Connection._send
  (dial and replay, back-pressure through drain(), fault injection),
  and while a session has frames there its later frames follow them,
  so per-session order and exactly-once delivery are _send's.  A
  Task per frame started one loop pass after the callback that made
  it, so every frame waited two passes to reach its wire.
- One read per burst of small frames, and a large body in place
  (FrameReceiver, an asyncio.BufferedProtocol under every connection:
  the transport `recv_into`s the buffer it is handed, no StreamReader
  between).  Reads land in one RX_SCRATCH buffer and every frame
  complete in it is cut out and queued in order — ten pings, or a
  sub-write reply and the ack ahead of it, cost one system call.  A
  header that announces a body (meta + data + crc) longer than
  JOIN_UP_TO gets that body a buffer of its own, and until it is full
  the kernel copies straight into it, as much a call as the socket
  holds: a 4 MiB body in the 1-3 reads the kernel needs, where the
  stream transport's 256 KiB a loop pass took 16, each a wake-up of a
  waiter that found the buffer short.  Why JOIN_UP_TO: it is the size
  above which the SEND side stops copying a payload into a frame
  buffer, so both ends agree on what "large" is and a frame is either
  joined-and-cut (small, two short copies) or never copied in user
  space at all; the rule reads only a length off the wire.  The
  message kinds whose handlers take any buffer (Message.takes_view)
  get such a body's data as a read-only view of that buffer, which
  is never written again — whoever keeps it (a store, the extent
  cache) keeps the buffer alive; the others get one bytes copy.  The
  payload crc is computed over the view before dispatch, as ever.
  Back-pressure: complete frames nobody has taken may hold
  RX_HOLD_MAX; past it the transport is paused until the read loop
  (parked, say, in a blocking handler) takes them.
- The Session owns the live TCP stream; Connections are facades over it,
  so a server reply issued after the client reconnected rides the new
  stream (the reference rebinds AsyncConnection to the existing session
  the same way on reconnect_ok).
- Lossy connections (heartbeats may opt in) skip retention, resume
  and acks: nothing is retained, so there is nothing to trim.

Fault injection (reference ms_inject_socket_failures / ms_inject_delay_*
in src/common/options.cc:1071-1092): per-messenger knobs that randomly
reset sockets or delay frame writes, used by the thrasher tests.

Idiomatic shift: a small POOL of asyncio event loops (each in its own
thread) replaces N epoll worker threads — every Messenger instance is
pinned to one loop of the pool at creation (reference AsyncMessenger
worker assignment).  A single shared loop was measured to serialize
the EC read fan-out: 8 concurrent 128 KiB sub-read replies took 4.2 ms
through one reactor vs 0.57 ms for one reply, because every frame's
encode + crc + retention copy runs on the loop thread.  Sessions,
sockets, and locks are all per-messenger, so loops never share
connection state.  The public surface (Messenger/Connection/
Dispatcher) keeps the reference's shape so daemon code reads the same.
"""

from __future__ import annotations

import asyncio
import collections
import json
import random
import struct
import threading
import time
import traceback
import uuid
from concurrent.futures import TimeoutError as _FuturesTimeout
from typing import Callable

from .message import (CTRL_ACK, CTRL_COMP, CTRL_ENC, CTRL_HELLO, Message,
                      encode_ack, encode_frame, frame_type_name,
                      type_name)
from ..common import spans
from .msgr_ledger import (FrameTx, MsgrLedger, ReactorSelector,
                          frame_sampled, msgr_ledger, sample_offset)

Dispatcher = Callable[["Connection", Message], None]


def _grow_socket_buffers(writer: asyncio.StreamWriter,
                         size: int = 4 << 20) -> None:
    """MiB-scale frames on default (~64-208 KiB) kernel buffers cost
    several epoll write/read cycles each; grow both directions."""
    import socket as _socket
    sock = writer.get_extra_info("socket")
    if sock is None:
        return
    try:
        sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_SNDBUF, size)
        sock.setsockopt(_socket.SOL_SOCKET, _socket.SO_RCVBUF, size)
    except OSError:
        pass

# A lossless peer that stops acking cannot hold frames forever: past this
# many retained frames the session is torn down (abnormal reset, like the
# reference's session reset after policy limits) rather than leaking.
UNACKED_HARD_CAP = 65536

# What a lossless session may owe its peer before it pays with a
# CTRL_ACK frame of its own (module doc, "acks ride"): frames delivered,
# payload bytes delivered (bounds what a sender of 4 MiB frames retains
# for a quiet reverse direction), seconds owed.  The timer is longer
# than any round trip on purpose: the reverse frame carries the ack.
ACK_EVERY_FRAMES = 64
ACK_EVERY_BYTES = 8 << 20
ACK_DELAY_S = 1.0

# Buffers that leave in one transport call are joined first up to this
# total (one `send`); past it they go as they are (`writelines`, one
# `sendmsg`), so a large payload is never copied into a frame buffer.
JOIN_UP_TO = 64 << 10

# The receive side (FrameReceiver): the one buffer every read lands in
# unless a large body is being filled (what asyncio's stream transport
# read a pass), and the body bytes a connection's complete frames may
# hold untaken before its transport is paused.
RX_SCRATCH = 256 << 10
RX_HOLD_MAX = 8 << 20


def _write_once(writer: asyncio.StreamWriter, bufs) -> None:
    """Hand `bufs` (non-empty buffers: a frame's parts, an ack ahead
    of them) to the transport in ONE call — one system call and one
    TCP segment where the socket takes it, so the peer's receiver
    wakes once for the whole frame."""
    if len(bufs) == 1:
        writer.write(bufs[0])
        return
    total = 0
    for b in bufs:
        total += len(b)
    if total <= JOIN_UP_TO:
        writer.write(b"".join(bufs))
        return
    if writer.transport.is_closing():
        # transport.write() drops bytes on a lost connection and
        # drain() then raises; writelines() has no such check and
        # would queue them behind a writer callback on a dead fd
        raise ConnectionResetError("wire closing")
    writer.writelines(bufs)


def _parse_raw(raw: bytes) -> tuple[int, int, bytes, bytes, int]:
    """Split one frame already in memory (the unwrapped payload of an
    ENC/COMP envelope) into (tid, seq, meta_raw, data, pcrc).  A short
    or mangled buffer raises ValueError so the read loop's corruption
    path (session-preserving wire reset) handles it — struct.error
    would kill the loop."""
    try:
        tid, seq, meta_len, data_len = \
            Message.parse_header(raw[:Message.HEADER_SIZE])
    except (struct.error, ValueError) as e:
        raise ValueError(f"bad inner frame: {e}") from e
    off = Message.HEADER_SIZE
    if len(raw) < off + meta_len + data_len + 4:
        raise ValueError("truncated inner frame")
    meta_raw = raw[off:off + meta_len]
    data = raw[off + meta_len:off + meta_len + data_len]
    pcrc = int.from_bytes(raw[-4:], "little")
    return tid, seq, meta_raw, data, pcrc


class FrameReceiver(asyncio.streams.FlowControlMixin,
                    asyncio.BufferedProtocol):
    """The protocol under one messenger connection: the transport
    reads the socket straight into the buffer `get_buffer` hands it
    (`recv_into`), and `buffer_updated` cuts what arrived into frames.

    Small frames, many a read: reads land in one scratch buffer, and
    every frame complete in it — header (magic and header crc checked),
    then body — is cut out as `bytes` and queued, in order.  A large
    body in place: a header that announces a body longer than
    JOIN_UP_TO gets that body a buffer of its own; the prefix that came
    with the header moves in, and until the body is full `get_buffer`
    hands out ITS unfilled rest, so the kernel copies to where the
    frame will live, as much a call as the socket holds.  Such a
    frame's `data` is a read-only view of that buffer, which is never
    written again.

    `next_frame` yields (tid, seq, meta_raw, data, pcrc, t_head,
    t_body) — the two stamps are the wire ledger's ("a frame's trip"):
    the clock when a sampled frame's header was parsed and when its
    last body byte had landed, 0 for a frame the sampling rule leaves
    out (a wrapped frame, ENC or COMP, shows its own seq only once
    unwrapped: stamped here, tested by the read loop).  EOF or a lost
    connection raises IncompleteReadError / the transport's error once
    the frames complete before it are taken; a bad magic or header crc
    raises ValueError the same way.

    Back-pressure: while the complete frames nobody has taken hold
    more than RX_HOLD_MAX the transport is paused, so a read loop
    parked in a slow handler stops a flooding peer at the socket.

    The write side's StreamWriter drains through this protocol
    (FlowControlMixin, as asyncio's own StreamReaderProtocol does)."""

    def __init__(self, ledger: MsgrLedger, sess: "Session | None" = None,
                 on_accept=None):
        super().__init__()
        self.ledger = ledger
        # the session whose sampling rule picks the frames to stamp:
        # a dialled wire knows it from the start, an accepted one once
        # the HELLO names it
        self.sess = sess
        self._on_accept = on_accept
        self.transport: asyncio.Transport | None = None
        self._scratch = bytearray(RX_SCRATCH)
        self._sview = memoryview(self._scratch)
        self._lo = self._hi = 0     # scratch[lo:hi]: read, not yet cut
        # (tid, seq, meta_len, data_len, t_head) of the frame whose
        # body is still arriving
        self._head: tuple | None = None
        # the large body being filled (a view of its own bytearray),
        # bytes landed, reads that landed
        self._body: memoryview | None = None
        self._bfill = self._breads = 0
        self._frames: collections.deque[tuple] = collections.deque()
        self._held = 0              # body bytes of the queued frames
        self._rx_paused = False
        self._exc: BaseException | None = None
        self._waiter: asyncio.Future | None = None

    # -- transport callbacks -------------------------------------------------

    def connection_made(self, transport) -> None:
        self.transport = transport
        if self._on_accept is not None:
            writer = asyncio.StreamWriter(transport, self, None,
                                          self._loop)
            # the task is referenced while it runs (the loop holds
            # tasks weakly)
            self._task = self._loop.create_task(
                self._on_accept(self, writer))

    def get_buffer(self, sizehint: int) -> memoryview:
        body = self._body
        if body is not None:
            return body[self._bfill:]
        return self._sview[self._hi:]

    def buffer_updated(self, nbytes: int) -> None:
        led = self.ledger
        if led.enabled:
            led.note_rx_read()
        if self._exc is not None:
            return                  # a dead stream's bytes: dropped
        if self._body is not None:
            self._bfill += nbytes
            self._breads += 1
            if self._bfill < len(self._body):
                return
            self._large_done()
        else:
            self._hi += nbytes
            try:
                self._cut_frames()
            except ValueError as e:
                self._fail(e)
                transport = self.transport
                if transport is not None:
                    transport.pause_reading()
                return
        if self._frames:
            if self._held > RX_HOLD_MAX and not self._rx_paused:
                self._rx_paused = True
                self.transport.pause_reading()
            self._wake()

    def eof_received(self) -> None:
        self._fail(asyncio.IncompleteReadError(b"", None))

    def connection_lost(self, exc) -> None:
        super().connection_lost(exc)
        self._fail(exc if exc is not None
                   else asyncio.IncompleteReadError(b"", None))

    # -- cutting -------------------------------------------------------------

    def _fail(self, exc: BaseException) -> None:
        if self._exc is None:
            self._exc = exc
            self._body = None
        self._wake()

    def _wake(self) -> None:
        waiter = self._waiter
        if waiter is not None and not waiter.done():
            waiter.set_result(None)

    def _stamp_head(self, tid: int, seq: int) -> int:
        sess = self.sess
        if sess is None:
            return 0
        timed = frame_sampled(seq, sess.sample_off) \
            if tid < CTRL_HELLO else tid in (CTRL_ENC, CTRL_COMP)
        return self.ledger.now_ns() if timed else 0

    def _cut_frames(self) -> None:
        """Cut every complete frame out of scratch[lo:hi]; leave a
        partial one where the next read can finish it."""
        view = self._sview
        lo, hi = self._lo, self._hi
        hsize = Message.HEADER_SIZE
        stamping = self.ledger.enabled
        while True:
            head = self._head
            if head is None:
                if hi - lo < hsize:
                    break
                tid, seq, meta_len, data_len = \
                    Message.parse_header(bytes(view[lo:lo + hsize]))
                lo += hsize
                head = self._head = (
                    tid, seq, meta_len, data_len,
                    self._stamp_head(tid, seq) if stamping else 0)
            tid, seq, meta_len, data_len, t_head = head
            need = meta_len + data_len + 4
            if need > JOIN_UP_TO:
                # a body of its own; what came with the header moves in
                try:
                    body = memoryview(bytearray(need))
                except (MemoryError, OverflowError) as e:
                    raise ValueError(f"frame body of {need} bytes") from e
                have = min(hi - lo, need)
                body[:have] = view[lo:lo + have]
                lo += have
                self._body, self._bfill = body, have
                self._breads = 1 if have else 0
                if have < need:
                    break           # lo == hi: the scratch is empty
                self._large_done()
                continue
            if hi - lo < need:
                break
            mid = lo + meta_len
            end = lo + need
            self._frames.append((
                tid, seq, bytes(view[lo:mid]), bytes(view[mid:end - 4]),
                int.from_bytes(view[end - 4:end], "little"), t_head,
                self.ledger.now_ns() if t_head else 0))
            self._held += need
            self._head = None
            lo = end
        if lo == hi:
            lo = hi = 0
        elif lo:
            # the partial frame (shorter than JOIN_UP_TO and a header)
            # goes to the front, so the next read may take the rest of
            # a whole scratch (the slice assignment is a memmove)
            n = hi - lo
            view[:n] = view[lo:hi]
            lo, hi = 0, n
        self._lo, self._hi = lo, hi

    def _large_done(self) -> None:
        """The body in `_body` is full: queue its frame."""
        body, self._body = self._body, None
        tid, seq, meta_len, data_len, t_head = self._head
        self._head = None
        led = self.ledger
        if led.enabled:
            led.note_large_body(self._breads, len(body))
        self._frames.append((
            tid, seq, bytes(body[:meta_len]),
            body[meta_len:meta_len + data_len].toreadonly(),
            int.from_bytes(body[-4:], "little"), t_head,
            led.now_ns() if t_head else 0))
        self._held += len(body)

    # -- the read loop's side ------------------------------------------------

    async def next_frame(self) -> tuple:
        """The next frame in arrival order; suspends only when none
        is complete."""
        while not self._frames:
            if self._exc is not None:
                raise self._exc
            self._waiter = self._loop.create_future()
            try:
                await self._waiter
            finally:
                self._waiter = None
        frame = self._frames.popleft()
        self._held -= len(frame[2]) + len(frame[3]) + 4
        if self._rx_paused and self._held <= RX_HOLD_MAX:
            self._rx_paused = False
            if self._exc is None:
                self.transport.resume_reading()
        return frame


class Session:
    """Per-peer-entity delivery state + the live wire; survives TCP
    reconnects (reference ProtocolV2 session: out_seq/in_seq/out_queue
    replay, rebound to a new AsyncConnection on resume)."""

    def __init__(self, lossless: bool = True, nonce: str | None = None):
        self.lossless = lossless
        # Distinguishes incarnations: a client that abandons a session
        # (unacked overflow) starts a new nonce, telling the server to
        # discard its old seq window instead of dedup-dropping the fresh
        # one (reference ProtocolV2 client_cookie semantics).
        self.nonce = nonce or uuid.uuid4().hex[:12]
        # where this session starts in the frame-sampling rule (both
        # ends derive it from the nonce; msgr_ledger.frame_sampled)
        self.sample_off = sample_offset(self.nonce)
        # Epoch cookies (reference ProtocolV2 client_cookie/server_cookie):
        # local_cookie identifies THIS session object; peer_cookie is the
        # last cookie seen from the peer.  A seq number is only meaningful
        # within the epoch whose cookie it was learned under — trusting a
        # stale in_seq would trim undelivered frames from the peer's
        # replay window (observed as lost replies across server restarts).
        self.local_cookie = uuid.uuid4().hex[:12]
        self.peer_cookie: str | None = None
        self.out_seq = 0          # last seq assigned to an outgoing frame
        self.in_seq = 0           # highest seq delivered to the dispatcher
        self.unacked: collections.deque[tuple[int, bytes]] = \
            collections.deque()
        # the live wire's receiver; also the wire's epoch token (a read
        # loop serves `sess.reader is reader` and nothing after it)
        self.reader: FrameReceiver | None = None
        self.writer: asyncio.StreamWriter | None = None
        self.send_lock = asyncio.Lock()
        # frames handed to the coroutine send path and not written
        # yet (their Tasks): while any is, later frames follow them
        self.slow_sends: set[asyncio.Task] = set()
        self.broken = False
        self.down_since: float | None = None
        self.last_acked = 0       # highest seq we have acked to the peer
        # what is owed since last_acked: payload bytes delivered, and
        # the one timer armed while in_seq > last_acked
        self.owed_bytes = 0
        self.ack_timer: asyncio.TimerHandle | None = None
        # auth state (per wire epoch; re-derived on every HELLO):
        # conn_key signs/encrypts this connection, auth_identity is the
        # verified peer {entity, caps} (reference CephXAuthorizer
        # session_key + secure-mode keys from crypto_onwire.cc)
        self.conn_key: bytes | None = None
        self.secure = False
        self.auth_identity: dict | None = None
        self._enc_ctr = 0
        self._enc_dir = b"\x01"   # \x01 = connector, \x02 = acceptor
        self._aead = None         # cached AESGCM (one key schedule)
        # on-wire compression (reference msgr2.1 compression feature):
        # negotiated at HELLO; frames >= comp_min wrap in CTRL_COMP
        # before (optional) encryption
        self.comp = None          # Compressor | None
        self.comp_min = 4096
        self.compressed_out = 0
        self._decomp_cache: dict = {}

    def wire_prepare(self, raw: bytes) -> bytes:
        """Outbound frame pipeline: compress-then-encrypt."""
        if self.comp is not None and len(raw) >= self.comp_min:
            raw = encode_frame(CTRL_COMP, 0, {"a": self.comp.name},
                               self.comp.compress(raw))
            self.compressed_out += 1
        if self.secure and self.conn_key:
            raw = self.wire_encrypt(raw)
        return raw

    def wire_decompress(self, algo: str, data: bytes) -> bytes:
        from ..compressor import CompressorError, create
        c = self._decomp_cache.get(algo)
        if c is None:
            try:
                c = self._decomp_cache[algo] = create(algo)
            except CompressorError as e:
                raise ValueError(f"bad compression algo: {e}") from e
        try:
            return c.decompress(data)
        except CompressorError as e:
            raise ValueError(f"corrupt compressed frame: {e}") from e

    def set_conn_key(self, key: bytes | None, direction: bytes) -> None:
        """Install the per-wire-epoch key; the counter reset is safe
        because every HELLO derives a fresh key from a fresh nonce."""
        self.conn_key = key
        self._enc_ctr = 0
        self._dec_ctr = 0
        self._enc_dir = direction
        if key is not None:
            from cryptography.hazmat.primitives.ciphers.aead import AESGCM
            self._aead = AESGCM(key)
        else:
            self._aead = None

    def wire_encrypt(self, raw: bytes) -> bytes:
        """AES-GCM-wrap one plaintext frame for the wire (secure mode,
        reference msg/async/crypto_onwire.cc rx/tx handlers)."""
        self._enc_ctr += 1
        nonce = self._enc_dir * 4 + self._enc_ctr.to_bytes(8, "little")
        ct = self._aead.encrypt(nonce, raw, b"")
        return encode_frame(CTRL_ENC, self._enc_ctr, {}, nonce + ct)

    def wire_decrypt(self, data: bytes) -> bytes:
        # The nonce is implicit state, not attacker-controlled input: it
        # must be exactly (peer direction byte, rx_counter+1).  Checking
        # the frame's claimed nonce against our own counter rejects
        # replayed or reordered ciphertext that would otherwise pass
        # AEAD and poison the seq window (reference crypto_onwire.cc
        # uses a strictly-incrementing implicit nonce for the same
        # reason).
        peer_dir = b"\x02" if self._enc_dir == b"\x01" else b"\x01"
        expect = peer_dir * 4 + (self._dec_ctr + 1).to_bytes(8, "little")
        if data[:12] != expect:
            raise ValueError(
                "secure frame rejected: nonce out of sequence "
                "(replayed or reordered ciphertext)")
        try:
            pt = self._aead.decrypt(data[:12], data[12:], b"")
        except Exception as e:  # noqa: BLE001 - InvalidTag et al
            # surfaces as a session-preserving wire reset (same path as
            # a crc failure in plain mode)
            raise ValueError(f"secure frame rejected: {e}") from e
        self._dec_ctr += 1
        return pt

    def owes_ack(self) -> bool:
        return self.lossless and self.in_seq > self.last_acked

    def ack_paid(self) -> None:
        """The peer has been told in_seq: by a CTRL_ACK, or by the
        HELLO of a new wire, which states it."""
        self.last_acked = self.in_seq
        self.owed_bytes = 0
        timer, self.ack_timer = self.ack_timer, None
        if timer is not None:
            timer.cancel()

    def take_ack(self) -> bytes:
        """The CTRL_ACK frame for everything delivered so far; the
        caller writes it (through wire_prepare, in write order)."""
        self.ack_paid()
        return encode_ack(self.in_seq)

    def reset_epoch(self) -> None:
        """Abandon this session's delivery state and start a fresh epoch
        in place: new nonce (receiver will not dedup against the old seq
        space) and new cookie (peer resets its dedup window).  Used to
        self-heal after an unacked-window overflow so callers holding a
        cached Connection keep working (at-least-once across the reset;
        the overflow already lost the old window)."""
        self.nonce = uuid.uuid4().hex[:12]
        self.sample_off = sample_offset(self.nonce)
        self.local_cookie = uuid.uuid4().hex[:12]
        self.peer_cookie = None
        self.out_seq = 0
        self.in_seq = 0
        self.ack_paid()
        self.unacked.clear()
        self.broken = False
        self.drop_wire()

    def record_out(self, seq: int, raw: bytes) -> None:
        if self.lossless:
            self.unacked.append((seq, raw))
            if len(self.unacked) > UNACKED_HARD_CAP:
                # peer has not acked for 64k frames: abnormal reset
                self.unacked.clear()
                self.broken = True
                self.drop_wire()

    def trim_acked(self, upto: int) -> None:
        while self.unacked and self.unacked[0][0] <= upto:
            self.unacked.popleft()

    def replay_frames(self, peer_in_seq: int) -> list[bytes]:
        self.trim_acked(peer_in_seq)
        # retention holds parts-tuples (zero-concat send path); join
        # only here, on the rare replay
        return [raw if isinstance(raw, bytes) else b"".join(raw)
                for _, raw in self.unacked]

    def drop_wire(self) -> None:
        import time
        self.down_since = time.monotonic()
        w, self.writer, self.reader = self.writer, None, None
        if w is not None:
            try:
                w.transport.abort()
            except Exception:  # noqa: BLE001
                pass


class Connection:
    """One peer endpoint (reference AsyncConnection).  Client connections
    own their Session and reconnect on failure; accepted connections bind
    to a server-side Session resumed via HELLO and never dial out —
    frames they queue while the wire is down are replayed when the peer
    reconnects."""

    def __init__(self, messenger: "Messenger",
                 peer_addr: tuple[str, int] | None,
                 lossless: bool = True,
                 session: Session | None = None,
                 can_reconnect: bool = True):
        self.messenger = messenger
        self.peer_addr = peer_addr
        self.lossless = lossless
        self.session = session or Session(lossless)
        self.can_reconnect = can_reconnect
        self._closed = False
        self.last_error: str | None = None
        self.peer_entity: str | None = None
        self._label: str | None = None   # cached ledger peer label

    def is_connected(self) -> bool:
        return self.session.writer is not None and not self._closed

    def _peer_label(self) -> str:
        """Short peer name for ledger rows / trace events: the peer
        entity with its per-process uuid dropped ('osd.3'), else
        ip:port.  Cached once the entity is known (it never changes
        afterwards)."""
        lab = self._label
        if lab is None:
            ent = self.peer_entity
            if ent:
                lab = ent.rsplit(".", 1)[0] or ent
                self._label = lab
            elif self.peer_addr:
                lab = f"{self.peer_addr[0]}:{self.peer_addr[1]}"
            else:
                lab = "?"
        return lab

    # -- sending (thread-safe entry) ---------------------------------------

    def send_message(self, msg: Message) -> None:
        m = self.messenger
        # the start of the frame's `hop` (wire ledger, "a frame's
        # trip"): read on the caller's thread, before the frame has
        # the seq that decides whether it is sampled
        led = m.ledger
        m._sendq.put(self, msg, led.now_ns() if led.enabled else 0)

    def _take_queued(self, msg: Message, t_call: int) -> None:
        """On the reactor, from its send queue's flush (module doc,
        "one pass onto the reactor"): write `msg` in place if the
        session can take it now, else hand it to `_send` behind the
        session's frames already there."""
        sess = self.session
        m = self.messenger
        led = m.ledger
        # the end of `hop`: the queue has reached the frame
        t_in = led.now_ns() if t_call else 0
        writer = sess.writer
        if writer is not None and not sess.slow_sends and \
                not sess.broken and not sess.send_lock.locked() and \
                not writer.transport.is_closing() and \
                not sess.reader._paused and \
                len(sess.unacked) < UNACKED_HARD_CAP and \
                not (m.inject_socket_failures or m.inject_delay_prob or
                     m.inject_dispatch_stall):
            if led.enabled:
                led.note_send_path(inline=True)
            self._send_now(msg, t_call, t_in)
            return
        if led.enabled:
            led.note_send_path(inline=False)
        task = m._loop.create_task(self._send(msg, t_call, t_in))
        sess.slow_sends.add(task)
        task.add_done_callback(sess.slow_sends.discard)

    def _send_now(self, msg: Message, t_call: int, t_in: int) -> None:
        """`_send` with nothing to wait for (`_take_queued` checked
        it): the send lock is free and not taken, so `sendlock` is 0;
        the transport is not paused, so `drain()` would return at
        once (a write that pauses it sends the session's next frame
        to `_send`, whose `drain()` waits)."""
        sess = self.session
        raw, tx = self._seal(msg, t_call, t_in, t_in)
        try:
            t_w = self._put(sess.writer, raw, type(msg).__name__, tx)
        except (ConnectionError, OSError) as e:
            # retained: the read loop's re-dial (or the peer's, on an
            # accepted wire) replays it
            self.last_error = str(e)
            sess.drop_wire()
            return
        if t_w:
            self.messenger.ledger.frame_drained(tx, t_w)
        self._note_sent(msg, raw)

    def _seal(self, msg: Message, t_call: int, t_in: int,
              t_lock: int) -> tuple | None:
        """Give `msg` the session's next seq, encode and retain it —
        under the send lock, or with the lock free and no await
        before the write.  Returns (raw, the stamps of a sampled
        frame or None), or None: the frame is dropped (a broken
        session this side cannot re-dial)."""
        sess = self.session
        if sess.broken:
            if not self.can_reconnect:
                # accepted side cannot dial; the peer's next
                # reconnect gets a fresh session (see _on_accept)
                return None
            sess.reset_epoch()
        sess.out_seq += 1
        tx = None
        if t_call and frame_sampled(sess.out_seq, sess.sample_off):
            tx = FrameTx(
                type(msg).__name__,
                (sess.nonce, self.can_reconnect, sess.out_seq),
                t_call, t_in, t_lock)
        # trace-only (common/spans.py: the reactors' CPU is
        # accounted by thread) and never across an await, so
        # msgr.send is two rows a frame — encode here, socket
        # write in _put; type and seq join them to the
        # receiver's msgr.decode / msgr.dispatch rows
        row = spans.annotation(
            "msgr.send", type=type(msg).__name__,
            seq=sess.out_seq) \
            if spans.tracing_now else None
        try:
            raw = msg.encode_parts(sess.out_seq)
            sess.record_out(sess.out_seq, raw)
        finally:
            if row is not None:
                row.__exit__(None, None, None)
        if tx is not None:
            tx.t_enc = self.messenger.ledger.now_ns()
        if sess.broken:       # overflow tripped by this very frame
            if not self.can_reconnect:
                return None
            sess.reset_epoch()          # carry this frame into the
            sess.out_seq = 1            # fresh epoch
            raw = msg.encode_parts(1)
            sess.record_out(1, raw)
            tx = None                   # (its trip is not timed)
        return raw, tx

    async def _send(self, msg: Message, t_call: int = 0,
                    t_in: int = 0) -> None:
        """The coroutine send path: a frame the send queue could not
        write in place (no wire yet, the lock held, a paused
        transport, fault injection, or an earlier frame of the
        session still here)."""
        sess = self.session
        m = self.messenger
        # `t_call` nonzero: the ledger is on, and `t_in` was read
        # where the send queue reached the frame
        async with sess.send_lock:
            sealed = self._seal(msg, t_call, t_in,
                                m.ledger.now_ns() if t_call else 0)
            if sealed is None:
                return
            raw, tx = sealed
            if m.inject_dispatch_stall > 0:
                # fault injection (conf ms_inject_dispatch_stall): the
                # assembled frame sits in the send queue while the
                # reactor "works" — a stalled dispatch's exact shape;
                # the late msgr_send(peer) stamp inherits the blame
                await asyncio.sleep(m.inject_dispatch_stall)
            try:
                if sess.writer is None:
                    if not self.can_reconnect:
                        return  # replayed when the peer reconnects
                    # a wire that was up and dropped (down_since) is
                    # re-dialled HERE when a frame finds it gone before
                    # the read loop does: the same reconnect round
                    # _reconnect counts, and counted once — if this
                    # dial fails, by _reconnect below
                    redial = sess.down_since is not None
                    await self._connect()
                    if redial and m.ledger.enabled:
                        m.stats.note_reconnect(self._peer_label())
                    if self.lossless:
                        # _connect's replay already carried raw
                        self._note_sent(msg, raw)
                        return
                await self._write_raw(raw, type(msg).__name__, tx)
                self._note_sent(msg, raw)
            except (ConnectionError, OSError, asyncio.TimeoutError,
                    asyncio.IncompleteReadError, ValueError) as e:
                # IncompleteReadError (EOF mid-HELLO) and ValueError
                # (corrupt HELLO reply) must not escape: an unhandled
                # reactor-task exception would strand the frame in
                # sess.unacked with no reconnect scheduled
                self.last_error = str(e)
                await self._reconnect()

    def _note_sent(self, msg: Message, raw) -> None:
        """Wire-plane ledger + trace stitch for one sent frame; one
        attribute check when the ledger is off."""
        m = self.messenger
        if not m.ledger.enabled:
            return
        parts = raw if isinstance(raw, tuple) else (raw,)
        nbytes = 0
        for p in parts:
            nbytes += len(p)
        peer = self._peer_label()
        m.stats.note_send(peer, type(msg).__name__, nbytes,
                          len(self.session.unacked))
        top = getattr(msg, "_top", None)
        if top is not None and getattr(top, "is_tracked", False):
            # the interval ENDING here (send-queue + wire write) lands
            # on the op timeline named by peer, so slow-op blame can
            # say "5.1 s in the send queue to osd.7"
            top.mark_event(f"msgr_send({peer})")

    async def _write_raw(self, raw: bytes, mtype: str,
                         tx: FrameTx | None = None) -> None:
        """The coroutine path's write: fault injection hooks live here
        (reference ms_inject_socket_failures / ms_inject_delay applied
        in AsyncConnection::write), then `_put`, then `drain()` with
        the send lock held.  `mtype`: the message's type, for the
        trace row and the by-type frame count; `tx`: the stamps of a
        sampled frame, whose `write` phase ends in `_put`."""
        m = self.messenger
        if m.inject_delay_prob > 0 and \
                m._inject_rng.random() < m.inject_delay_prob:
            await asyncio.sleep(m._inject_rng.random() * m.inject_delay_max)
        if m.inject_socket_failures > 0 and \
                m._inject_rng.randrange(m.inject_socket_failures) == 0:
            m.injected_failures += 1
            self.session.drop_wire()
            raise ConnectionResetError("injected socket failure")
        writer = self.session.writer
        if writer is None:
            # wire dropped while we slept in the injected delay (the
            # accepted-conn read loop nulls it without the send lock)
            raise ConnectionResetError("wire dropped during delayed write")
        t_w = self._put(writer, raw, mtype, tx)
        await writer.drain()
        if t_w:
            m.ledger.frame_drained(tx, t_w)

    def _put(self, writer: asyncio.StreamWriter, raw, mtype: str,
             tx: FrameTx | None) -> int:
        """Single choke point for outgoing data frames: hand one
        sealed frame, and the ack the session owes ahead of it, to
        the transport in one call.  Returns the write-end stamp of a
        sampled frame (where `lat_frame_drain` starts), else 0."""
        m = self.messenger
        led = m.ledger
        sess = self.session
        t_w = 0
        row = spans.annotation("msgr.send", type=mtype,
                               seq=sess.out_seq) \
            if spans.tracing_now else None
        try:
            if tx is not None:
                led.frame_depart(tx)
            parts = raw if isinstance(raw, tuple) else (raw,)
            # the ack this session owes rides this write, ahead of the
            # frame: no segment and no wake-up of its own at the peer
            ack = sess.take_ack() if sess.owes_ack() else None
            if sess.comp is not None or \
                    (sess.secure and sess.conn_key):
                # compression/encryption wrap a whole frame: join
                # first.  Each frame is wrapped in the order it is
                # written (the AES-GCM nonce is a strict counter)
                bufs = [] if ack is None else [sess.wire_prepare(ack)]
                joined = b"".join(parts)
                wired = sess.wire_prepare(joined)
                if led.enabled:
                    m.stats.note_wrapped(
                        self._peer_label(), len(wired),
                        compressed=sess.comp is not None and
                        len(joined) >= sess.comp_min,
                        encrypted=bool(sess.secure and sess.conn_key))
                bufs.append(wired)
            else:
                bufs = parts if ack is None else (ack, *parts)
            _write_once(writer, bufs)
            if tx is not None:
                t_w = led.frame_sent(tx)
            if led.enabled:
                led.note_wire(1, (mtype,),
                              rode=0 if ack is None else 1)
        finally:
            if row is not None:
                row.__exit__(None, None, None)
        return t_w

    async def _connect(self) -> None:
        """Open the TCP stream and run the HELLO exchange: send our
        entity + in_seq (+ authorizer), read the peer's (+ mutual auth
        proof), trim + replay unacked."""
        assert self.peer_addr is not None
        sess = self.session
        m = self.messenger
        loop = asyncio.get_running_loop()
        transport, reader = await loop.create_connection(
            lambda: FrameReceiver(m.ledger, sess), *self.peer_addr)
        writer = asyncio.StreamWriter(transport, reader, None, loop)
        _grow_socket_buffers(writer)
        hello_meta = {
            "entity": m.entity,
            "session": sess.nonce,
            "in_seq": sess.in_seq,
            "peer_cookie": sess.peer_cookie,
            "lossless": self.lossless,
            "secure": m.secure,
            "compress": [m.compress_algo] if m.compress_algo else [],
        }
        authorizer = None
        if m.auth is not None:
            authorizer = m.auth.build_authorizer(secure=m.secure)
            hello_meta["auth"] = authorizer
        writer.write(encode_frame(CTRL_HELLO, 0, hello_meta))
        if m.ledger.enabled:
            m.ledger.note_hello()
        await writer.drain()
        tid, _seq, meta_raw, *_ = await asyncio.wait_for(
            reader.next_frame(), timeout=5.0)
        if tid != CTRL_HELLO:
            writer.close()
            raise ConnectionError(f"expected HELLO, got frame type {tid:#x}")
        meta = json.loads(meta_raw.decode())
        if meta.get("auth_error"):
            # bad credentials are fatal, not retryable
            writer.close()
            self._closed = True
            raise ConnectionError(f"auth rejected: {meta['auth_error']}")
        if authorizer is not None:
            from ..auth.cephx import AuthError
            try:
                key = m.auth.check_reply(
                    authorizer, meta.get("auth_reply"))
            except AuthError as e:
                writer.close()
                self._closed = True
                raise ConnectionError(str(e)) from e
            sess.set_conn_key(key, b"\x01")
            # the secure decision was authenticated by check_reply
            # (mismatch already raised); m.secure == the agreed mode
            sess.secure = m.secure
            # mutual proof: whoever answered holds cluster-side
            # credentials (service key, keyring, or our ticket's
            # session key — all daemon-resident), so frames arriving
            # on this outbound session are from a cluster daemon
            sess.auth_identity = {"entity": meta.get("entity"),
                                  "kind": "service", "caps": ""}
        # compression: the server echoes the chosen algo — accept it
        # only if it is exactly what we offered (a bogus echo must not
        # crash the connect path or select an algo we lack)
        chosen = meta.get("compress")
        if chosen and chosen == m.compress_algo:
            from ..compressor import create
            sess.comp = create(chosen)
            sess.comp_min = m.compress_min
        else:
            sess.comp = None
        self.peer_entity = meta.get("entity")
        cookie = meta.get("cookie")
        if self.lossless and cookie != sess.peer_cookie:
            # New server-side session epoch (restart, prune, or we never
            # saw this session's first reply): its out_seq space starts
            # over at 0, so our dedup window must too, or we would
            # silently drop its first in_seq frames as replays.
            sess.in_seq = 0
            sess.peer_cookie = cookie
        sess.reader, sess.writer = reader, writer
        sess.down_since = None
        sess.ack_paid()           # our HELLO stated in_seq
        frames = sess.replay_frames(int(meta.get("in_seq", 0)))
        if frames and m.ledger.enabled:
            m.stats.note_replay(self._peer_label(), len(frames))
            m.ledger.note_wire(len(frames),
                               [frame_type_name(f) for f in frames])
        for raw in frames:
            writer.write(sess.wire_prepare(raw))
        await writer.drain()
        self.messenger._spawn_read_loop(self)

    async def _reconnect(self) -> None:
        """Lossless policy: reconnect; the HELLO exchange replays exactly
        the frames the peer is missing (reference session reset/replay)."""
        if not self.lossless or not self.can_reconnect or \
                self.peer_addr is None or self._closed:
            return
        m = self.messenger
        if m.ledger.enabled:
            m.stats.note_reconnect(self._peer_label())
        for attempt in range(5):
            try:
                await asyncio.sleep(0.05 * (attempt + 1))
                self.session.drop_wire()
                await self._connect()
                return
            except (ConnectionError, OSError, asyncio.TimeoutError,
                    asyncio.IncompleteReadError, ValueError) as e:
                self.last_error = str(e)
        self._closed = True

    def _owe_ack(self, nbytes: int) -> None:
        """A frame of `nbytes` payload was delivered on a lossless
        session.  Its ack waits for the next data frame written to
        this peer (_put) and is a frame of its own only at the
        ACK_EVERY_* limits or after ACK_DELAY_S."""
        sess = self.session
        sess.owed_bytes += nbytes
        if sess.in_seq - sess.last_acked >= ACK_EVERY_FRAMES or \
                sess.owed_bytes >= ACK_EVERY_BYTES:
            self._send_ack()
        elif sess.ack_timer is None:
            sess.ack_timer = self.messenger._loop.call_later(
                ACK_DELAY_S, self._ack_due)

    def _ack_due(self) -> None:
        # not cancelled, so not paid since it was armed
        self.session.ack_timer = None
        self._send_ack()

    def _send_ack(self) -> None:
        """A CTRL_ACK frame of its own (reactor thread only)."""
        sess = self.session
        writer = sess.writer
        if writer is None:
            return  # the next HELLO states in_seq
        try:
            writer.write(sess.wire_prepare(sess.take_ack()))
        except (ConnectionError, OSError):
            pass  # peer will learn our in_seq from the next HELLO
        led = self.messenger.ledger
        if led.enabled:
            led.note_wire(1, acks=1)

    async def _close(self) -> None:
        self._closed = True
        sess = self.session
        if sess.writer is not None:
            try:
                sess.writer.close()
            except Exception:  # noqa: BLE001
                pass
            sess.writer = None
            sess.reader = None

    def close(self) -> None:
        self.messenger._run_soon(self._close())


class _SendQueue:
    """One reactor loop's frames between `send_message` and the wire
    (module doc, "one pass onto the reactor").  Any thread appends
    (conn, msg, t_call); the append that finds no flush pending
    signals the loop — through its self-pipe from another thread, a
    plain call_soon from the loop's own — and `flush`, one callback,
    takes the frames queued by then in order."""

    def __init__(self, loop: asyncio.AbstractEventLoop):
        self.loop = loop
        self.thread_id: int | None = None   # the loop's, once it runs
        self.frames: collections.deque[tuple] = collections.deque()
        # a flush is scheduled and has not started: appends need not
        # signal.  Two threads may both find it false and both signal;
        # the second flush then finds nothing to take
        self.armed = False

    def put(self, conn: Connection, msg: Message, t_call: int) -> None:
        self.frames.append((conn, msg, t_call))
        if self.armed:
            return
        self.armed = True
        if threading.get_ident() == self.thread_id:
            self.loop.call_soon(self.flush)
            return
        self.loop.call_soon_threadsafe(self.flush)
        led = conn.messenger.ledger
        if led.enabled:
            led.note_send_wake()

    def flush(self) -> None:
        # disarmed BEFORE the first take: an append from here on
        # either is taken below or signals a flush of its own
        self.armed = False
        frames = self.frames
        # only what is queued now: appends from other threads while
        # this runs wait for their own flush, not hold the loop
        for _ in range(len(frames)):
            conn, msg, t_call = frames.popleft()
            try:
                conn._take_queued(msg, t_call)
            except Exception:  # noqa: BLE001 - one frame, not the queue
                traceback.print_exc()


class Messenger:
    """Owns the reactor; binds servers; creates client connections
    (reference Messenger::create + bind + add_dispatcher_head)."""

    _loops: list[asyncio.AbstractEventLoop] = []
    _loop_threads: list[threading.Thread] = []
    _send_queues: list[_SendQueue] = []
    _executor = None
    _next_loop = 0
    _loop_lock = threading.Lock()
    # pool size (reference ms_async_op_threads): loops beyond the core
    # count only add context switches — measured on a 1-core host,
    # 4 loops made the 8-way 128 KiB fan-out *slower* (4.8 vs 4.2 ms).
    # The auto default; conf ms_async_op_threads overrides it through
    # configure_pool() BEFORE the first messenger exists.
    import os as _os
    REACTORS = max(1, min(4, _os.cpu_count() or 1))

    @classmethod
    def configure_pool(cls, reactors) -> None:
        """Startup sizing of the reactor pool (conf
        ms_async_op_threads): applies to the NEXT pool creation — an
        already-running pool keeps its size (pinned loops cannot be
        resized live; the reference reads ms_async_op_threads once at
        start too).  0/None keeps the cpu-count auto size."""
        if reactors:
            n = int(reactors)
            if n > 0:
                cls.REACTORS = n

    def __init__(self, name: str = "client", auth=None,
                 secure: bool = False):
        self.name = name
        # Stable per-instance identity; the session key (reference
        # entity_name_t + nonce in the ProtocolV2 banner).
        self.entity = f"{name}.{uuid.uuid4().hex[:12]}"
        # auth context (auth.CephxAuth) — when set, every accepted
        # connection must present a verifiable authorizer and every
        # outgoing HELLO carries one; secure=True additionally AES-GCM
        # encrypts all frames under the per-connection key
        self.auth = auth
        self.secure = secure
        # on-wire compression opt-in (reference ms_osd_compress_mode);
        # effective only when both endpoints enable it
        self.compress_algo: str | None = None
        self.compress_min = 4096
        self.dispatcher: Dispatcher | None = None
        # fast dispatch (reference ms_fast_dispatch): a predicate
        # selecting messages whose handler is guaranteed non-blocking
        # (no nested synchronous RPC, no long store I/O waits).  Those
        # run INLINE on the reactor, skipping the executor's two
        # context switches per message — the dominant cost of the EC
        # sub-read fan-out on few-core hosts.
        self.fast_dispatch: Callable[[Message], bool] | None = None
        # test hook: drop received messages matching a predicate
        # (message-loss partitions without killing processes)
        self.recv_filter = None
        self.my_addr: tuple[str, int] | None = None
        self._server: asyncio.AbstractServer | None = None
        self._conns: dict[tuple[str, int], Connection] = {}
        self._accepted: list[Connection] = []
        self._sessions: dict[str, Session] = {}
        # fault injection (reference ms_inject_* dev options)
        self.inject_socket_failures = 0   # ~1/N frames resets the socket
        self.inject_delay_prob = 0.0
        self.inject_delay_max = 0.0
        self.injected_failures = 0
        self._inject_rng = random.Random(0xC3B7)
        # conf ms_inject_dispatch_stall: sleep this long in the send
        # path before the wire write (a stalled dispatch for the
        # slow-op blame gates)
        self.inject_dispatch_stall = 0.0
        # blocking-bridge deadline (conf ms_sync_timeout; was a
        # hardcoded 30 s) — expiries count in msgr_sync_timeouts
        self.sync_timeout = 30.0
        # wire-plane flight recorder (msg/msgr_ledger.py): the
        # process ledger plus this messenger's own counter slice
        self.ledger = MsgrLedger.host_instance()
        self.stats = self.ledger.register_messenger(self.entity)
        # pin this messenger to one loop of the pool for its lifetime
        self._loop = self._pick_loop()
        self.stats.reactor = self._loops.index(self._loop)
        self._sendq = self._send_queues[self.stats.reactor]

    # -- reactor pool -------------------------------------------------------

    @classmethod
    def _ensure_pool(cls) -> list[asyncio.AbstractEventLoop]:
        with cls._loop_lock:
            if not cls._loops or \
                    not all(t.is_alive() for t in cls._loop_threads):
                cls._loops, cls._loop_threads = [], []
                cls._send_queues = []
                # Wide dispatcher pool, SHARED across loops: handlers may
                # block on nested RPC round-trips (shard stat/attr fetches
                # inside a client-op handler), so the pool must exceed the
                # plausible nesting across all in-process daemons
                # (single-host test clusters share this pool).
                from concurrent.futures import ThreadPoolExecutor
                cls._executor = ThreadPoolExecutor(
                    max_workers=96, thread_name_prefix="msgr-dispatch")
                meters = []
                for i in range(cls.REACTORS):
                    # a selector that times itself: the loop's wall
                    # time as asleep + running (wire ledger, "reactor
                    # loops")
                    meter = ReactorSelector()
                    meters.append(meter)
                    loop = asyncio.SelectorEventLoop(meter)
                    loop.set_default_executor(cls._executor)
                    sendq = _SendQueue(loop)

                    def run(loop=loop, meter=meter, sendq=sendq):
                        asyncio.set_event_loop(loop)
                        sendq.thread_id = threading.get_ident()
                        meter.loop_started()
                        loop.run_forever()

                    t = threading.Thread(target=run,
                                         name=f"msgr-reactor-{i}",
                                         daemon=True)
                    t.start()
                    cls._loops.append(loop)
                    cls._loop_threads.append(t)
                    cls._send_queues.append(sendq)
                # arm the per-reactor loop-lag probe on the fresh pool
                # (wire-plane flight recorder, msg/msgr_ledger.py)
                msgr_ledger().attach_reactors(cls._loops, meters=meters)
                # the reactor threads' whole CPU (wire work and the
                # handlers fast-dispatched inline on them) is the
                # `host_spans` set's `msgr.reactor_cpu`
                spans.account_threads("msgr.reactor", "msgr-reactor-")
            return cls._loops

    @classmethod
    def _pick_loop(cls) -> asyncio.AbstractEventLoop:
        loops = cls._ensure_pool()
        with cls._loop_lock:
            cls._next_loop += 1
            return loops[cls._next_loop % len(loops)]

    @classmethod
    def dispatch_executor(cls):
        """The shared dispatcher thread pool — for handlers that must
        hand work OFF the reactor (blocking pipeline continuations)."""
        cls._ensure_pool()
        return cls._executor

    @classmethod
    def submit_dispatch(cls, span: str, fn, *args) -> None:
        """dispatch_executor().submit with the exception fence the
        bare Future lacks: a pipeline continuation that raises must
        surface a traceback, not die unobserved in the Future.  Queue
        wait and run time land in the wire-plane ledger's
        lat_msgr_qwait / lat_msgr_dispatch histograms; `span` is the
        continuation's own name in the span table (the caller's layer,
        not `msgr.`: what runs is the caller's work)."""
        led = msgr_ledger()
        t_sub = led.dispatch_submit() if led.enabled else None

        def run():
            t_run = led.dispatch_run(t_sub, span) \
                if t_sub is not None else None
            try:
                fn(*args)
            except Exception:  # noqa: BLE001
                import traceback
                traceback.print_exc()
            finally:
                if t_run is not None:
                    led.dispatch_done(t_run)

        cls.dispatch_executor().submit(run)

    def _run_soon(self, coro) -> None:
        # self._loop is pinned for the messenger's lifetime: pool
        # loops never stop while healthy, and run_coroutine_threadsafe
        # queues correctly even on a loop that has not entered
        # run_forever yet — re-picking here could split one session's
        # coroutines (and its asyncio.Lock) across two loops
        asyncio.run_coroutine_threadsafe(coro, self._loop)

    def send_batch(self, pairs) -> None:
        """`send_message` for each (conn, msg) of `pairs`, in order,
        with the `hop` clock read once for the batch: a k-way shard
        fan-out joins the send queue behind one wake-up, and a peer
        that is dead or re-dialling holds back only its own session's
        frames (the coroutine path is per session)."""
        t_call = self.ledger.now_ns() if self.ledger.enabled else 0
        for conn, msg in pairs:
            conn.messenger._sendq.put(conn, msg, t_call)

    def _run_sync(self, coro, timeout: float | None = None):
        """Blocking bridge into the reactor.  The default deadline is
        conf ms_sync_timeout (was a hardcoded 30 s); an expiry counts
        in the ledger (msgr_sync_timeouts) before surfacing — the
        caller still needs the exception, but the event is no longer
        invisible."""
        fut = asyncio.run_coroutine_threadsafe(coro, self._loop)
        try:
            return fut.result(self.sync_timeout if timeout is None
                              else timeout)
        except _FuturesTimeout:
            if self.ledger.enabled:
                self.stats.note_sync_timeout()
            raise

    # -- server side --------------------------------------------------------

    def add_dispatcher(self, dispatcher: Dispatcher) -> None:
        self.dispatcher = dispatcher

    def bind(self, addr: tuple[str, int]) -> tuple[str, int]:
        """Bind and start accepting; port 0 picks a free port."""

        async def _bind():
            return await asyncio.get_running_loop().create_server(
                lambda: FrameReceiver(self.ledger,
                                      on_accept=self._on_accept),
                addr[0], addr[1])

        self._server = self._run_sync(_bind())
        sock = self._server.sockets[0]
        self.my_addr = sock.getsockname()[:2]
        return self.my_addr

    async def _on_accept(self, reader: FrameReceiver,
                         writer: asyncio.StreamWriter) -> None:
        """Accept = read the peer's HELLO, bind/resume its Session, reply
        with our in_seq, replay anything it is missing."""
        try:
            tid, _seq, meta_raw, *_ = await asyncio.wait_for(
                reader.next_frame(), timeout=10.0)
            if tid != CTRL_HELLO:
                writer.close()
                return
            meta = json.loads(meta_raw.decode())
        except (asyncio.IncompleteReadError, asyncio.TimeoutError,
                ConnectionError, OSError, ValueError):
            writer.close()
            return
        entity = str(meta.get("entity", ""))
        lossless = bool(meta.get("lossless", True))
        nonce = str(meta.get("session", ""))
        claimed_entity = entity
        # authorizer gate (reference AuthAuthorizeHandler at accept):
        # with an auth context, no verifiable authorizer -> no session
        auth_identity = None
        conn_key = None
        auth_reply = None
        if self.auth is not None:
            from ..auth.cephx import AuthError
            try:
                auth_identity, conn_key, auth_reply = \
                    self.auth.verify_authorizer(meta.get("auth"),
                                                server_secure=self.secure)
            except AuthError as e:
                try:
                    writer.write(encode_frame(CTRL_HELLO, 0, {
                        "entity": self.entity, "auth_error": str(e)}))
                    if self.ledger.enabled:
                        self.ledger.note_hello()
                    await writer.drain()
                except (ConnectionError, OSError):
                    pass
                writer.close()
                return
            # Session resumption is a capability of the AUTHENTICATED
            # identity: a peer holding different credentials must not
            # resume (and thereby hijack + drain the replay window of)
            # another daemon's session just by claiming its entity
            # string from a sniffed HELLO.
            entity = f"{auth_identity['entity']}/{claimed_entity}"
        self._prune_sessions()
        if lossless:
            sess = self._sessions.get(entity)
            # a broken session (unacked overflow) must not be resumed:
            # its _send path drops frames, so hand out a fresh one — the
            # new cookie makes the client reset its dedup window
            if sess is None or sess.nonce != nonce or sess.broken:
                sess = Session(lossless=True, nonce=nonce)
                self._sessions[entity] = sess
        else:
            sess = Session(lossless=False, nonce=nonce)
        sess.drop_wire()          # supersede any stale stream
        _grow_socket_buffers(writer)
        sess.reader, sess.writer = reader, writer
        reader.sess = sess
        sess.auth_identity = auth_identity
        sess.set_conn_key(conn_key, b"\x02")
        sess.secure = bool(auth_identity and
                           auth_identity.get("secure"))
        # compression: accept the client's offer when we opt in too
        offered = meta.get("compress") or []
        chosen = None
        if self.compress_algo and offered:
            from ..compressor import available, create
            for algo in offered:
                if algo in available():
                    chosen = algo
                    sess.comp = create(algo)
                    sess.comp_min = self.compress_min
                    break
        if chosen is None:
            sess.comp = None
        conn = Connection(self, None, lossless=lossless, session=sess,
                          can_reconnect=False)
        conn.peer_entity = claimed_entity
        peer = writer.get_extra_info("peername")
        conn.peer_addr = peer[:2] if peer else None
        # one facade per session: drop superseded ones from the registry
        self._accepted = [c for c in self._accepted
                          if c.session is not sess]
        self._accepted.append(conn)
        try:
            reply_meta = {"entity": self.entity, "in_seq": sess.in_seq,
                          "cookie": sess.local_cookie,
                          "secure": sess.secure,
                          "compress": chosen}
            if auth_reply is not None:
                reply_meta["auth_reply"] = auth_reply
            writer.write(encode_frame(CTRL_HELLO, 0, reply_meta))
            if self.ledger.enabled:
                self.ledger.note_hello()
            sess.ack_paid()       # the HELLO stated in_seq
            # The client's in_seq only counts frames of THIS session
            # epoch if it has seen our cookie; a stale epoch's in_seq
            # must trim nothing or undelivered replies would be lost.
            peer_in = int(meta.get("in_seq", 0)) \
                if meta.get("peer_cookie") == sess.local_cookie else 0
            frames = sess.replay_frames(peer_in)
            if frames and self.ledger.enabled:
                self.stats.note_replay(conn._peer_label(), len(frames))
                self.ledger.note_wire(
                    len(frames), [frame_type_name(f) for f in frames])
            for raw in frames:
                writer.write(sess.wire_prepare(raw))
            await writer.drain()
        except (ConnectionError, OSError):
            writer.close()
            return
        self._spawn_read_loop(conn)

    def _prune_sessions(self, max_down: float = 600.0) -> None:
        """Reap server-side sessions whose wire has been down for a long
        time (their entities are per-process uuids, so a dead peer never
        comes back) and accepted-conn facades whose wire was superseded."""
        import time
        now = time.monotonic()
        for entity, sess in list(self._sessions.items()):
            if sess.writer is None and sess.down_since is not None and \
                    now - sess.down_since > max_down:
                del self._sessions[entity]
        self._accepted = [c for c in self._accepted
                          if c.session.reader is not None]

    # -- client side --------------------------------------------------------

    def connect(self, addr: tuple[str, int],
                lossless: bool = True) -> Connection:
        """Get-or-create the client connection for addr.  Lossless and
        lossy conns are separate sessions (the reference runs heartbeats
        on dedicated lossy messengers for the same reason: ping retention
        and replay make no sense)."""
        key = (addr[0], addr[1], lossless)
        conn = self._conns.get(key)
        if conn is None or conn._closed:
            # Carry the old session into the replacement connection: the
            # server resumes sessions by entity, so a fresh seq space
            # would collide with its dedup window (frames silently
            # dropped as "already seen").  A broken session (unacked
            # overflow) starts over with a new nonce.
            old = conn
            sess = None
            if old is not None and not old.session.broken:
                sess = old.session
            conn = Connection(self, (addr[0], addr[1]), lossless=lossless,
                              session=sess)
            self._conns[key] = conn
        return conn

    # -- read loop ----------------------------------------------------------

    def _spawn_read_loop(self, conn: Connection) -> None:
        self._run_soon(self._read_loop(conn, conn.session.reader))

    async def _read_loop(self, conn: Connection,
                         reader: FrameReceiver) -> None:
        sess = conn.session
        led = self.ledger
        try:
            while not conn._closed and reader is sess.reader:
                # t_head: nonzero while this frame's trip is timed
                # (wire ledger, "a frame's trip"); a frame already
                # complete is taken without suspending
                tid, seq, meta_raw, data, pcrc, t_head, t_body = \
                    await reader.next_frame()
                if reader is not sess.reader:
                    # epoch reset while we were blocked in next_frame: a
                    # buffered old-epoch frame must not touch the fresh
                    # epoch's seq window (in_seq poisoning)
                    break
                wrapped = tid in (CTRL_ENC, CTRL_COMP)
                if tid == CTRL_ENC:
                    if sess.conn_key is None:
                        raise ValueError("encrypted frame on plain session")
                    inner = sess.wire_decrypt(data)  # raises on tamper
                    tid, seq, meta_raw, data, pcrc = _parse_raw(inner)
                elif sess.secure and sess.conn_key is not None and \
                        tid != CTRL_HELLO:
                    # plaintext data frame on a secure session: reject
                    raise ValueError("plaintext frame on secure session")
                if tid == CTRL_COMP:
                    algo = json.loads(meta_raw.decode()).get("a", "")
                    inner = sess.wire_decompress(algo, data)
                    tid, seq, meta_raw, data, pcrc = _parse_raw(inner)
                if t_head and wrapped and not (
                        tid < CTRL_HELLO and
                        frame_sampled(seq, sess.sample_off)):
                    t_head = 0    # unwrapped: the rule leaves it out
                if tid == CTRL_ACK:
                    sess.trim_acked(seq)
                    continue
                if tid == CTRL_HELLO:
                    continue  # late/duplicate hello: ignore
                if conn.lossless and seq <= sess.in_seq:
                    # replayed frame we already delivered: re-ack, drop
                    # (reference ProtocolV2 in_seq dedup on session resume)
                    conn._send_ack()
                    continue
                if t_head:
                    slot, t_arr = led.frame_claim(
                        (sess.nonce, not conn.can_reconnect, seq),
                        t_head)
                row = spans.annotation("msgr.decode",
                                       type=type_name(tid), seq=seq) \
                    if spans.tracing_now else None
                try:
                    msg = Message.decode(tid, seq, meta_raw, data, pcrc)
                finally:
                    if row is not None:
                        row.__exit__(None, None, None)
                # the arguments of led.frame_delivered, for whichever
                # thread runs the handler's first line
                frame = (slot, type(msg).__name__, t_arr, t_body,
                         led.now_ns()) if t_head else None
                # ingest stamp for op tracking (reference
                # Message::recv_stamp set by the messenger): dispatch
                # latency is attributable even when the executor queues
                msg.recv_stamp = time.time()
                if self.ledger.enabled:
                    self.stats.note_recv(
                        conn._peer_label(), type(msg).__name__,
                        Message.HEADER_SIZE + len(meta_raw) +
                        len(data) + 4)
                sess.in_seq = seq
                if sess.lossless:
                    conn._owe_ack(len(data))
                if self.recv_filter is not None and \
                        self.recv_filter(msg):
                    # injected receive-side loss (partition testing):
                    # the frame is consumed and acked but never reaches
                    # the dispatcher — indistinguishable, to the
                    # protocol above, from a network that ate it
                    continue
                if self.dispatcher is not None:
                    if self.fast_dispatch is not None and \
                            self.fast_dispatch(msg):
                        # inline on the reactor (handler is declared
                        # non-blocking); fence exceptions so a handler
                        # bug cannot kill the read loop
                        # (a trace row only: lat_msgr_dispatch and
                        # the span table keep to executor-run handlers)
                        row = spans.annotation(
                            "msgr.dispatch." + type(msg).__name__,
                            seq=seq) \
                            if spans.tracing_now else None
                        if frame is not None:
                            led.frame_delivered(*frame)
                        try:
                            self.dispatcher(conn, msg)
                        except Exception:  # noqa: BLE001
                            import traceback
                            traceback.print_exc()
                        finally:
                            if row is not None:
                                row.__exit__(None, None, None)
                    else:
                        # dispatch off-reactor so handlers may send
                        # synchronously / block on nested RPCs; the
                        # ledger times queue wait + handler run so
                        # "dispatcher slow" is attributable
                        if led.enabled:
                            t_sub = led.dispatch_submit()

                            def _timed(d=self.dispatcher, c=conn,
                                       mm=msg, t=t_sub, fr=frame):
                                t_run = led.dispatch_run(
                                    t, "msgr.dispatch."
                                    + type(mm).__name__, fr)
                                try:
                                    d(c, mm)
                                finally:
                                    led.dispatch_done(t_run)

                            await asyncio.get_event_loop() \
                                .run_in_executor(None, _timed)
                        else:
                            await asyncio.get_event_loop() \
                                .run_in_executor(None, self.dispatcher,
                                                 conn, msg)
        except (asyncio.IncompleteReadError, ConnectionError, OSError):
            # Wire died under us.  Mark the wire down (starts the prune
            # clock for accepted sessions); client conns re-dial so
            # pending server replies (in the peer's unacked window) flow.
            if not conn.can_reconnect:
                if sess.reader is reader:
                    sess.drop_wire()
            elif not conn._closed:
                async with sess.send_lock:
                    if sess.reader is reader or sess.reader is None:
                        sess.drop_wire()
                        await conn._reconnect()
        except ValueError as e:
            # crc/corruption: abort this wire; the session (seq window)
            # survives, so a reconnect replays cleanly (reference
            # ProtocolV2 treats a bad crc as a session-preserving reset)
            conn.last_error = str(e)
            if sess.reader is reader:
                sess.drop_wire()
            if conn.can_reconnect and not conn._closed:
                async with sess.send_lock:
                    if sess.writer is None:
                        await conn._reconnect()

    # -- lifecycle ----------------------------------------------------------

    def shutdown(self) -> None:
        async def _stop():
            if self._server is not None:
                self._server.close()
            for c in list(self._conns.values()) + self._accepted:
                await c._close()
            self._sessions.clear()
            self._accepted.clear()
            self._conns.clear()
        try:
            self._run_sync(_stop(), timeout=5)
        except Exception:  # noqa: BLE001
            pass
