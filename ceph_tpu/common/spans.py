"""Thread-executed spans: self wall time and self thread-CPU per name,
visible in a profiler trace on that trace's own clock.

One call at the hooks the recorders already have (the wire ledger's
dispatch_run/dispatch_done, the launch queue's launch/finalize, the
EC drains, the OSD op path): `with span("ec.assemble", pgid=...)` or
the `begin()`/`end()` pair where the hook itself is a pair.

A span does two things:

* It opens a `jax.profiler.TraceAnnotation(name, **ids)` while a
  profiler session is recording (and only then: `TraceMe.is_enabled()`
  is one call; a process that never imported jax pays a dict lookup).
  The row lands in the HOST plane of the same `.xplane.pb` whose
  device plane holds the kernels, on one clock, so a device-idle gap
  can be laid against what the host was doing.  `ids` (trace_id,
  launch id, pgid) join a row to `dump_historic_ops` / `launch
  profile`.
* On exit it adds its SELF wall seconds (`perf_counter_ns`), SELF
  thread-CPU seconds (`thread_time_ns`) and 1 to a process-wide table
  by name.  Self = own duration minus the spans opened inside it on
  the same thread (a thread-local stack).  Wall inside a span includes
  waiting — for a lock, an RPC, the GIL — and thread-CPU does not, so
  wall >> cpu says "waiting", wall ~= cpu says "working".

What keeps that cheap enough to leave on:

* Thread-CPU is SAMPLED: reading it is a system call (0.4 us on bare
  Linux, 6.2 us under the gVisor sandbox the benchmark's chip machine
  runs in: 13.9 us a span with both reads against 3.0 us without, and
  the budget is 2), the wall clock is not.  Every CPU_EVERY-th
  top-level span of a name reads it, and so does everything opened
  inside that one, so within a sampled tree the self-CPU arithmetic is
  exact; `<name>_cpu` is the sampled sum scaled by n / n_sampled.
* A span is ON when the recorder whose layer it times is (`on=`: the
  wire ledger for `msgr.*`, the device profiler for `lq.*` / `ec.*`,
  the op tracker for `osd.*` / `store.*`; all are on by default).  Off,
  it is two clock reads — `wall_ns` for the hook that takes its sample
  from the span — and touches neither stack, table nor trace.

The table is the perf set `host_spans` (`<name>_wall`, `<name>_cpu`
seconds, `<name>_n`, and the `process_cpu_s` gauge read at dump time):
one object per process, registered into exactly ONE daemon's
collection per host by the `_perf_registered` rule the wire ledger and
the device profiler follow.

Not in the table:

* Per-MESSAGE work on the messenger's reactor threads (frame encode,
  socket write, decode, fast-dispatched handlers) gets no table span:
  at ~50 a client op they cost more than they tell (a 4 KiB write lost
  6.6 % in 6 of 6 chip pairs with them).  Those sites open an
  `annotation()` while `tracing_now` — a row in a profiler trace, one
  attribute read otherwise — and the reactors' CPU is accounted whole,
  by thread: `account_threads(label, thread-name prefix)` makes the
  set report the gauge `<label>_cpu` (the LIVE threads' CPU seconds
  from /proc, read at dump time).  That figure is everything the
  reactor threads run: asyncio and socket calls, frame encode, decode
  and crc, and the handlers the OSD fast-dispatches inline
  (osd/daemon.py: MOSDOp's op-pool submit, MOSDECSubOpRead's shard
  store read, the read / sub-write replies' routing, pings) — wire
  work and those handlers, not wire work alone.

Rules for callers: a span begins and ends on ONE thread and never
covers an `await` (coroutines of one reactor thread would interleave
on its stack).  `end()` is idempotent, so a hook pair can also close
in a `finally`.
"""

from __future__ import annotations

import os
import sys
import threading
import time

from .perf_counters import PerfCounters

# module attributes so a test can inject a clock
_wall_ns = time.perf_counter_ns
_cpu_ns = time.thread_time_ns
# one top-level span in this many (per name) reads the thread-CPU
# clock, with its whole tree
CPU_EVERY = 16

_tls = threading.local()
# name -> [self wall ns, sampled self cpu ns, count, sampled count];
# rows are created under the lock and updated with plain adds under
# the GIL (the PerfCounters rule: no lock on the hot path)
_table: dict[str, list] = {}
_table_lock = threading.Lock()
_trace_me = None        # jax.profiler.TraceAnnotation, once jax is there


def _resolve_trace_me() -> bool:
    """Bind jax.profiler.TraceAnnotation once jax is in the process;
    never imports jax itself (a session can only be on in a process
    that already did)."""
    global _trace_me
    if "jax" not in sys.modules:
        return False
    from jax.profiler import TraceAnnotation
    _trace_me = TraceAnnotation
    return True


# Is a profiler session recording?  Asked of jax at most every 5 ms,
# by whichever span begins next, and kept here: the hot path (and the
# messenger's trace-only sites) reads a module attribute instead of
# making a call a span.
tracing_now = False
_asked_at = 0
_ASK_EVERY_NS = 5_000_000


def _ask_tracing(now_ns: int) -> None:
    global tracing_now, _asked_at
    _asked_at = now_ns
    tracing_now = (_trace_me is not None or _resolve_trace_me()) \
        and _trace_me.is_enabled()


def annotation(name: str, **ids):
    """An OPEN row of the recording trace, for the trace-only sites;
    call only while `tracing_now` and close it in a `finally`:

        row = spans.annotation(..) if spans.tracing_now else None
        try: ...
        finally:
            if row is not None:
                row.__exit__(None, None, None)
    """
    ann = _trace_me(name, **{
        k: v if isinstance(v, (int, str)) else str(v)
        for k, v in ids.items()})
    ann.__enter__()
    return ann


class Span:
    """One open (then closed) span.  After `end()`, `wall_ns` holds
    its whole duration (children included) for the hook that wants the
    same interval for its own histogram.  `on=False` (its recorder is
    off) leaves only that: no stack, no table row, no trace row.  The
    hot path is written for few calls: the thread's open spans are a
    linked list through `parent`, and the table row is looked up
    once."""

    __slots__ = ("name", "on", "ids", "t0", "c0", "child_wall",
                 "child_cpu", "ann", "wall_ns", "cpu_on", "parent", "row")

    def __init__(self, name: str, on: bool = True, **ids):
        self.name = name
        self.on = on
        self.ids = ids
        self.child_wall = 0
        self.child_cpu = 0
        self.ann = None
        self.wall_ns: int | None = None

    def __enter__(self) -> "Span":
        if not self.on:
            self.t0 = _wall_ns()
            return self
        row = _table.get(self.name)
        if row is None:
            with _table_lock:
                row = _table.setdefault(self.name, [0, 0, 0, 0])
        self.row = row
        try:
            parent = _tls.top
        except AttributeError:
            parent = None
        self.parent = parent
        _tls.top = self
        # thread-CPU: every CPU_EVERY-th top-level span of a name, and
        # whatever opens inside one that reads it
        self.cpu_on = parent.cpu_on if parent is not None \
            else row[2] % CPU_EVERY == 0
        if self.cpu_on:
            self.c0 = _cpu_ns()
        self.t0 = now = _wall_ns()
        if now - _asked_at > _ASK_EVERY_NS:
            _ask_tracing(now)
        if tracing_now:
            # a profiler session is recording: the row goes into its
            # host plane, on its clock
            self.ann = annotation(self.name, **self.ids)
        return self

    @property
    def wall_s(self) -> float:
        return (self.wall_ns or 0) * 1e-9

    def end(self, *exc) -> None:
        """Close the span (idempotent); also its `__exit__`."""
        if self.wall_ns is not None:
            return
        wall = _wall_ns() - self.t0
        self.wall_ns = wall
        if not self.on:
            return
        cpu = _cpu_ns() - self.c0 if self.cpu_on else 0
        if self.ann is not None:
            self.ann.__exit__(None, None, None)
        try:
            top = _tls.top
        except AttributeError:
            top = None
        while top is not None and top is not self:
            # hook pairs left open by an exception path go with it
            top = top.parent
        if top is None:
            return          # ended off its thread: no honest numbers
        parent = _tls.top = self.parent
        if parent is not None:
            parent.child_wall += wall
            parent.child_cpu += cpu
        row = self.row
        row[0] += wall - self.child_wall
        row[2] += 1
        if self.cpu_on:
            row[1] += cpu - self.child_cpu
            row[3] += 1

    __exit__ = end


span = Span     # context manager form: `with span("store.commit", pgid=p):`


def begin(name: str, on: bool = True, **ids) -> Span:
    """Hook-pair form: returns the open span; close it with `end`."""
    return Span(name, on, **ids).__enter__()


def end(sp: Span | None) -> None:
    if sp is not None:
        sp.end()


def inside() -> bool:
    """Is a span open on the calling thread?  The `on=` of a span that
    has no recorder of its own and times a part of whatever encloses
    it (`store.clone` inside `store.commit`)."""
    return getattr(_tls, "top", None) is not None


# label -> thread-name prefix of the threads whose whole CPU the set
# reports under `<label>_cpu`
_thread_groups: dict[str, str] = {}
_CLK_TCK = os.sysconf("SC_CLK_TCK") if hasattr(os, "sysconf") else 100


def account_threads(label: str, name_prefix: str) -> None:
    _thread_groups[label] = name_prefix


def thread_cpu_s(native_id: int) -> float:
    """utime + stime of one thread of this process, from /proc (10 ms
    ticks; 0.0 where there is no /proc)."""
    try:
        with open(f"/proc/self/task/{native_id}/stat") as f:
            fields = f.read().rsplit(")", 1)[1].split()
        return (int(fields[11]) + int(fields[12])) / _CLK_TCK
    except (OSError, ValueError, IndexError):
        return 0.0


def thread_groups() -> dict[str, float]:
    """{label: CPU seconds of the group's LIVE threads} as of now —
    a gauge: a thread that exits takes its seconds with it."""
    live = [(t.name, t.native_id) for t in threading.enumerate()
            if t.native_id]
    return {label: sum(thread_cpu_s(i) for name, i in live
                       if name.startswith(prefix))
            for label, prefix in list(_thread_groups.items())}


def table() -> dict[str, tuple[float, float, int]]:
    """{name: (self wall s, self cpu s, count)} as of now; the CPU is
    the sampled sum scaled to all `count` spans."""
    with _table_lock:
        rows = list(_table.items())
    return {name: (w * 1e-9, c * 1e-9 * n / m if m else 0.0, n)
            for name, (w, c, n, m) in rows if n}


def reset() -> None:
    """Tests only: forget every row, the calling thread's open spans
    and which daemon exports the set, and ask jax anew whether a
    session records."""
    global _asked_at
    with _table_lock:
        _table.clear()
    _tls.__dict__.clear()
    _asked_at = 0
    _perf._perf_registered = False


class _HostSpanCounters(PerfCounters):
    """The `host_spans` perf set: a view of the table, rendered when
    dumped (so the hot path never builds a key string)."""

    def __init__(self):
        super().__init__("host_spans", {})

    def dump(self) -> dict:
        out: dict = {"process_cpu_s": time.process_time()}
        for name, (wall, cpu, n) in table().items():
            out[f"{name}_wall"] = wall
            out[f"{name}_cpu"] = cpu
            out[f"{name}_n"] = n
        for label, cpu in thread_groups().items():
            out[f"{label}_cpu"] = cpu
        return out

    def schema(self) -> dict:
        out = {"process_cpu_s": "gauge"}
        for name in table():
            out[f"{name}_wall"] = out[f"{name}_cpu"] = "time"
            out[f"{name}_n"] = "u64"
        for label in _thread_groups:
            out[f"{label}_cpu"] = "gauge"
        return out


_perf = _HostSpanCounters()


def host_spans() -> PerfCounters:
    """The process's one `host_spans` set (see module doc for who
    registers it)."""
    return _perf
