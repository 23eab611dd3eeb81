"""Wire: what each reactor thread did with the window.  The pool's
event loops run on a selector that times itself
(ceph_tpu/msg/msgr_ledger.py ReactorSelector, docs/TRACING.md "Reactor
loops"): per reactor `i` the `msgr_ledger` set carries
`reactor_select_s.<i>` (seconds asleep in `select`),
`reactor_wall_s.<i>` (asleep + running: every second since the loop
started is one or the other), `reactor_cpu_s.<i>` (the thread's utime
+ stime from /proc, 10 ms ticks) and `reactor_sleeps.<i>` (selects
that were allowed to block: a wake-up each).  From their deltas:

- `reactor_busy_share_max`: the busiest reactor's running share of the
  window, max over i of 1 - select / wall;
- `reactor_load_imbalance`: that share over the mean of all reactors'
  (1.0 = even; messengers are pinned to reactors round-robin);
- `reactor_stall_share`: of the time the reactors were running, the
  share they were not on a CPU — sum(wall - select - cpu) / sum(wall -
  select), floored at 0: waiting for the GIL, or preempted (their
  sockets do not block);
- `wire_wakeups_per_frame`: sum of sleeps / data frames written
  (`msgr_frames_out`).

A program without the rows (the parent of the PR that added them)
gives nothing."""

from perf_dumps import counter_delta

_COUNT = {"source": "program_counter", "layer": "wire"}
METRICS = {
    "reactor_busy_share_max": dict(
        _COUNT, unit="share", better="lower", moves="write_MBps"),
    "reactor_load_imbalance": dict(
        _COUNT, unit="ratio", better="lower", moves="write_p95_ms"),
    "reactor_stall_share": dict(
        _COUNT, unit="share", better="lower", moves="write_MBps"),
    "wire_wakeups_per_frame": dict(
        _COUNT, unit="count", better="lower", moves="write_MBps"),
}


def _ledger(snap: dict) -> dict:
    """The process's one `msgr_ledger` set, in whichever OSD's dump
    carries it."""
    for dump in snap["osd_perf"]:
        if "msgr_ledger" in dump:
            return dump["msgr_ledger"]
    return {}


def read(ctx: dict) -> dict:
    before, after = _ledger(ctx["before"]), _ledger(ctx["after"])
    ids = [key.split(".", 1)[1] for key in after
           if key.startswith("reactor_wall_s.")]

    def delta(key: str, i: str) -> float:
        return after.get(f"{key}.{i}", 0) - before.get(f"{key}.{i}", 0)

    rows = [(delta("reactor_wall_s", i), delta("reactor_select_s", i),
             delta("reactor_cpu_s", i), delta("reactor_sleeps", i))
            for i in ids]
    rows = [r for r in rows if r[0] > 0]
    if not rows:
        return {}
    busy = [1.0 - select / wall for wall, select, _, _ in rows]
    out = {"reactor_busy_share_max": max(busy)}
    mean = sum(busy) / len(busy)
    if mean > 0:
        out["reactor_load_imbalance"] = max(busy) / mean
    running = sum(wall - select for wall, select, _, _ in rows)
    if running > 0:
        out["reactor_stall_share"] = max(
            0.0, sum(wall - select - cpu
                     for wall, select, cpu, _ in rows) / running)
    frames = counter_delta(ctx, "msgr_ledger", "msgr_frames_out")
    if frames > 0:
        out["wire_wakeups_per_frame"] = \
            sum(sleeps for _, _, _, sleeps in rows) / frames
    return out
