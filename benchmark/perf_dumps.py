"""Helpers the counter readers share: sums over the `perf dump`s of
all OSDs, as deltas between the two snapshots of a run."""

from __future__ import annotations


def _walk(snap: dict, set_prefix: str, key: str):
    for dump in snap["osd_perf"]:
        for set_name, counters in dump.items():
            if set_name.startswith(set_prefix) and key in counters:
                yield counters[key]


def counter_delta(ctx: dict, set_prefix: str, key: str) -> float:
    """Sum of a plain counter over every matching set of every OSD."""
    return (sum(_walk(ctx["after"], set_prefix, key))
            - sum(_walk(ctx["before"], set_prefix, key)))


def hist_delta(ctx: dict, set_prefix: str, key: str,
               first_osd_only: bool = False) -> tuple[float, int]:
    """(sum of seconds, count) a histogram took between the snapshots,
    over every matching set of every OSD.  Sets that are one object
    for the whole process (the wire ledger, the device profiler)
    appear in every OSD's dump: read those from the first OSD only."""
    def total(snap):
        rows = list(_walk(snap, set_prefix, key))
        if first_osd_only:
            rows = rows[:1]
        return (sum(r["sum"] for r in rows),
                sum(r["count"] for r in rows))
    s1, n1 = total(ctx["after"])
    s0, n0 = total(ctx["before"])
    return s1 - s0, n1 - n0


def client_ops_between(ctx: dict) -> int:
    """Client writes acknowledged between the two snapshots."""
    lo, hi = ctx["before"]["t"], ctx["after"]["t"]
    return sum(1 for _, _, t1, err in ctx["run"]["ops"]
               if err is None and lo <= t1 <= hi)
