"""Device idle time by what the host was doing: device busy intervals
+ host span rows -> seconds of device idle under each top-level span
name, and `no_span`.  Pure arithmetic on rows, no jax, no chip.

Rows are the flattened form of trace_reduce.py
    {"line", "name", "start_ns", "dur_ns"}
with `line` the host thread for a span row.  Both kinds of row must be
on one time base: the profiler writes the program's spans
(ceph_tpu/common/spans.py, TraceAnnotation) into the host plane of the
trace whose device plane holds the kernels.

A span is TOP-LEVEL when no other span of its own thread contains it
(`osd.sub_write_apply` inside `msgr.dispatch.MOSDECSubOpWrite` counts
as the latter).  Many threads can be inside a span at one instant, so
every idle instant is shared equally among the top-level spans open
across all threads just then; an instant with none is `no_span`.  The
shares therefore add up to the idle time exactly:

    sum(result.values()) == window - union(device busy)

Wiring this into run.py's `breakdown.idle_gaps` takes an edit to
run.py (it deletes the trace before readers run) and trace_reduce.py
(it keeps device planes only): a `benchmark` PR's job.
"""

from __future__ import annotations

NO_SPAN = "no_span"


def top_level(span_rows: list[dict]) -> list[tuple[int, int, str]]:
    """[(start, end, name)] of the spans no other span of their own
    thread contains."""
    by_line: dict = {}
    for r in span_rows:
        by_line.setdefault(r["line"], []).append(
            (r["start_ns"], r["start_ns"] + r["dur_ns"], r["name"]))
    out = []
    for rows in by_line.values():
        cover = None
        # a parent sorts before its children: earlier start, or the
        # same start and a later end
        for s, e, name in sorted(rows, key=lambda t: (t[0], -t[1])):
            if cover is None or s >= cover:
                out.append((s, e, name))
                cover = e
    return out


def idle_intervals(busy: list[tuple[int, int]], lo: int, hi: int
                   ) -> list[tuple[int, int]]:
    """The stretches of [lo, hi] no busy interval covers."""
    out, cur = [], lo
    for s, e in sorted(busy):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            out.append((cur, s))
        cur = max(cur, e)
    if hi > cur:
        out.append((cur, hi))
    return out


def attribute_idle(busy: list[tuple[int, int]], span_rows: list[dict],
                   lo: int, hi: int) -> dict[str, float]:
    """{top-level span name | "no_span": seconds of device idle}
    inside [lo, hi] (ns on the rows' time base)."""
    spans = top_level(span_rows)
    # sweep over every boundary: between two neighbours the set of
    # open spans and the device's state do not change
    edges = {lo, hi}
    idle = idle_intervals(busy, lo, hi)
    for s, e in idle:
        edges.update((s, e))
    for s, e, _ in spans:
        if e > lo and s < hi:
            edges.update((max(s, lo), min(e, hi)))
    points = sorted(edges)
    opens = sorted(spans)                   # by start
    out: dict[str, float] = {}
    active: list[tuple[int, int, str]] = []
    nxt = gap = 0
    for a, b in zip(points, points[1:]):
        while nxt < len(opens) and opens[nxt][0] <= a:
            active.append(opens[nxt])
            nxt += 1
        active = [sp for sp in active if sp[1] > a]
        while gap < len(idle) and idle[gap][1] <= a:
            gap += 1
        if gap == len(idle) or not idle[gap][0] <= a < idle[gap][1]:
            continue                        # the device is busy here
        if not active:
            out[NO_SPAN] = out.get(NO_SPAN, 0.0) + (b - a) / 1e9
            continue
        share = (b - a) / 1e9 / len(active)
        for _, _, name in active:
            out[name] = out.get(name, 0.0) + share
    return out
