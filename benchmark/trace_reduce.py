"""From a jax.profiler trace to device numbers: planes -> device-op
intervals -> union.  Kept with the benchmark so that every PR reads
the same number the same way; selfcheck.py reduces the recorded trace
in testdata/ to known numbers.

A trace is first flattened to plain rows
    {"plane", "line", "name", "start_ns", "dur_ns"}
(only the device planes' rows are kept), which is also the form the
recorded trace is stored in.  Everything after that is arithmetic on
rows and needs neither jax nor a chip.
"""

from __future__ import annotations

import glob
import json
import os
import re

_HERE = os.path.dirname(os.path.abspath(__file__))
DEVICE_PLANE = re.compile(r"^/device:(TPU|GPU):\d+$")


def load_families(path: str | None = None) -> dict:
    with open(path or os.path.join(_HERE, "kernel_families.json")) as f:
        return json.load(f)


def find_xplane(trace_dir: str) -> str:
    paths = sorted(glob.glob(os.path.join(
        trace_dir, "plugins", "profile", "*", "*.xplane.pb")))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def device_rows(xplane_path: str) -> list[dict]:
    """Every event of every device plane of an .xplane.pb."""
    from jax.profiler import ProfileData
    rows = []
    for plane in ProfileData.from_file(xplane_path).planes:
        if not DEVICE_PLANE.match(plane.name):
            continue
        for line in plane.lines:
            for ev in line.events:
                rows.append({"plane": plane.name, "line": line.name,
                             "name": ev.name,
                             "start_ns": int(ev.start_ns),
                             "dur_ns": int(ev.duration_ns)})
    return rows


def union_ns(intervals) -> int:
    """Total length covered by [(start, end), ...]."""
    total, cur_s, cur_e = 0, None, None
    for s, e in sorted(intervals):
        if cur_e is None or s > cur_e:
            if cur_e is not None:
                total += cur_e - cur_s
            cur_s, cur_e = s, e
        elif e > cur_e:
            cur_e = e
    if cur_e is not None:
        total += cur_e - cur_s
    return total


def gaps_ns(intervals, lo: int, hi: int) -> list[int]:
    """Lengths of the stretches of [lo, hi] that no interval covers."""
    out, cur = [], lo
    for s, e in sorted(intervals):
        s, e = max(s, lo), min(e, hi)
        if e <= s:
            continue
        if s > cur:
            out.append(s - cur)
        cur = max(cur, e)
    if hi > cur:
        out.append(hi - cur)
    return out


def family_of(name: str, families: dict) -> str:
    for fam in families["families"]:
        if any(re.search(p, name) for p in fam["patterns"]):
            return fam["family"]
    return families["other"]


def reduce_rows(rows: list[dict], window_s: float,
                families: dict | None = None) -> dict:
    """Device numbers of one traced window.

    busy_s: per device plane the union of the intervals of the lines
    named in families["busy_lines"] (the ops themselves; the first of
    those lines the plane has), averaged over the planes.
    families: seconds and launches per kernel family, from the lines
    named in families["kernel_lines"] (whole XLA modules, so that a
    kernel's time includes every op of its program), summed over
    planes.
    """
    families = families or load_families()
    planes = sorted({r["plane"] for r in rows})
    if not planes:
        return {"planes": 0, "busy_s": 0.0, "window_s": window_s,
                "families": {}, "device_ops": [], "idle_gaps": []}

    def pick(plane, wanted):
        have = {r["line"] for r in rows if r["plane"] == plane}
        for name in wanted:
            if name in have:
                return name
        return None

    busy, fam_s, fam_n, ops, gaps = [], {}, {}, {}, []
    for plane in planes:
        bl = pick(plane, families["busy_lines"])
        kl = pick(plane, families["kernel_lines"])
        iv = [(r["start_ns"], r["start_ns"] + r["dur_ns"]) for r in rows
              if r["plane"] == plane and r["line"] == bl]
        busy.append(union_ns(iv))
        if iv:
            lo = min(s for s, _ in iv)
            gaps += gaps_ns(iv, lo, max(e for _, e in iv))
        for r in rows:
            if r["plane"] != plane or r["line"] != kl:
                continue
            fam = family_of(r["name"], families)
            fam_s[fam] = fam_s.get(fam, 0) + r["dur_ns"]
            fam_n[fam] = fam_n.get(fam, 0) + 1
            key = f"{fam}:{r['name'].split('(')[0]}"
            ops[key] = ops.get(key, 0) + r["dur_ns"]
    busy_s = sum(busy) / len(busy) / 1e9
    buckets = {"gaps_under_10ms": 0, "gaps_10_to_100ms": 0,
               "gaps_over_100ms": 0}
    for g in gaps:
        key = ("gaps_under_10ms" if g < 10e6 else
               "gaps_10_to_100ms" if g < 100e6 else "gaps_over_100ms")
        buckets[key] += g
    idle = [[k, v / 1e9 / len(planes)] for k, v in buckets.items()]
    idle.append(["longest_single_gap", max(gaps, default=0) / 1e9])
    top = sorted(ops.items(), key=lambda kv: -kv[1])[:10]
    return {
        "planes": len(planes), "busy_s": busy_s, "window_s": window_s,
        "families": {f: {"seconds": fam_s[f] / 1e9, "launches": fam_n[f]}
                     for f in fam_s},
        "device_ops": [[k, v / 1e9] for k, v in top],
        "idle_gaps": idle,
    }
