"""The process's one `host_spans` perf set (ceph_tpu/common/spans.py:
`<span>_wall`, `<span>_cpu` seconds and `<span>_n` per span name, plus
`process_cpu_s`) as the span readers see it: found in whichever OSD's
`perf dump` carries it, as a delta between the two snapshots."""

from __future__ import annotations


def span_set(snap: dict) -> dict:
    """The `host_spans` set among the OSDs' dumps, or {} (a program
    without spans)."""
    for dump in snap["osd_perf"]:
        if "host_spans" in dump:
            return dump["host_spans"]
    return {}


def span_delta(ctx: dict, suffix: str) -> dict:
    """{span name: after - before} of every `<name><suffix>` key, for
    suffix "_wall", "_cpu" or "_n"."""
    s0, s1 = span_set(ctx["before"]), span_set(ctx["after"])
    return {key[:-len(suffix)]: val - s0.get(key, 0)
            for key, val in s1.items() if key.endswith(suffix)}


def process_cpu_delta(ctx: dict) -> float:
    s0, s1 = span_set(ctx["before"]), span_set(ctx["after"])
    return s1.get("process_cpu_s", 0.0) - s0.get("process_cpu_s", 0.0)
