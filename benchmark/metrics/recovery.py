"""Recovery after an OSD's restart, from the program's own counters and
spans (docs/TRACING.md "Control plane"), read between the harness's
first snapshot and the third reading a restart cell's `verify` takes
once the cluster is active+clean again (`recovered_snapshot` in the
verdict: the harness's second reading comes right after the drain,
and recovery need not have ended there).  A cell without that third
reading, or a program without a counter, gives nothing for its
metric.

- recover_s: the restarted OSD's boot (its ledger's `osd_boot_at`,
  the newest boot in the cluster) -> the latest transition of any PG
  to clean on any OSD (`pg_last_clean_at`): a PG the restart degraded
  turns clean when a recovery pass has put every object on every
  acting shard;
- recovery_shard_MBps: shard bytes recovery pushed (`osd.N`
  `recovery_pushed_bytes`) / recover_s;
- recovery_scanned_per_recovered: objects the recovery passes examined
  (`pg_objects_scanned`) per object they rebuilt, copied or pushed
  (`pg_objects_recovered`), ROADMAP A9;
- recover_reads_ms_mean, recover_decode_wait_ms_mean,
  recover_push_ms_mean: the spans `ec.recover_reads` (a recovery
  slice's survivor reads issued -> the replies), `ec.recover_decode_wait`
  (blocked on a recovery decode's ticket), `ec.recover_push` (a
  group's verify against the hinfo and push), self wall / count;
- peering_ms_mean: `lat_peering_total` of every OSD, mean of a round.

A restarted daemon's counters start from zero: its delta is the new
daemon's whole count."""

from span_dumps import span_set

_REC = {"source": "program_counter", "layer": "recovery"}
METRICS = {
    "recover_s": dict(_REC, unit="s", better="lower",
                      moves="write_p95_ms"),
    "recovery_shard_MBps": dict(_REC, unit="MB/s", better="higher",
                                moves="write_MBps"),
    "recovery_scanned_per_recovered": dict(
        _REC, unit="ratio", better="lower", moves="write_MBps"),
    "recover_reads_ms_mean": dict(_REC, unit="ms", better="lower",
                                  moves="write_p95_ms"),
    "recover_decode_wait_ms_mean": dict(_REC, unit="ms", better="lower",
                                        moves="write_p95_ms"),
    "recover_push_ms_mean": dict(_REC, unit="ms", better="lower",
                                 moves="write_p95_ms"),
    "peering_ms_mean": dict(_REC, unit="ms", better="lower",
                            moves="write_p95_ms"),
}
_SPANS = {"recover_reads_ms_mean": "ec.recover_reads",
          "recover_decode_wait_ms_mean": "ec.recover_decode_wait",
          "recover_push_ms_mean": "ec.recover_push"}


def _osd_values(snap: dict, set_prefix: str, key: str) -> list:
    """Per OSD, the values of `key` in its sets named `set_prefix*`."""
    return [[c[key] for name, c in dump.items()
             if name.startswith(set_prefix) and key in c]
            for dump in snap["osd_perf"]]


def _restarted(before: dict, after: dict) -> list[bool]:
    """Per OSD: did its daemon boot again between the readings (its
    ledger's `osd_boot_at` moved)?"""
    return [a != b for b, a in zip(
        _osd_values(before, "pg_ledger", "osd_boot_at"),
        _osd_values(after, "pg_ledger", "osd_boot_at"))]


def _delta(before: dict, after: dict, set_prefix: str, key: str,
           part=lambda v: v, count=lambda v: v) -> float:
    """Sum over OSDs of `part` after - before, a restarted daemon's
    (or, on a program without the boot gauge, one whose `count` went
    down) counted from zero."""
    total = 0.0
    booted = _restarted(before, after)
    for i, (b, a) in enumerate(zip(_osd_values(before, set_prefix, key),
                                   _osd_values(after, set_prefix, key))):
        restarted = (i < len(booted) and booted[i]) or \
            sum(count(v) for v in a) < sum(count(v) for v in b)
        total += sum(part(v) for v in a) \
            - (0 if restarted else sum(part(v) for v in b))
    return total


def _gauges(snap: dict, key: str) -> list[float]:
    return [v for vals in _osd_values(snap, "pg_ledger", key)
            for v in vals if v]


def read(ctx: dict) -> dict:
    after = (ctx.get("verdict") or {}).get("recovered_snapshot")
    if not after:
        return {}
    before = ctx["before"]
    out = {}
    boots, cleans = _gauges(after, "osd_boot_at"), \
        _gauges(after, "pg_last_clean_at")
    if boots and cleans and max(cleans) > max(boots):
        out["recover_s"] = max(cleans) - max(boots)
        pushed = _delta(before, after, "osd.", "recovery_pushed_bytes")
        if pushed > 0:
            out["recovery_shard_MBps"] = pushed / out["recover_s"] / 1e6
    recovered = _delta(before, after, "pg_ledger", "pg_objects_recovered")
    if recovered > 0:
        out["recovery_scanned_per_recovered"] = _delta(
            before, after, "pg_ledger", "pg_objects_scanned") / recovered
    s0, s1 = span_set(before), span_set(after)
    for metric, name in _SPANS.items():
        n = s1.get(f"{name}_n", 0) - s0.get(f"{name}_n", 0)
        if n > 0:
            out[metric] = 1e3 * (s1[f"{name}_wall"]
                                 - s0.get(f"{name}_wall", 0.0)) / n

    def peering(part):
        return _delta(before, after, "pg_ledger", "lat_peering_total",
                      part=part, count=lambda h: h["count"])
    rounds = peering(lambda h: h["count"])
    if rounds > 0:
        out["peering_ms_mean"] = 1e3 * peering(lambda h: h["sum"]) \
            / rounds
    return out
