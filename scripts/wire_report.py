#!/usr/bin/env python3
"""One benchmark run with the wire ledger's whole account beside it.

    python3 scripts/wire_report.py --out chiprun_out/<name>.json \
        --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Runs `benchmark/run.py` as it is (same arguments, same result line as
the last stdout line) and writes, as one JSON object to `--out`, what
the process's `msgr_ledger` set took between the run's two counter
snapshots and the result line does not carry (docs/TRACING.md "A
frame's trip", "Reactor loops"):

- `trips`: per message type, the sampled frames' mean microseconds in
  each of the eight phases (each over the halves that recorded it),
  their sum, and `drain`;
- `frames_by_type`: data frames written per client op, by message
  type, with `CTRL_ACK` and `CTRL_HELLO` beside them;
- `reactors`: per reactor loop the seconds asleep, running, on a CPU
  and stalled (running, not on a CPU), wake-ups, and the messengers
  pinned to it;
- `sends`: data frames the reactors' send queues wrote in place and
  handed to the coroutine path, the share in place, and frames a
  cross-thread wake-up (messenger.py module doc, "one pass onto the
  reactor");
- `locks`: the object lock's waits and holds over all OSDs;
- `checks`: the numbers the two instruments share (`op_wire_in` of the
  op tracker against the `MOSDOp` trip; `msgr.reactor_cpu` against
  the reactors' own rows).

Nothing here is a benchmark file: the yardstick is `benchmark/`.
"""

from __future__ import annotations

import argparse
import json
import os
import sys

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, ROOT)

from ceph_tpu.msg.msgr_ledger import FRAME_PHASES, trip_means  # noqa: E402


def _delta(after: dict, before: dict, key: str) -> float:
    return after.get(key, 0) - before.get(key, 0)


def _hist(snaps, prefix: str, key: str) -> tuple[float, int]:
    import perf_dumps
    return perf_dumps.hist_delta(
        {"before": snaps[0], "after": snaps[1]}, prefix, key)


def report(snaps: list, ledgers: list, pinned: dict, ops: int) -> dict:
    b, a = ledgers
    types = sorted(k.split(".", 1)[1] for k in a
                   if k.startswith("frame_n."))
    trips = {}
    for t in types:
        row = trip_means({
            "n": _delta(a, b, f"frame_n.{t}"),
            "rx_n": _delta(a, b, f"frame_rx_n.{t}"),
            "transit_n": _delta(a, b, f"frame_transit_n.{t}"),
            "ns": {p: _delta(a, b, f"frame_ns.{t}.{p}")
                   for p in FRAME_PHASES + ("drain",)}})
        row["trip_us"] = sum(row["mean_us"].get(p, 0.0)
                             for p in FRAME_PHASES)
        trips[t] = row
    frames = {k.split(".", 1)[1]: _delta(a, b, k) for k in a
              if k.startswith("msgr_frames_out_by_type.")}
    reactors = []
    for i in sorted({k.split(".", 1)[1] for k in a
                     if k.startswith("reactor_wall_s.")}, key=int):
        wall, asleep, cpu = (_delta(a, b, f"reactor_{k}_s.{i}")
                             for k in ("wall", "select", "cpu"))
        reactors.append({
            "reactor": int(i), "pinned": pinned.get(int(i), []),
            "wall_s": wall, "select_s": asleep,
            "running_s": wall - asleep, "cpu_s": cpu,
            "stalled_s": wall - asleep - cpu,
            "busy_share": 1 - asleep / wall if wall else None,
            "sleeps": _delta(a, b, f"reactor_sleeps.{i}"),
            "iterations": _delta(a, b, f"reactor_iterations.{i}")})
    inline, slow, wakes = (_delta(a, b, f"msgr_{k}") for k in (
        "sends_inline", "sends_slow", "send_wakes"))
    wait_s, wait_n = _hist(snaps, "optracker.", "lat_obj_lock_wait")
    hold_s, hold_n = _hist(snaps, "optracker.", "lat_obj_lock_hold")
    wire_in_s, wire_in_n = _hist(snaps, "optracker.",
                                 "lat_phase_osd_op_wire_in")
    import span_dumps
    spans_cpu = span_dumps.span_delta(
        {"before": snaps[0], "after": snaps[1]}, "_cpu")
    return {
        "client_ops": ops,
        "frames_out": _delta(a, b, "msgr_frames_out"),
        "frames_per_op_by_type": {
            t: n / ops for t, n in sorted(frames.items(),
                                          key=lambda kv: -kv[1])}
        if ops else {},
        "frames_by_type": frames,
        "trips": trips,
        "samples_unpaired": _delta(a, b, "msgr_frame_samples_unpaired"),
        "stamps_evicted": _delta(a, b, "msgr_frame_stamps_evicted"),
        "reactors": reactors,
        "sends": {"inline": inline, "slow": slow, "wakes": wakes,
                  "inline_share": inline / (inline + slow)
                  if inline + slow else None,
                  "frames_per_wake": (inline + slow) / wakes
                  if wakes else None},
        "locks": {"wait_ms_mean": 1e3 * wait_s / wait_n if wait_n
                  else None, "waits": wait_n,
                  "hold_ms_mean": 1e3 * hold_s / hold_n if hold_n
                  else None, "holds": hold_n,
                  "locked_ops_per_client_op": wait_n / ops if ops
                  else None},
        "checks": {
            "op_wire_in_ms_mean": 1e3 * wire_in_s / wire_in_n
            if wire_in_n else None,
            "mosdop_trip_ms": trips["MOSDOp"]["trip_us"] / 1e3
            if "MOSDOp" in trips else None,
            "msgr_reactor_cpu_s": spans_cpu.get("msgr.reactor"),
            "sum_reactor_cpu_s": sum(r["cpu_s"] for r in reactors)},
    }


def main() -> int:
    ap = argparse.ArgumentParser()
    ap.add_argument("--out", required=True)
    args, rest = ap.parse_known_args()
    import deploy
    import run
    from ceph_tpu.msg.msgr_ledger import msgr_ledger
    snaps, ledgers, pinned = [], [], {}
    take = deploy.Deployment.snapshot

    def snapshot(self):
        snap = take(self)
        led = msgr_ledger()
        snaps.append(snap)
        ledgers.append(led.perf.dump())
        pinned.clear()
        for name, row in led.status()["messengers"].items():
            pinned.setdefault(row["reactor"], []).append(
                name.rsplit(".", 1)[0])
        return snap

    deploy.Deployment.snapshot = snapshot
    # the readers of a traced run count the client ops acknowledged
    # between the snapshots from run.py's own record: listen in
    import perf_dumps
    acked, count = [], perf_dumps.client_ops_between

    def client_ops_between(ctx):
        acked.append(count(ctx))
        return acked[-1]

    perf_dumps.client_ops_between = client_ops_between
    code = run.main(rest)
    if len(snaps) >= 2:
        lo, hi = snaps[0]["t"], snaps[1]["t"]
        osd_ops = int(perf_dumps.counter_delta(
            {"before": snaps[0], "after": snaps[1]}, "osd.", "op"))
        # an untraced run reads no per-layer metric: per OSD op then
        out = report(snaps[:2], ledgers[:2], pinned,
                     acked[0] if acked else osd_ops)
        out["osd_ops"] = osd_ops
        out["ops_are"] = "client ops" if acked else "OSD ops"
        out["window_s"] = hi - lo
        os.makedirs(os.path.dirname(os.path.abspath(args.out)),
                    exist_ok=True)
        with open(args.out, "w") as f:
            json.dump(out, f, indent=1)
    return code


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        code = e.code if isinstance(e.code, int) else 1
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
