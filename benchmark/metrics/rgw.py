"""The S3 gateway: where a PUT's time goes inside the gateway, how
many RADOS ops it costs, how much of the OSDs' op load is index and
accounting work on the replicated pool, and what the client sees
beyond the gateway.  All from the gateway's own `perf dump` (set
`rgw`, ceph_tpu/rgw/store.py; docs/TRACING.md "The S3 gateway"), which
the generator reads beside each of the harness's two snapshots and
hands on in its run record (`gateway_perf`).  A cell without a
gateway, or a program without the counters, reports nothing here.

The `rgw_put_*` histograms take one sample per plain object PUT
answered 200, so frontend + data write + index + accounting is the
gateway's PUT less its own Python between the calls (routing, JSON,
the reply)."""

from perf_dumps import counter_delta

_MS = {"unit": "ms", "better": "lower", "source": "program_counter",
       "layer": "gateway", "moves": "write_p95_ms"}
_SPLIT = {
    "rgw_put_ms_mean": "rgw_put_lat",
    "rgw_frontend_ms_mean": "rgw_put_frontend_lat",
    "rgw_data_write_ms_mean": "rgw_put_data_lat",
    "rgw_index_ms_per_put": "rgw_put_index_lat",
    "rgw_account_ms_per_put": "rgw_put_account_lat",
}

METRICS = {name: dict(_MS) for name in _SPLIT}
METRICS["rgw_rados_ops_per_put"] = {
    "unit": "ratio", "better": "lower", "source": "program_counter",
    "layer": "gateway", "moves": "write_MBps"}
METRICS["rgw_index_ops_share"] = {
    "unit": "share", "better": "lower", "source": "program_counter",
    "layer": "OSD op path", "moves": "write_MBps"}
METRICS["client_outside_rgw_ms_mean"] = dict(_MS, layer="client")


def read(ctx: dict) -> dict:
    dumps = ctx["run"].get("gateway_perf") or {}
    before = dumps.get("before", {}).get("rgw")
    after = dumps.get("after", {}).get("rgw")
    if not before or not after:
        return {}

    def delta(key: str) -> float:
        return after.get(key, 0) - before.get(key, 0)

    puts = delta("rgw_put")
    if puts <= 0:
        return {}
    out = {}
    for name, key in _SPLIT.items():
        if key in after and key in before:
            out[name] = 1e3 * (after[key]["sum"]
                               - before[key]["sum"]) / puts
    out["rgw_rados_ops_per_put"] = delta("rgw_put_rados_ops") / puts
    # of the ops the OSDs received between the harness's snapshots,
    # those the gateway sent to its replicated pool (the gateway's
    # dumps are read a moment inside the harness's)
    meta = ctx["config"]["gateway"]["meta_pool"]["name"]
    osd_ops = counter_delta(ctx, "osd.", "op")
    if osd_ops > 0:
        out["rgw_index_ops_share"] = \
            delta(f"rgw_rados_ops.{meta}") / osd_ops
    lo, hi = dumps["before"]["t"], dumps["after"]["t"]
    acked = [t1 - t0 for _, t0, t1, err in ctx["run"]["ops"]
             if err is None and lo <= t1 <= hi]
    if acked and "rgw_put_ms_mean" in out:
        out["client_outside_rgw_ms_mean"] = \
            1e3 * sum(acked) / len(acked) - out["rgw_put_ms_mean"]
    return out
