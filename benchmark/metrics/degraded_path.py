"""What one OSD down adds to an overwrite, layer by layer, as deltas
between the two snapshots (docs/PIPELINE.md "Overwrites on a degraded
PG"):

- OSD op path: the share of the data pool's PGs that serve with a
  hole in their acting set at the window's opening (`ec.<pgid>` gauge
  `ec_acting_holes`, set by each PG's last recovery pass);
- EC backend: the share of pre-reads that lost a data shard and had
  to reconstruct (`ec_rmw_reconstructs` of `ec_rmw_reads`), what one
  of those costs from "every data shard has answered, one failed" to
  the reconstructed stripe (`lat_ec_rmw_reconstruct`: the parity
  round trip and the decode; inside `rmw_read_ms_mean`, inside
  `op_prepare_ms_mean`), and the shard transactions really sent per
  client write (`ec_sub_writes_sent`: k+m whole, k+m-1 with a hole);
- launch queue: how long a dispatch thread blocks on its decode
  launch (`lat_ec_decode_wait`) and how many decode submissions ride
  one launch (`ec_host_decode_runs` / `ec_host_decode_launches`).

A program without a counter gives nothing for its metric."""

from counter_presence import has_counter
from perf_dumps import _walk, client_ops_between, counter_delta, hist_delta

_EC = {"source": "program_counter", "layer": "EC backend"}
_LQ = {"source": "program_counter", "layer": "launch queue"}
METRICS = {
    "degraded_pg_share": {
        "unit": "share", "better": "lower", "source": "program_counter",
        "layer": "OSD op path", "moves": "write_MBps"},
    "rmw_reconstruct_share": dict(_EC, unit="share", better="lower",
                                  moves="write_MBps"),
    "rmw_reconstruct_ms_mean": dict(_EC, unit="ms", better="lower",
                                    moves="write_p95_ms"),
    "subwrites_per_op": dict(_EC, unit="ratio", better="lower",
                             moves="write_MBps"),
    "decode_wait_ms_mean": dict(_LQ, unit="ms", better="lower",
                                moves="write_p95_ms"),
    "lq_decode_runs_per_launch": dict(_LQ, unit="runs", better="higher",
                                      moves="write_MBps"),
}


def read(ctx: dict) -> dict:
    out = {}
    if has_counter(ctx, "ec.", "ec_acting_holes"):
        holes = list(_walk(ctx["before"], "ec.", "ec_acting_holes"))
        out["degraded_pg_share"] = sum(1 for h in holes if h > 0) \
            / ctx["config"]["pool"]["pg_num"]
    reads = counter_delta(ctx, "ec.", "ec_rmw_reads")
    if reads > 0 and has_counter(ctx, "ec.", "ec_rmw_reconstructs"):
        out["rmw_reconstruct_share"] = counter_delta(
            ctx, "ec.", "ec_rmw_reconstructs") / reads
    for name, hist in (("rmw_reconstruct_ms_mean",
                        "lat_ec_rmw_reconstruct"),
                       ("decode_wait_ms_mean", "lat_ec_decode_wait")):
        total, n = hist_delta(ctx, "ec.", hist)
        if n > 0:
            out[name] = 1e3 * total / n
    ops = client_ops_between(ctx)
    if ops > 0 and has_counter(ctx, "ec.", "ec_sub_writes_sent"):
        out["subwrites_per_op"] = counter_delta(
            ctx, "ec.", "ec_sub_writes_sent") / ops
    launches = counter_delta(ctx, "ec_host_queue",
                             "ec_host_decode_launches")
    if launches > 0 and has_counter(ctx, "ec_host_queue",
                                    "ec_host_decode_runs"):
        out["lq_decode_runs_per_launch"] = counter_delta(
            ctx, "ec_host_queue", "ec_host_decode_runs") / launches
    return out
