"""EC backend, the read-modify-write half: what a partial-stripe write
reads back before it can encode (`ec.<pgid>` `ec_rmw_reads`,
`ec_rmw_read_bytes`, `ec_rmw_cache_hit_bytes`, `lat_ec_rmw_read`) and
how its parity was launched (`ec_plain_drains` of `ec_drain_submits`).
The wait itself lies inside the op's `prepare` phase; `rmw_read_ms_mean`
says how much of `op_prepare_ms_mean` it is.  A program without these
counters gives nothing."""

from counter_presence import has_counter, user_bytes_between
from perf_dumps import counter_delta, hist_delta

_EC = {"source": "program_counter", "layer": "EC backend"}
METRICS = {
    "rmw_read_ms_mean": dict(_EC, unit="ms", better="lower",
                             moves="write_p95_ms"),
    "rmw_read_bytes_per_user_byte": dict(_EC, unit="ratio",
                                         better="lower",
                                         moves="write_MBps"),
    "rmw_cache_hit_share": dict(_EC, unit="share", better="higher",
                                moves="write_MBps"),
    "ec_plain_drain_share": dict(_EC, unit="share", better="lower",
                                 moves="write_MBps"),
}


def read(ctx: dict) -> dict:
    out = {}
    total, n = hist_delta(ctx, "ec.", "lat_ec_rmw_read")
    if n > 0:
        out["rmw_read_ms_mean"] = 1e3 * total / n
    if has_counter(ctx, "ec.", "ec_rmw_read_bytes"):
        read_bytes = counter_delta(ctx, "ec.", "ec_rmw_read_bytes")
        user = user_bytes_between(ctx)
        if user > 0:
            out["rmw_read_bytes_per_user_byte"] = read_bytes / user
        if read_bytes > 0:
            out["rmw_cache_hit_share"] = counter_delta(
                ctx, "ec.", "ec_rmw_cache_hit_bytes") / read_bytes
    drains = counter_delta(ctx, "ec.", "ec_drain_submits")
    if drains > 0 and has_counter(ctx, "ec.", "ec_plain_drains"):
        out["ec_plain_drain_share"] = counter_delta(
            ctx, "ec.", "ec_plain_drains") / drains
    return out
