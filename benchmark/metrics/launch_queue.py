"""The host launch queue: how many runs (objects' stripe sets) ride
one device launch, and how long a submission waits for its launch."""

from perf_dumps import hist_delta

METRICS = {
    "lq_runs_per_launch": {
        "unit": "runs", "better": "higher", "source": "program_counter",
        "layer": "launch queue", "moves": "write_MBps"},
    "lq_batch_wait_ms_mean": {
        "unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "launch queue", "moves": "write_p95_ms"},
}


def read(ctx: dict) -> dict:
    q0, q1 = ctx["before"]["launch_queue"], ctx["after"]["launch_queue"]
    out = {}
    if q0 and q1 and q1["launches"] > q0["launches"]:
        out["lq_runs_per_launch"] = (
            (q1["coalesced_runs"] - q0["coalesced_runs"])
            / (q1["launches"] - q0["launches"]))
    total, n = hist_delta(ctx, "", "lat_ec_batch_wait",
                          first_osd_only=True)
    if n > 0:
        out["lq_batch_wait_ms_mean"] = 1e3 * total / n
    return out
