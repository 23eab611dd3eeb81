"""OSD op path: mean time a client op spends in its primary OSD, from
the op tracker's `lat_total_osd_op` histograms (the tracked-op type of
every client op; a write cell sends writes only)."""

from perf_dumps import hist_delta

METRICS = {
    "osd_op_ms_mean": {
        "unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "OSD op path", "moves": "write_p95_ms"},
}


def read(ctx: dict) -> dict:
    total, n = hist_delta(ctx, "optracker.", "lat_total_osd_op")
    return {"osd_op_ms_mean": 1e3 * total / n} if n > 0 else {}
