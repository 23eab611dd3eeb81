"""Readers of the device trace (traced runs only): what the chip did
inside the traced slice of the window."""

from deploy import ec_geometry
from roofline import chunk_bytes, encode_work, roofline_seconds

METRICS = {
    "device_idle_share": {
        "unit": "share", "better": "lower", "source": "device_trace",
        "layer": "device", "moves": "write_MBps"},
    "kernel_fused_roofline": {
        "unit": "%", "better": "higher", "source": "device_trace",
        "layer": "kernels", "moves": "write_MBps"},
    "kernel_fused_GBps": {
        "unit": "GB/s", "better": "higher", "source": "device_trace",
        "layer": "kernels", "moves": "write_MBps"},
}


def read(ctx: dict) -> dict:
    trace = ctx["trace"]
    if not trace or not trace["planes"] or ctx["rehearsal"]:
        return {}
    out = {"device_idle_share":
           1.0 - trace["busy_s"] / trace["window_s"]}
    fused = trace["families"].get("fused_encode")
    if not fused or fused["seconds"] <= 0 or \
            not trace["launch_queue_bytes"]:
        return out
    # the work of the slice, from shapes: the launch queue's input
    # bytes between the profiler's start and stop are whole objects'
    # data shards (k * chunk each, padded to whole stripes)
    k, m, su = ec_geometry(ctx["config"])
    chunk = chunk_bytes(ctx["traffic"]["object_bytes"], k, su)
    runs = trace["launch_queue_bytes"] / (k * chunk)
    work = {key: val * runs
            for key, val in encode_work(k, m, chunk).items()}
    least_s, _ = roofline_seconds(work, ctx["device"]["kind"])
    out["kernel_fused_roofline"] = 100.0 * least_s / fused["seconds"]
    out["kernel_fused_GBps"] = work["bytes_in"] / fused["seconds"] / 1e9
    return out
