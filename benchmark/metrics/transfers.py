"""Launch queue, host side of a launch: the self wall time of staging
(`ec.h2d`: pad, concatenate, hand the words to the device) and of the
read-back (`ec.d2h_wait`: blocks on the device, then copies) per
launch; the bytes a launch hands to the device (staged data + the
constant matrices it uploads anew each time: what exceeds the padded
data is the re-upload) and reads back (parity + crc bits); and the
share of the launched bytes that is padding.  The byte figures are
exact counts from the shapes handed to and returned by the jit."""

from perf_dumps import counter_delta
from span_dumps import span_delta

_PER_LAUNCH = {"h2d_ms_per_launch": "ec.h2d",
               "d2h_wait_ms_per_launch": "ec.d2h_wait"}

METRICS = {name: {"unit": "ms", "better": "lower",
                  "source": "program_counter", "layer": "launch queue",
                  "moves": "write_MBps"} for name in _PER_LAUNCH}
METRICS["lq_padded_byte_share"] = {
    "unit": "share", "better": "lower", "source": "program_counter",
    "layer": "launch queue", "moves": "write_MBps"}
_BYTES = {"h2d_bytes_per_launch": "ec_h2d_bytes",
          "d2h_bytes_per_launch": "ec_d2h_bytes"}
METRICS.update({name: {"unit": "bytes", "better": "lower",
                       "source": "program_counter",
                       "layer": "launch queue", "moves": "write_MBps"}
                for name in _BYTES})


def read(ctx: dict) -> dict:
    out = {}
    q0, q1 = ctx["before"]["launch_queue"], ctx["after"]["launch_queue"]
    wall = span_delta(ctx, "_wall")
    launches = q1["launches"] - q0["launches"] if q0 and q1 else 0
    for name, span in _PER_LAUNCH.items():
        if launches > 0 and span in wall:
            out[name] = 1e3 * wall[span] / launches
    padded = counter_delta(ctx, "ec_host_queue",
                           "ec_host_launch_padded_bytes")
    if padded > 0:
        out["lq_padded_byte_share"] = 1.0 - counter_delta(
            ctx, "ec_host_queue", "ec_host_launch_bytes") / padded
    for name, counter in _BYTES.items():
        moved = counter_delta(ctx, "ec_host_queue", counter)
        if launches > 0 and moved > 0:
            out[name] = moved / launches
    return out
