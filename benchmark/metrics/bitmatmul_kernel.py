"""Kernels, `gf_bitmatmul` in a window that launches it for two ends —
the plain parity of an overwrite and the decode of a degraded pre-read
are ONE XLA module (`jit_gf_bitmatmul_pallas_w32` on the chip) with
two matrices, so the trace cannot tell them apart and
`kernel_plain_roofline`'s work (every launch a plain one) would be
wrong here (traced runs only).  Device time of those modules in the
traced slice against the least work of the slice's launches counted
PER KIND.  The program's part is a plain fact — how many bytes of rows
its launches of each kind handed to the kernel
(`ec_host_launch_in_bytes.<kind>`, k rows a launch) —, the rule is
benchmark/roofline_bitmatmul.py's: a plain launch of that width needs
k rows in and m out, a decode k rows in and out only the rows the read
lacked, one here (the configuration's `failure` takes one OSD: a
stripe lacks at most one shard, and a pre-read that lacks a parity
shard decodes nothing).  What the kernel computes beyond that —
`ec_host_launch_out_bytes.decode` says it: today the unread parity
shard as well — is not counted as work.

Those counters are read at the two snapshots, not at the edges of the
slice: the window's work per kind is scaled by the share of the
window's launched bytes that fell into the slice
(`launch_queue_bytes` of the slice / the snapshots' difference of
`coalesced_bytes`) — right while the mix of kinds is steady through
the window, which a closed loop on a fixed set of degraded PGs is.  A
program without the per-kind counters gives nothing."""

from counter_presence import has_counter
from deploy import ec_geometry
from metrics.plain_kernel import plain_seconds
from perf_dumps import counter_delta
from roofline import roofline_seconds
from roofline_bitmatmul import bitmatmul_work

_KERNEL = {"better": "higher", "source": "device_trace",
           "layer": "kernels", "moves": "write_MBps"}
METRICS = {"kernel_bitmatmul_roofline": dict(_KERNEL, unit="%"),
           "kernel_bitmatmul_GBps": dict(_KERNEL, unit="GB/s")}
KINDS = ("plain_encode", "decode")
LOST_ROWS = 1       # data rows a reconstructing read lacks: one OSD down
_SET = "ec_host_queue"


def window_in_bytes(ctx: dict) -> tuple[int, int]:
    """Bytes of the rows the window's (plain, decode) launches handed
    to the kernel, from the per-kind counters."""
    return tuple(counter_delta(ctx, _SET,
                               f"ec_host_launch_in_bytes.{kind}")
                 for kind in KINDS)


def read(ctx: dict) -> dict:
    trace = ctx["trace"]
    if not trace or not trace["planes"] or ctx["rehearsal"]:
        return {}
    if not has_counter(ctx, _SET, "ec_host_launch_in_bytes.plain_encode"):
        return {}
    seconds = plain_seconds(trace["device_ops"])
    q0, q1 = ctx["before"]["launch_queue"], ctx["after"]["launch_queue"]
    launched = q1["coalesced_bytes"] - q0["coalesced_bytes"] \
        if q0 and q1 else 0
    if seconds <= 0 or launched <= 0 or not trace["launch_queue_bytes"]:
        return {}
    share = trace["launch_queue_bytes"] / launched
    k, m, _ = ec_geometry(ctx["config"])
    plain_in, decode_in = window_in_bytes(ctx)
    work = bitmatmul_work(k, m, LOST_ROWS, share * plain_in,
                          share * decode_in)
    least_s, _ = roofline_seconds(work, ctx["device"]["kind"])
    return {"kernel_bitmatmul_roofline": 100.0 * least_s / seconds,
            "kernel_bitmatmul_GBps": work["bytes_in"] / seconds / 1e9}
