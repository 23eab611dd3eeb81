"""Kernels, the plain (no-crc) parity launch of an overwrite (traced
runs only): device time of its XLA modules in the traced slice against
the least work of the bytes the launch queue sent in that slice
(benchmark/roofline_plain.py: k shards in, m out).

`kernel_families.json` has no family for these modules (they fall to
`other`), so they are summed here from the slice's per-module rows by
the jit's name, `gf_bitmatmul` (ceph_tpu/ops/bitsliced.py:
`jit_gf_bitmatmul_pallas_w32` on the chip).  The work is right where
every launch of the slice is a plain one — a cell whose window makes
fused launches too must not list these metrics."""

import re

from deploy import ec_geometry
from roofline import roofline_seconds
from roofline_plain import plain_encode_work

_KERNEL = {"better": "higher", "source": "device_trace",
           "layer": "kernels", "moves": "write_MBps"}
METRICS = {"kernel_plain_roofline": dict(_KERNEL, unit="%"),
           "kernel_plain_GBps": dict(_KERNEL, unit="GB/s")}
PLAIN_MODULE = re.compile(r"gf_bitmatmul")


def plain_seconds(device_ops: list) -> float:
    """Seconds of the plain parity modules among the slice's
    [family:module, seconds] rows."""
    return sum(s for key, s in device_ops
               if PLAIN_MODULE.search(key.split(":", 1)[-1]))


def read(ctx: dict) -> dict:
    trace = ctx["trace"]
    if not trace or not trace["planes"] or ctx["rehearsal"]:
        return {}
    seconds = plain_seconds(trace["device_ops"])
    if seconds <= 0 or not trace["launch_queue_bytes"]:
        return {}
    # the launch queue's input bytes between the profiler's start and
    # stop are k shards of the launched widths, unpadded
    k, m, _ = ec_geometry(ctx["config"])
    work = plain_encode_work(k, m, trace["launch_queue_bytes"] / k)
    least_s, _ = roofline_seconds(work, ctx["device"]["kind"])
    return {"kernel_plain_roofline": 100.0 * least_s / seconds,
            "kernel_plain_GBps": work["bytes_in"] / seconds / 1e9}
