"""EC backend: the share of write drains that rode the fused
encode+crc kernel (the rest fell back to the XLA formulation)."""

from perf_dumps import counter_delta

METRICS = {
    "ec_fused_drain_share": {
        "unit": "share", "better": "higher", "source": "program_counter",
        "layer": "EC backend", "moves": "write_MBps"},
}


def read(ctx: dict) -> dict:
    drains = counter_delta(ctx, "ec.", "ec_drain_submits")
    if drains <= 0:
        return {}
    return {"ec_fused_drain_share":
            counter_delta(ctx, "ec.", "ec_fused_kernel_drains") / drains}
