"""Launch queue: the share of the bytes launches uploaded in the
window that was constants — crc matrices and run-layout maps, which
do not depend on the data.  The program counts a constant where it
uploads it (`ec_h2d_const_bytes`, a part of `ec_h2d_bytes`), so a
launch path that keeps its constants on the device reads ~0 here and
one that uploads them anew with every launch reads their whole share
(0.985 of a 4 KiB launch's upload before PR 29).  A program without
the counter (the parent of the PR that added it) gives nothing."""

from counter_presence import has_counter
from perf_dumps import counter_delta

METRICS = {
    "lq_const_upload_share": {
        "unit": "share", "better": "lower", "source": "program_counter",
        "layer": "launch queue", "moves": "write_MBps"},
}


def read(ctx: dict) -> dict:
    if not has_counter(ctx, "ec_host_queue", "ec_h2d_const_bytes"):
        return {}
    uploaded = counter_delta(ctx, "ec_host_queue", "ec_h2d_bytes")
    if uploaded <= 0:
        return {}
    return {"lq_const_upload_share": counter_delta(
        ctx, "ec_host_queue", "ec_h2d_const_bytes") / uploaded}
