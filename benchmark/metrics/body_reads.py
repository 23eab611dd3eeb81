"""Wire: how many reads it takes to get a large frame body off its
socket.  A body longer than 64 KiB (the messenger's JOIN_UP_TO) is
received in place, in a buffer of its own that the kernel copies into
directly; the program counts, in the process's one wire ledger
(`msgr_ledger`), the bodies received so (`msgr_large_bodies`) and the
reads that landed in them (`msgr_large_body_reads`: the read that
carried a body's first bytes behind its header included).  A 4 MiB
body read 256 KiB a pass, as a stream transport reads, costs 16 and
more; one that takes what the socket holds each pass, 1–3.  A program
without the counters (the parent of the PR that added them), or a
window in which no large body arrived (the 4 KiB cells), gives
nothing."""

from counter_presence import has_counter
from perf_dumps import counter_delta

METRICS = {
    "wire_reads_per_large_body": {
        "unit": "count", "better": "lower", "source": "program_counter",
        "layer": "wire", "moves": "write_MBps"},
}


def read(ctx: dict) -> dict:
    if not has_counter(ctx, "msgr_ledger", "msgr_large_bodies"):
        return {}
    bodies = counter_delta(ctx, "msgr_ledger", "msgr_large_bodies")
    if bodies <= 0:
        return {}
    return {"wire_reads_per_large_body": counter_delta(
        ctx, "msgr_ledger", "msgr_large_body_reads") / bodies}
