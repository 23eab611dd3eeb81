"""Wire: how many calls hand bytes to a socket for each data frame
written, and how many of them are acknowledgements travelling as
frames of their own.  The program counts both where they happen, in
the process's one wire ledger (`msgr_ledger`: `msgr_frames_out`,
`msgr_socket_writes`, `msgr_acks_out`; every messenger of the process,
the client's and the mon's too).  A frame written part by part and
acked by a frame of its own reads 3–4 writes and one ack a frame; a
frame that leaves in one call with the ack its session owes ahead of
it reads ~1 and ~0 (the sessions that stay one-way pay by timer).  A
program without the counters (the parent of the PR that added them)
gives nothing."""

from counter_presence import has_counter
from perf_dumps import counter_delta

METRICS = {
    "wire_writes_per_frame": {
        "unit": "count", "better": "lower", "source": "program_counter",
        "layer": "wire", "moves": "write_MBps"},
    "wire_acks_per_frame": {
        "unit": "count", "better": "lower", "source": "program_counter",
        "layer": "wire", "moves": "write_MBps"},
}


def read(ctx: dict) -> dict:
    if not has_counter(ctx, "msgr_ledger", "msgr_frames_out"):
        return {}
    frames = counter_delta(ctx, "msgr_ledger", "msgr_frames_out")
    if frames <= 0:
        return {}
    return {
        "wire_writes_per_frame": counter_delta(
            ctx, "msgr_ledger", "msgr_socket_writes") / frames,
        "wire_acks_per_frame": counter_delta(
            ctx, "msgr_ledger", "msgr_acks_out") / frames,
    }
