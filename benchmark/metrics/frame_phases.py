"""Wire: a data frame's one-way trip as a sum.  One frame in 16 of
every session, picked by a rule of its seq that sender and receiver
evaluate alike, has the clock read at eight points on its way
(ceph_tpu/msg/msgr_ledger.py FRAME_PHASES, docs/TRACING.md "A frame's
trip"): `send_message` called on the sender's thread -> `_send`
entered on its reactor (`hop`) -> the session's send lock held
(`sendlock`) -> frame encoded and retained (`encode`) -> handed to the
transport (`write`) -> the header in the receiving reactor's hands
(`transit`: transport buffer, kernel, the peer reactor's wake-up and
ready queue) -> the body read (`body_read`) -> decoded (`decode`) ->
the handler's first line, inline on the reactor or on the dispatch
executor (`to_handler`).  The eight partition the trip, so their means
add up to the mean of "send called -> handler start" over the sampled
frames of ALL message kinds; the program keeps the same sums by kind
beside them (`frame_ns.<Type>.<phase>`), which no benchmark file
reads.  Each is the mean over the samples its own histogram took
between the snapshots, in the process's one `msgr_ledger` set.  A
program without the histograms (the parent of the PR that added
them) gives nothing."""

from perf_dumps import hist_delta

_PHASES = ("hop", "sendlock", "encode", "write", "transit",
           "body_read", "decode", "to_handler")

METRICS = {
    f"frame_{phase}_ms_mean": {
        "unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "wire", "moves": "write_p95_ms"}
    for phase in _PHASES}


def read(ctx: dict) -> dict:
    out = {}
    for phase in _PHASES:
        total, n = hist_delta(ctx, "msgr_ledger", f"lat_frame_{phase}",
                              first_osd_only=True)
        if n > 0:
            out[f"frame_{phase}_ms_mean"] = 1e3 * total / n
    return out
