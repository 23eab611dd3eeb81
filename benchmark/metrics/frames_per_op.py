"""Wire: data frames written per client op.  Every messenger of the
process counts the data frames it hands to a transport in the one
`msgr_ledger` set (`msgr_frames_out`: client, mon and OSDs; replays
count again), and since the PR that added this reader also by message
kind (`msgr_frames_out_by_type.<Type>`, their sum; `CTRL_ACK` and
`CTRL_HELLO` beside them, not in it) — the count behind "frames an op
by message kind", worked out by hand until then: a client write is
`MOSDOp` + reply 2 and sub-writes + acks 2(k+m-1), an overwrite's
sub-reads and replies on top.  What the quotient reads above that is
background: heartbeats, `MPGStats`, mon traffic.  A client op is a
write acknowledged between the snapshots (in the S3 cell: a PUT).  A
program without `msgr_frames_out` gives nothing."""

from counter_presence import has_counter
from perf_dumps import client_ops_between, counter_delta

METRICS = {
    "wire_frames_per_op": {
        "unit": "count", "better": "lower", "source": "program_counter",
        "layer": "wire", "moves": "write_MBps"},
}


def read(ctx: dict) -> dict:
    if not has_counter(ctx, "msgr_ledger", "msgr_frames_out"):
        return {}
    ops = client_ops_between(ctx)
    frames = counter_delta(ctx, "msgr_ledger", "msgr_frames_out")
    if ops <= 0 or frames <= 0:
        return {}
    return {"wire_frames_per_op": frames / ops}
