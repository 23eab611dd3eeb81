"""Wire: handler time of all messages of all daemons per client op,
and the mean wait of a message for its dispatch thread (the process's
one wire ledger)."""

from perf_dumps import client_ops_between, hist_delta

METRICS = {
    "wire_dispatch_ms_per_op": {
        "unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "wire", "moves": "write_MBps"},
    "wire_qwait_ms_mean": {
        "unit": "ms", "better": "lower", "source": "program_counter",
        "layer": "wire", "moves": "write_p95_ms"},
}


def read(ctx: dict) -> dict:
    out = {}
    ops = client_ops_between(ctx)
    total, n = hist_delta(ctx, "msgr_ledger", "lat_msgr_dispatch",
                          first_osd_only=True)
    if ops > 0 and n > 0:
        out["wire_dispatch_ms_per_op"] = 1e3 * total / ops
    total, n = hist_delta(ctx, "msgr_ledger", "lat_msgr_qwait",
                          first_osd_only=True)
    if n > 0:
        out["wire_qwait_ms_mean"] = 1e3 * total / n
    return out
