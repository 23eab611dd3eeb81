"""The client op's timeline as a sum: the op tracker's phase
histograms (`lat_phase_<op_type>_<phase>`, ceph_tpu/common/
tracked_op.py PHASE_ANCHORS) partition every op's own timeline, so
queue_wait + prepare + encode + fanout_commit is `osd_op_ms_mean`
again, and wire_in is what lies before the primary's handler.  What
the client saw beyond wire_in and the primary's op is closed by
difference — and named as one — until a `benchmark` PR puts the
client's own `perf dump` into the snapshot."""

from perf_dumps import hist_delta

_MS = {"unit": "ms", "better": "lower", "source": "program_counter",
       "moves": "write_p95_ms"}
_PHASES = {
    "op_wire_in_ms_mean": ("wire", "lat_phase_osd_op_wire_in"),
    "op_queue_wait_ms_mean": ("OSD op path",
                              "lat_phase_osd_op_queue_wait"),
    "op_prepare_ms_mean": ("OSD op path", "lat_phase_osd_op_prepare"),
    "op_encode_ms_mean": ("EC backend", "lat_phase_osd_op_encode"),
    "op_fanout_commit_ms_mean": ("EC backend",
                                 "lat_phase_osd_op_fanout_commit"),
    "subwrite_apply_ms_mean": ("store", "lat_phase_ec_sub_write_apply"),
}

METRICS = {name: dict(_MS, layer=layer)
           for name, (layer, _) in _PHASES.items()}
METRICS["client_outside_osd_ms_mean"] = dict(_MS, layer="client")


def read(ctx: dict) -> dict:
    out = {}
    for name, (_, key) in _PHASES.items():
        total, n = hist_delta(ctx, "optracker.", key)
        if n > 0:
            out[name] = 1e3 * total / n
    total, n = hist_delta(ctx, "optracker.", "lat_total_osd_op")
    lo, hi = ctx["before"]["t"], ctx["after"]["t"]
    acked = [t1 - t0 for _, t0, t1, err in ctx["run"]["ops"]
             if err is None and lo <= t1 <= hi]
    if "op_wire_in_ms_mean" in out and n > 0 and acked:
        out["client_outside_osd_ms_mean"] = (
            1e3 * sum(acked) / len(acked)
            - out["op_wire_in_ms_mean"] - 1e3 * total / n)
    return out
