"""OSD op path: the per-object lock of the primary.  All ops on one
object serialize on a striped lock (ceph_tpu/osd/daemon.py
`_handle_client_op`; a replicated write or a `cls` call keeps it
through its replicas' commit).  Every tracked client op that takes it
adds one sample to `lat_obj_lock_wait` (arrival at the lock ->
acquired) and one to `lat_obj_lock_hold` (acquired -> released) in its
OSD's `optracker.osd.N` set (docs/TRACING.md "Phases"); the means here
are over all OSDs.  Both lie inside the op's `fanout_commit` / `prepare`
phases and partition nothing.  A program without the histograms (the
parent of the PR that added them) gives nothing."""

from perf_dumps import hist_delta

_MS = {"unit": "ms", "better": "lower", "source": "program_counter",
       "layer": "OSD op path", "moves": "write_p95_ms"}
METRICS = {"obj_lock_wait_ms_mean": dict(_MS),
           "obj_lock_hold_ms_mean": dict(_MS)}


def read(ctx: dict) -> dict:
    out = {}
    for name in METRICS:
        total, n = hist_delta(ctx, "optracker.",
                              "lat_" + name[:-len("_ms_mean")])
        if n > 0:
            out[name] = 1e3 * total / n
    return out
