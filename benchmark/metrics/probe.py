"""EC backend: the share of object-metadata probes that went to the
wire.  A probe asks "does this object exist, and what are its hinfo
and size"; the primary's own shard answers it without a message when
it holds the object, or when the PG is clean for its interval and a
local miss is a miss everywhere; every other probe costs k+m-1
`MOSDECSubOpRead` round trips through the reactors."""

from perf_dumps import counter_delta

METRICS = {
    "ec_probe_remote_share": {
        "unit": "share", "better": "lower", "source": "program_counter",
        "layer": "EC backend", "moves": "write_MBps"},
}


def read(ctx: dict) -> dict:
    sweeps = counter_delta(ctx, "ec.", "ec_probe_sweeps")
    if sweeps <= 0:
        return {}
    return {"ec_probe_remote_share":
            counter_delta(ctx, "ec.", "ec_probe_remote_sweeps") / sweeps}
