"""Thread-CPU per layer per client op, from the process's `host_spans`
perf set (ceph_tpu/common/spans.py): every span adds its SELF
thread-CPU seconds under its name, children subtracted, so the layers'
sums do not overlap.  `wire_cpu_ms_per_op` is the `msgr.` prefix: the
self-CPU of the executor-run message handlers (`msgr.dispatch.<Type>`,
what they do outside the `osd.` / `ec.` spans inside them) plus the
WHOLE CPU of the messenger's reactor threads, accounted by thread
(`msgr.reactor_cpu`).  The reactors run asyncio, the socket calls,
frame encode, decode and crc — and the handlers the OSD fast-dispatches
inline on them: MOSDOp's op-pool submit, MOSDECSubOpRead's shard store
read (the hinfo probe), the routing of read and sub-write replies,
pings.  So the figure is an upper bound on wire work: it holds that
much OSD and store work too, and only a profiler trace (rows
`msgr.send`, `msgr.decode`, `msgr.dispatch.<Type>` on the reactor
threads) parts them.  Executor continuations (`ec.on_commit`,
`ec.on_read_done`) count with the EC backend.  One process holds
client, mon and all OSDs, so "per op" is the whole host's CPU in that
layer per acknowledged write.  `host_cpu_accounted_share` is how much
of the process's CPU all of these explain — by spans and by thread
accounting together."""

from perf_dumps import client_ops_between
from span_dumps import process_cpu_delta, span_delta

_CPU = {"unit": "ms", "better": "lower", "source": "program_counter",
        "moves": "write_MBps"}
# layer -> the span-name prefixes that belong to it
_LAYERS = {
    "wire_cpu_ms_per_op": ("wire", ("msgr.",)),
    "osd_cpu_ms_per_op": ("OSD op path", ("osd.",)),
    "ec_cpu_ms_per_op": ("EC backend", ("ec.", "lq.")),
    "store_cpu_ms_per_op": ("store", ("store.",)),
}

METRICS = {name: dict(_CPU, layer=layer)
           for name, (layer, _) in _LAYERS.items()}
METRICS["host_cpu_accounted_share"] = {
    "unit": "share", "better": "higher", "source": "program_counter",
    "layer": "client", "moves": "write_MBps"}


def read(ctx: dict) -> dict:
    cpu = span_delta(ctx, "_cpu")
    if not cpu:
        return {}
    out = {}
    ops = client_ops_between(ctx)
    if ops > 0:
        for name, (_, prefixes) in _LAYERS.items():
            out[name] = 1e3 * sum(
                v for k, v in cpu.items() if k.startswith(prefixes)) / ops
    process = process_cpu_delta(ctx)
    if process > 0:
        out["host_cpu_accounted_share"] = sum(cpu.values()) / process
    return out
