"""The S3 gateway: how many times a PUT rewrites an account object
(`user.<owner>`, one object for all of a user's PUTs, three replicas,
class calls on it one at a time).  The program counts it where it
happens (`rgw_put_account_writes` in the gateway's set `rgw`,
ceph_tpu/rgw/store.py; docs/TRACING.md "The S3 gateway"): of the `cls
user` calls made for plain object PUTs answered 200, those that staged
a write — a quota gate that came back with a reservation token, every
stats call, every release sent.  Read from the gateway's own dumps,
where `rgw.py` reads them (`gateway_perf` in the run record).  A cell
without a gateway, or a program without the counter (the parent of the
PR that added it), reports nothing here."""

METRICS = {
    "rgw_account_writes_per_put": {
        "unit": "count", "better": "lower", "source": "program_counter",
        "layer": "gateway", "moves": "write_MBps"},
}

_KEY = "rgw_put_account_writes"


def read(ctx: dict) -> dict:
    dumps = ctx["run"].get("gateway_perf") or {}
    before = dumps.get("before", {}).get("rgw")
    after = dumps.get("after", {}).get("rgw")
    if not before or not after or _KEY not in after:
        return {}
    puts = after.get("rgw_put", 0) - before.get("rgw_put", 0)
    if puts <= 0:
        return {}
    return {"rgw_account_writes_per_put":
            (after[_KEY] - before.get(_KEY, 0)) / puts}
