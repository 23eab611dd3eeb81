"""Traffic generator `s3_closed_loop_put`: the COSBench write-stage
role.  N workers, each on its own keep-alive HTTP/1.1 connection to
the deployment's S3 gateway, PUT a fresh object, wait for the reply
and PUT the next.  Every request is SigV4-signed with a signed payload
hash, by this file's own code (stdlib `hmac` / `hashlib`; nothing of
`ceph_tpu.rgw.sigv4`).  The parameters come from a traffic file, the
gateway and its buckets from the configuration; nothing here knows a
cell's name.

What the deployment needs beyond its one pool is made here, before the
window and inside set-up, as an operator does before `radosgw` starts
and a COSBench `init` stage after: the replicated index pool, the
gateway (on a RADOS client of its own, carrying the cluster's
configuration), the buckets — through S3 `CreateBucket` — and
`warmup_ops` throw-away PUTs that are acknowledged PUTs like the rest.

The window is `closed_loop_write`'s (see there): workers staggered
over `stagger_s`, `ramp_s` of uncounted traffic, opened and closed on
the clock, counters read outside it, nothing else in the process.

Placement does not depend on the seed: PUT number N, counting up from
0 across all workers, goes to bucket `<bucket_prefix>{N mod buckets +
1}` under the key `<object_prefix>{N}` (COSBench's `mycontainers` /
`myobjects`, 1-based containers).  The seed makes the DATA, as
`closed_loop_write.make_payloads` does: payload N mod pool with N in
its first 8 bytes.

A PUT is timed from the first byte of the request handed to the
socket to the last byte of the reply read; building and signing it
lie before that and are reported beside (`client_sign_ms_mean`).
"""

from __future__ import annotations

import datetime
import gc
import hashlib
import hmac
import http.client
import itertools
import threading
import time
import urllib.parse
import xml.etree.ElementTree as ET

import numpy as np

from generators.closed_loop_write import _sleep_until, object_data
from generators.closed_loop_write import end_to_end as _window_numbers
from generators.closed_loop_write import launch_shapes as _append_shapes
from generators.closed_loop_write import make_payloads as _payload_pool

END_TO_END = ("write_MBps", "write_p95_ms")


def _require_gateway_conf() -> None:
    """A program whose gateway cannot be told its index shard count
    by the deployment's configuration, or has no counters to read,
    cannot run this deployment: say so before anything is booted (the
    harness calls `launch_shapes` first), not after a minute of
    set-up."""
    from ceph_tpu.rgw.gateway import S3Gateway
    from ceph_tpu.rgw.store import RGWStore
    if not hasattr(S3Gateway, "perf_dump") or \
            not hasattr(RGWStore, "begin_request"):
        raise SystemExit(
            "benchmark: this program's S3Gateway reads no rgw_* option "
            "from its client's configuration and has no `rgw` perf "
            "set: it cannot create 11-shard bucket indexes from "
            "CreateBucket nor say where a PUT's time goes. Refusing "
            "to run.")


def launch_shapes(traffic: dict, config: dict) -> list[tuple]:
    """n concurrent PUTs are n whole-object appends on the data pool:
    `closed_loop_write`'s shapes at this object size."""
    _require_gateway_conf()
    return _append_shapes(traffic, config)


def make_payloads(traffic: dict, seed: int) -> dict:
    return {"seed": seed, "pool": _payload_pool(traffic, seed)}


def bucket_names(config: dict) -> list[str]:
    gw = config["gateway"]
    return [f"{gw['bucket_prefix']}{i + 1}" for i in range(gw["buckets"])]


def placement(traffic: dict, config: dict, n: int) -> tuple[str, str]:
    gw = config["gateway"]
    return (f"{gw['bucket_prefix']}{n % gw['buckets'] + 1}",
            f"{traffic['object_prefix']}{n}")


# -- the S3 client: one connection, SigV4 by hand ----------------------------

_SAFE = "-_.~"


class S3Connection:
    """One keep-alive HTTP/1.1 connection to the gateway and the
    signing of what goes over it (AWS Signature Version 4, header
    form, payload hash signed)."""

    def __init__(self, addr: tuple[str, int], gateway: dict):
        self.addr = addr
        self.host = f"{addr[0]}:{addr[1]}"
        self.access, self.secret = gateway["access_key"], \
            gateway["secret_key"]
        self.region = gateway["region"]
        self.conn = http.client.HTTPConnection(*addr, timeout=120)

    def _signing_key(self, datestamp: str) -> bytes:
        key = f"AWS4{self.secret}".encode()
        for part in (datestamp, self.region, "s3", "aws4_request"):
            key = hmac.new(key, part.encode(), hashlib.sha256).digest()
        return key

    def sign(self, method: str, path: str, query: str,
             body: bytes) -> dict:
        """The request's headers, Authorization among them."""
        now = datetime.datetime.now(datetime.timezone.utc)
        amzdate = now.strftime("%Y%m%dT%H%M%SZ")
        datestamp = amzdate[:8]
        payload_hash = hashlib.sha256(body).hexdigest()
        headers = {"host": self.host,
                   "x-amz-content-sha256": payload_hash,
                   "x-amz-date": amzdate}
        signed = ";".join(sorted(headers))
        canon_query = "&".join(
            f"{urllib.parse.quote(k, safe=_SAFE)}="
            f"{urllib.parse.quote(v, safe=_SAFE)}"
            for k, v in sorted(urllib.parse.parse_qsl(
                query, keep_blank_values=True)))
        canon = "\n".join([
            method, urllib.parse.quote(path, safe="/" + _SAFE),
            canon_query,
            "".join(f"{k}:{headers[k]}\n" for k in sorted(headers)),
            signed, payload_hash])
        scope = f"{datestamp}/{self.region}/s3/aws4_request"
        to_sign = "\n".join([
            "AWS4-HMAC-SHA256", amzdate, scope,
            hashlib.sha256(canon.encode()).hexdigest()])
        sig = hmac.new(self._signing_key(datestamp), to_sign.encode(),
                       hashlib.sha256).hexdigest()
        headers["Authorization"] = (
            f"AWS4-HMAC-SHA256 Credential={self.access}/{scope}, "
            f"SignedHeaders={signed}, Signature={sig}")
        return headers

    def send(self, method: str, path: str, query: str, headers: dict,
             body: bytes) -> tuple[int, dict, bytes]:
        """(status, reply headers, reply body).  A transport error
        leaves the connection closed; the next request opens anew."""
        url = urllib.parse.quote(path, safe="/" + _SAFE) \
            + (f"?{query}" if query else "")
        try:
            self.conn.request(method, url, body=body, headers=headers)
            reply = self.conn.getresponse()
            data = reply.read()
        except (OSError, http.client.HTTPException):
            self.conn.close()
            raise
        return reply.status, dict(reply.getheaders()), data

    def request(self, method: str, path: str, query: str = "",
                body: bytes = b"") -> tuple[int, dict, bytes]:
        return self.send(method, path, query,
                         self.sign(method, path, query, body), body)

    def close(self) -> None:
        self.conn.close()


def list_bucket(conn: S3Connection, bucket: str, page: int
                ) -> list[tuple[str, int, str]]:
    """The whole bucket through ListObjectsV2, page by page on the
    continuation tokens: (key, size, etag) in the order returned."""
    rows, token = [], None
    for _ in range(1_000_000):
        query = f"list-type=2&max-keys={page}"
        if token is not None:
            query += "&continuation-token=" + urllib.parse.quote(
                token, safe="")
        status, _, body = conn.request("GET", f"/{bucket}", query)
        if status != 200:
            raise RuntimeError(f"ListObjectsV2 {bucket}: HTTP {status}")
        root = ET.fromstring(body)
        for item in root.iter("Contents"):
            rows.append((item.findtext("Key"),
                         int(item.findtext("Size")),
                         item.findtext("ETag").strip('"')))
        if root.findtext("IsTruncated") != "true":
            return rows
        token = root.findtext("NextContinuationToken")
        if not token:
            raise RuntimeError(f"ListObjectsV2 {bucket}: a truncated "
                               f"page without a continuation token")
    raise RuntimeError(f"ListObjectsV2 {bucket}: no end of pages")


# -- set-up inside drive -----------------------------------------------------

def _start_gateway(dep, state: dict) -> dict:
    """The index pool, the gateway on a client of its own, and the
    buckets through S3 CreateBucket."""
    from ceph_tpu.rgw.gateway import S3Gateway
    spec = dep.config["gateway"]
    meta = spec["meta_pool"]
    times = {}
    t0 = time.perf_counter()
    dep.client.create_pool(meta["name"], meta["type"],
                           size=meta["size"], pg_num=meta["pg_num"])
    dep.cluster.wait_active_clean(timeout=300.0)
    times["index_pool_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    gateway = state["gateway"] = S3Gateway(
        dep.cluster.client(), (spec["host"], 0),
        creds={spec["access_key"]: spec["secret_key"]})
    state["addr"] = tuple(gateway.addr)
    times["gateway_s"] = time.perf_counter() - t0
    t0 = time.perf_counter()
    todo = iter(bucket_names(dep.config))
    errors = []

    def creator() -> None:
        conn = S3Connection(state["addr"], spec)
        try:
            for bucket in todo:
                status, _, body = conn.request("PUT", f"/{bucket}")
                if status != 200:
                    errors.append(f"{bucket}: HTTP {status} {body[:200]}")
        except Exception as e:  # noqa: BLE001 — set-up must not go on
            errors.append(repr(e))              # with buckets missing
        finally:
            conn.close()

    threads = [threading.Thread(target=creator, name=f"bench-init-{i}")
               for i in range(8)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    if errors:
        raise RuntimeError(f"CreateBucket failed: {errors[:3]}")
    times["buckets_s"] = time.perf_counter() - t0
    return times


# -- the run -----------------------------------------------------------------

def drive(dep, traffic: dict, state: dict, seconds: float,
          before_window=None, in_window=None) -> dict:
    """Set-up of the gateway and its buckets, then warm-up, ramp,
    window and drain as `closed_loop_write.drive`.  Returns the per-op
    records (n, t_start, t_ack, error or None) — warm-up, ramp and
    tail included —, the window's clock and the gateway's `perf dump`
    beside each of the harness's two snapshots."""
    setup = _start_gateway(dep, state)
    gateway, spec = state["gateway"], dep.config["gateway"]
    writers = traffic["writers"]
    conns = [S3Connection(state["addr"], spec) for _ in range(writers)]
    numbers = itertools.count()
    records = [[] for _ in range(writers)]
    sign_s = [0.0] * writers
    etags = state["put_etags"] = {}

    def one_put(w: int) -> None:
        n = next(numbers)
        bucket, key = placement(traffic, dep.config, n)
        body = object_data(state["pool"], n)
        conn = conns[w]
        t_sign = time.perf_counter()
        headers = conn.sign("PUT", f"/{bucket}/{key}", "", body)
        t0 = time.perf_counter()
        sign_s[w] += t0 - t_sign
        try:
            status, reply, _ = conn.send("PUT", f"/{bucket}/{key}", "",
                                         headers, body)
            err = None if status == 200 else f"HTTP{status}"
            etags[n] = reply.get("ETag")
        except (OSError, http.client.HTTPException) as e:
            err = type(e).__name__              # counted, by type
        records[w].append((n, t0, time.perf_counter(), err))

    # throw-away PUTs: the whole path once per worker connection and
    # handler thread before anything is timed
    t0 = time.perf_counter()
    warm_left = itertools.count()

    def warmer(w: int) -> None:
        while next(warm_left) < traffic["warmup_ops"]:
            one_put(w)

    threads = [threading.Thread(target=warmer, args=(w,),
                                name=f"bench-warm-{w}")
               for w in range(writers)]
    for t in threads:
        t.start()
    for t in threads:
        t.join()
    setup["warmup_s"] = time.perf_counter() - t0
    gc.collect()
    gc.freeze()

    t_start = time.perf_counter() + 0.05
    t_open = t_start + traffic["ramp_s"]
    t_close = t_open + seconds

    def writer(w: int) -> None:
        _sleep_until(t_start + traffic["stagger_s"] * w / writers)
        while time.perf_counter() < t_close:
            one_put(w)

    def gateway_perf() -> dict:
        return dict(gateway.perf_dump(), t=time.perf_counter())

    threads = [threading.Thread(target=writer, args=(w,),
                                name=f"bench-writer-{w}")
               for w in range(writers)]
    for t in threads:
        t.start()
    _sleep_until(t_open - traffic["counter_lead_s"])
    if before_window is not None:
        before_window()
    perf = {"before": gateway_perf()}
    _sleep_until(t_open)
    if in_window is not None:
        in_window(t_open, t_close)
    _sleep_until(t_close)
    for t in threads:
        t.join()
    perf["after"] = gateway_perf()
    for conn in conns:
        conn.close()
    ops = sorted(r for rows in records for r in rows)
    return {"ops": ops, "t_open": t_open, "t_close": t_close,
            "t_drained": time.perf_counter(),
            "ops_per_writer": [len(rows) for rows in records],
            "setup": setup, "gateway_perf": perf,
            "client_sign_s": sum(sign_s)}


def end_to_end(traffic: dict, run: dict) -> dict:
    out = _window_numbers(traffic, run)
    out["s3_setup"] = run["setup"]
    if run["ops"]:
        out["client_sign_ms_mean"] = \
            1e3 * run["client_sign_s"] / len(run["ops"])
    return out


# -- the comparison that decides `correct` -----------------------------------

def _walk_pool(dep, pool_name: str) -> dict:
    """{osd id: {object name: (cid, ghobject)}} of a pool's head
    objects as they lie in the stores, and their bytes together."""
    pool_id = dep.cluster.mon.osdmap.lookup_pool(pool_name).id
    found, stored = {}, 0
    for osd in dep.cluster.osds:
        for cid in osd.store.list_collections():
            if cid.pgid.pool != pool_id:
                continue
            for goid in osd.store.list_objects(cid):
                if goid.hobj.name.startswith("__"):
                    continue
                stored += osd.store.stat(cid, goid)
                found.setdefault(osd.osd_id, {})[goid.hobj.name] = (
                    cid, goid)
    return {"objects": found, "stored_bytes": stored, "pool_id": pool_id}


def _index_replicas(dep, state: dict) -> dict:
    """Every index shard object of every bucket on each replica of
    its PG: how many objects there should be, how many copies are
    missing, how many objects differ between their copies."""
    meta = dep.config["gateway"]["meta_pool"]
    walk = _walk_pool(dep, meta["name"])
    osdmap = dep.cluster.mon.osdmap
    index = state["gateway"].store.index
    shards = missing = differing = 0
    for bucket in bucket_names(dep.config):
        for oid in index.read_layout(bucket).oids("index"):
            shards += 1
            pgid = osdmap.object_to_pg(walk["pool_id"], oid)
            acting = osdmap.pg_to_up_acting_osds(pgid)[1]
            copies = []
            for osd_id in acting:
                hit = walk["objects"].get(osd_id, {}).get(oid)
                if hit is None:
                    missing += 1
                    continue
                copies.append(bytes(
                    dep.cluster.osds[osd_id].store.read(*hit)))
            if len(acting) != meta["size"]:
                missing += meta["size"] - len(acting)
            if len(set(copies)) > 1:
                differing += 1
    return {"shards": shards, "missing": missing, "differing": differing,
            "stored_bytes": walk["stored_bytes"]}


def verify(dep, traffic: dict, state: dict, run: dict, seed: int,
           reference) -> dict:
    """Every number against its limit (all 0, all exact): failed PUTs
    and PUTs answered with a wrong ETag; every acknowledged key read
    back through the gateway — bytes, ETag, Content-Length — against
    the model; every bucket's whole paginated listing against the
    model's; for a seed-drawn sample of the objects (first and last
    always in) all k+m shards of the RADOS data object as they lie in
    the stores against the reference's encoding, bytes and crcs; and
    every index shard object's bytes on all replicas of its PG."""
    try:
        return _verify(dep, traffic, state, run, seed, reference)
    finally:
        state["gateway"].shutdown()


def _verify(dep, traffic: dict, state: dict, run: dict, seed: int,
            reference) -> dict:
    from deploy import ec_geometry
    spec = dep.config["gateway"]
    size = traffic["object_bytes"]
    acked = [n for n, _, _, err in run["ops"] if err is None]
    failed = sum(1 for op in run["ops"] if op[3] is not None)
    model = reference.BucketModel(bucket_names(dep.config))
    for n in acked:
        model.put(*placement(traffic, dep.config, n),
                  object_data(state["pool"], n))
    etag_at_put = sum(
        1 for n in acked
        if (state["put_etags"].get(n) or "").strip('"')
        != model.expected_object(*placement(traffic, dep.config, n))[1])

    # -- read-back of every acknowledged key through the gateway
    unreadable, differing, etag_wrong, length_wrong = [], [], [], []

    def reader(part) -> None:
        conn = S3Connection(state["addr"], spec)
        for n in part:
            bucket, key = placement(traffic, dep.config, n)
            try:
                status, headers, body = conn.request(
                    "GET", f"/{bucket}/{key}")
            except (OSError, http.client.HTTPException):
                status = None
            if status != 200:
                unreadable.append(n)
                continue
            want_size, want_etag = model.expected_object(bucket, key)
            if body != object_data(state["pool"], n):
                differing.append(n)
            if headers.get("ETag") != f'"{want_etag}"':
                etag_wrong.append(n)
            if headers.get("Content-Length") != str(want_size):
                length_wrong.append(n)
        conn.close()

    readers = traffic["readback"]["readers"]
    threads = [threading.Thread(target=reader, args=(acked[r::readers],))
               for r in range(readers)]
    t0 = time.perf_counter()
    for t in threads:
        t.start()
    for t in threads:
        t.join()

    # -- every bucket's whole listing
    listing = {"missing": 0, "unexpected": 0, "doubled": 0, "wrong": 0,
               "misordered": 0}
    listed = 0
    conn = S3Connection(state["addr"], spec)
    for bucket in bucket_names(dep.config):
        got = list_bucket(conn, bucket, traffic["list_page"])
        listed += len(got)
        for what, count in reference.compare_listing(
                model.expected_listing(bucket), got).items():
            listing[what] += count
    conn.close()
    readback_s = time.perf_counter() - t0

    # -- what the device produced, as it lies in the stores
    t0 = time.perf_counter()
    k, m, su = ec_geometry(dep.config)
    index = dep.store_index()
    rng = np.random.default_rng([seed, 0xC0FFEE])
    limit = traffic["audit"]["max_objects"]
    if limit >= len(acked):
        to_audit = list(acked)
    else:
        keep = {acked[0], acked[-1]}
        keep.update(int(n) for n in rng.choice(
            acked, size=limit - 2, replace=False))
        to_audit = sorted(keep)
    missing = bytes_wrong = crcs_wrong = shards = 0
    for n in to_audit:
        body = object_data(state["pool"], n)
        name = reference.data_object_name(
            *placement(traffic, dep.config, n))
        want, want_crcs = reference.expected_shards(body, k, m, su)
        for shard, osd_id in enumerate(dep.acting(name)):
            shards += 1
            got = dep.read_shard(index, osd_id, shard, name)
            if got is None:
                missing += 1
                continue
            data, crcs, shard_size, logical = got
            if data.shape != want[shard].shape or \
                    not np.array_equal(data, want[shard]):
                bytes_wrong += 1
            if crcs != want_crcs or shard_size != want.shape[1] \
                    or logical != size:
                crcs_wrong += 1

    # -- the index, replica by replica
    replicas = _index_replicas(dep, state)
    want_shards = spec["buckets"] * dep.config["deployment"]["conf"][
        "rgw_bucket_index_shards"]
    compared = {
        "put_errors": [failed, 0],
        "put_etag_wrong": [etag_at_put, 0],
        "readback_unreadable": [len(unreadable), 0],
        "readback_differing": [len(differing), 0],
        "readback_etag_wrong": [len(etag_wrong), 0],
        "readback_length_wrong": [len(length_wrong), 0],
        "listing_keys_missing": [listing["missing"], 0],
        "listing_keys_unexpected": [listing["unexpected"], 0],
        "listing_keys_doubled": [listing["doubled"], 0],
        "listing_entries_wrong": [listing["wrong"], 0],
        "listing_buckets_misordered": [listing["misordered"], 0],
        "audit_shards_missing": [missing, 0],
        "audit_shard_bytes_wrong": [bytes_wrong, 0],
        "audit_shard_crcs_wrong": [crcs_wrong, 0],
        "index_shard_objects_absent": [
            abs(want_shards - replicas["shards"]), 0],
        "index_replicas_missing": [replicas["missing"], 0],
        "index_replicas_differing": [replicas["differing"], 0],
    }
    return {
        "compared": compared,
        "checked": {"acked": len(acked), "read_back": len(acked),
                    "listed": listed,
                    "audited_objects": len(to_audit),
                    "audited_shards": shards,
                    "index_shard_objects": replicas["shards"]},
        "correct": bool(acked) and shards > 0
        and all(v <= lim for v, lim in compared.values()),
        "attempted": len(run["ops"]), "failed": failed,
        "acked_bytes": len(acked) * size,
        # the data pool's shards AND everything the index pool holds,
        # replicas counted: what the PUTs cost in space
        "stored_bytes": index["stored_bytes"] + replicas["stored_bytes"],
        "readback_s": readback_s,
        "audit_s": time.perf_counter() - t0,
    }
