"""Single-daemon process entrypoint (reference src/ceph_mon.cc /
src/ceph_osd.cc main(): one daemon per OS process).

Spawned by ProcCluster (proc_cluster.py) — the multi-process topology
in which kill -9 is a real SIGKILL, concurrency is real parallelism
(no shared GIL), and serialization bugs can't hide behind shared
memory.  Also usable standalone:

    python -m ceph_tpu.tools.daemon_main mon --rank 0 \
        --addrs 127.0.0.1:7001,127.0.0.1:7002,127.0.0.1:7003
    python -m ceph_tpu.tools.daemon_main osd --id 0 \
        --mon 127.0.0.1:7001 --objectstore filestore --data-dir /tmp/o0

Prints one "READY <addr>" line on stdout once serving, then runs until
SIGTERM/SIGKILL.
"""

from __future__ import annotations

import argparse
import signal
import sys
import time


def _force_cpu() -> None:
    """One process per chip: a chip belongs to the single process that
    first touches it, and a second one fails or hangs at start-up.  A
    daemon_main child is one of MANY processes on its host
    (ProcCluster, deploy), so every child pins JAX to the CPU platform
    before any backend initialises — its EC pools run the CPU plugins
    or the jax plugin's XLA twin.  The process that holds the chip is
    the in-process topology (tools/vstart.Cluster, chip_smoke.py)."""
    try:
        import jax
    except ImportError:     # numpy-only deployment: CPU plugins anyway
        return
    jax.config.update("jax_platforms", "cpu")


def _parse_addr(s: str) -> tuple[str, int]:
    host, _, port = s.rpartition(":")
    return (host, int(port))


def run_mon(args) -> int:
    from ..mon import Monitor
    addrs = [_parse_addr(a) for a in args.addrs.split(",")]
    mon = Monitor(addr=addrs[args.rank],
                  failure_quorum=args.failure_quorum,
                  data_dir=args.data_dir)
    if len(addrs) > 1:
        mon.join(addrs, args.rank)
    print(f"READY {mon.addr[0]}:{mon.addr[1]}", flush=True)
    _serve_forever(mon.shutdown)
    return 0


def _prep_mesh_env(conf: dict) -> None:
    """CPU meshes need their virtual devices BEFORE the jax backend
    initializes: when this daemon is mesh-enabled and XLA_FLAGS does
    not already force a host device count, derive one from the
    mesh_devices conf (shape product, count, or the 8-device default).
    A no-op for daemons without mesh mode or with the flag pre-set."""
    import os
    val = str(conf.get("osd_ec_use_mesh", "")).lower()
    if val not in ("true", "1", "yes", "on"):
        return
    flags = os.environ.get("XLA_FLAGS", "")
    if "xla_force_host_platform_device_count" in flags:
        return
    # same parser the MeshService will apply (parallel/service.py is
    # jax-free at module level, so importing it here cannot trip the
    # backend init this function exists to pre-empt)
    from ..parallel.service import MeshError, parse_mesh_shape
    try:
        n_shard, n_data = parse_mesh_shape(
            str(conf.get("mesh_devices", "")), 8)
        n = n_shard * n_data
    except MeshError:
        n = 8      # the service will surface the bad spec itself
    os.environ["XLA_FLAGS"] = (
        f"{flags} --xla_force_host_platform_device_count={n}").strip()


def run_osd(args) -> int:
    conf = {}
    for kv in args.conf or []:
        k, _, v = kv.partition("=")
        conf[k] = v
    _prep_mesh_env(conf)   # before create_store/daemon import any jax
    from ..osd.daemon import OSDDaemon
    from ..store import create_store
    store = create_store(args.objectstore, args.data_dir)
    mons = [_parse_addr(a) for a in args.mon.split(",")]
    # conf rides the constructor: startup options (osd_op_queue) pick
    # construction-time shape and must precede anything reading them
    osd = OSDDaemon(args.id, mons, store=store,
                    heartbeat_interval=args.heartbeat, conf=conf)
    osd.boot()
    print(f"READY {osd.addr[0]}:{osd.addr[1]}", flush=True)
    _serve_forever(osd.shutdown)
    return 0


def run_mds(args) -> int:
    from ..fs.mds import MDSDaemon
    mons = [_parse_addr(a) for a in args.mon.split(",")]
    mds = MDSDaemon(mons, name=args.name)
    print(f"READY {mds.addr[0]}:{mds.addr[1]}", flush=True)
    _serve_forever(mds.shutdown)
    return 0


def run_rgw(args) -> int:
    from ..rados import RadosClient
    from ..rgw import S3Gateway
    mons = [_parse_addr(a) for a in args.mon.split(",")]
    client = RadosClient(mons).connect()
    gw = S3Gateway(client, addr=("127.0.0.1", args.port))
    print(f"READY {gw.addr[0]}:{gw.addr[1]}", flush=True)
    _serve_forever(gw.shutdown)
    return 0


def _serve_forever(on_term) -> None:
    stop = []

    def _term(_sig, _frm):
        stop.append(1)
    signal.signal(signal.SIGTERM, _term)
    signal.signal(signal.SIGINT, _term)
    while not stop:
        time.sleep(0.2)
    try:
        on_term()
    except Exception:  # noqa: BLE001 - dying anyway
        pass


def main(argv=None) -> int:
    _force_cpu()
    ap = argparse.ArgumentParser(prog="daemon_main")
    sub = ap.add_subparsers(dest="role", required=True)

    mp = sub.add_parser("mon")
    mp.add_argument("--rank", type=int, default=0)
    mp.add_argument("--addrs", required=True,
                    help="comma list of host:port for ALL mon ranks")
    mp.add_argument("--failure-quorum", type=int, default=2)
    mp.add_argument("--data-dir", default=None)

    op = sub.add_parser("osd")
    op.add_argument("--id", type=int, required=True)
    op.add_argument("--mon", required=True,
                    help="comma list of mon host:port")
    op.add_argument("--objectstore", default="memstore")
    op.add_argument("--data-dir", default=None)
    op.add_argument("--heartbeat", type=float, default=1.0)
    op.add_argument("--conf", action="append", default=[],
                    help="k=v config overrides (repeatable)")

    dp = sub.add_parser("mds")
    dp.add_argument("--mon", required=True)
    dp.add_argument("--name", default="a")

    gp = sub.add_parser("rgw")
    gp.add_argument("--mon", required=True)
    gp.add_argument("--port", type=int, default=0)

    args = ap.parse_args(argv)
    return {"mon": run_mon, "osd": run_osd,
            "mds": run_mds, "rgw": run_rgw}[args.role](args)


if __name__ == "__main__":
    sys.exit(main())
