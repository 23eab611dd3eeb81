"""ProcCluster: the multi-process dev cluster.

Same surface as vstart.Cluster but every daemon is its own OS process
(reference qa/standalone/ceph-helpers.sh run_mon/run_osd: real daemons,
one host).  What this buys over the thread topology:

  * kill -9 is a REAL SIGKILL — no destructor, no flushed buffer, no
    shared-memory state surviving by accident; revive replays whatever
    the store made durable, exactly like a crashed host
  * concurrency is real parallelism (each daemon owns a Python
    interpreter — no shared GIL), so cluster throughput numbers measure
    the system, not one interpreter's scheduler
  * serialization is load-bearing: every byte between daemons crosses
    a socket; nothing can lean on sharing objects in memory

Library use:
    with ProcCluster(n_osds=4, objectstore="filestore") as c:
        client = c.client()
        ...
        c.kill_osd(2)          # SIGKILL the process
        c.revive_osd(2)        # respawn on the surviving store
"""

from __future__ import annotations

import socket
import subprocess
import sys
import tempfile
import time
from pathlib import Path

from ..rados import RadosClient


def wait_ready(proc: subprocess.Popen, what: str,
               timeout: float = 120.0) -> str:
    """Wait for daemon_main's one-line READY handshake on a raw-fd
    pipe (buffered wrappers can strand the line — see _wait_ready's
    original note).  Scans for READY BEFORE checking liveness so a
    daemon that prints READY and exits still reports its address.
    Shared by ProcCluster and the cephadm-role deployer."""
    import os
    import select
    fd = proc.stdout.fileno()
    buf = b""
    deadline = time.time() + timeout
    while time.time() < deadline:
        *complete, _partial = buf.split(b"\n")
        for line in complete:
            if line.startswith(b"READY"):
                return line.split()[1].decode()
        if proc.poll() is not None:
            raise RuntimeError(f"{what} died at boot "
                               f"(rc={proc.returncode})")
        r, _, _ = select.select([fd], [], [], 0.2)
        if r:
            chunk = os.read(fd, 4096)
            if chunk:
                buf += chunk
    raise RuntimeError(f"{what} not ready in {timeout}s")


def _free_ports(n: int) -> list[int]:
    """Reserve n distinct loopback ports (bind-then-release; the race
    window on a dev box is acceptable for test clusters — the reference
    helpers pick fixed port ranges the same way)."""
    socks, ports = [], []
    for _ in range(n):
        s = socket.socket()
        s.bind(("127.0.0.1", 0))
        socks.append(s)
        ports.append(s.getsockname()[1])
    for s in socks:
        s.close()
    return ports


class ProcCluster:
    def __init__(self, n_osds: int = 4, n_mons: int = 1,
                 objectstore: str = "filestore",
                 data_dir: str | None = None,
                 heartbeat_interval: float = 1.0,
                 failure_quorum: int = 2,
                 conf: dict | None = None,
                 boot_timeout: float = 120.0,
                 mesh_devices: str | None = None,
                 prewarm: bool = False):
        # compile lifecycle (docs/PIPELINE.md): in the process
        # topology EVERY OSD process prewarms its own interpreter's
        # jit caches, so the shared persistent compile cache does the
        # cross-process heavy lifting (first booter compiles to disk,
        # the rest read; the children inherit
        # JAX_COMPILATION_CACHE_DIR with the rest of the environment)
        if prewarm:
            conf = dict(conf or {})
            conf.setdefault("osd_ec_prewarm", True)
        self.n_osds = n_osds
        self.n_mons = n_mons
        self.objectstore = objectstore
        # multichip mode in the process topology: each OSD process
        # stands in for a host and owns its OWN mesh (a jax mesh
        # cannot span OS processes here); daemon_main pre-sets
        # XLA_FLAGS from this conf before jax initializes so CPU
        # meshes get their virtual devices — docs/MULTICHIP.md
        self.mesh_devices = mesh_devices
        if mesh_devices is not None:
            conf = dict(conf or {})
            conf.setdefault("osd_ec_use_mesh", True)
            conf.setdefault("mesh_devices", mesh_devices)
        self.data_dir = Path(data_dir or tempfile.mkdtemp(
            prefix="ceph_tpu_proc_"))
        self.heartbeat_interval = heartbeat_interval
        self.failure_quorum = failure_quorum
        self.conf = dict(conf or {})
        # per-OSD conf overrides carried across revive (chaos knobs
        # must survive restarts): merged over self.conf at every
        # (re)spawn of that daemon
        self.osd_conf: dict[int, dict] = {}
        self.boot_timeout = boot_timeout
        self.mon_ports = _free_ports(n_mons)
        self.mon_addrs = [("127.0.0.1", p) for p in self.mon_ports]
        self.mon_procs: list[subprocess.Popen] = []
        self.osd_procs: list[subprocess.Popen | None] = []
        self.extra_procs: list[subprocess.Popen] = []
        self._clients: list[RadosClient] = []

    # -- spawning -----------------------------------------------------------

    def _spawn(self, argv: list[str]) -> subprocess.Popen:
        return subprocess.Popen(
            [sys.executable, "-m", "ceph_tpu.tools.daemon_main", *argv],
            stdout=subprocess.PIPE, stderr=subprocess.DEVNULL,
            text=True)

    def _wait_ready(self, proc: subprocess.Popen, what: str) -> str:
        return wait_ready(proc, what, self.boot_timeout)

    def start(self) -> "ProcCluster":
        try:
            return self._start()
        except Exception:
            self.stop()        # never leak orphan daemon processes
            raise

    def _start(self) -> "ProcCluster":
        addrs = ",".join(f"{h}:{p}" for h, p in self.mon_addrs)
        for rank in range(self.n_mons):
            p = self._spawn([
                "mon", "--rank", str(rank), "--addrs", addrs,
                "--failure-quorum", str(self.failure_quorum),
                "--data-dir", str(self.data_dir / f"mon.{rank}")])
            self.mon_procs.append(p)
        for rank, p in enumerate(self.mon_procs):
            self._wait_ready(p, f"mon.{rank}")
        for i in range(self.n_osds):
            self.osd_procs.append(self._spawn_osd(i))
        for i, p in enumerate(self.osd_procs):
            self._wait_ready(p, f"osd.{i}")
        # wait until the map shows every OSD up
        admin = self.admin()
        deadline = time.time() + self.boot_timeout
        while time.time() < deadline:
            admin.objecter.refresh_map(timeout=2.0)
            osds = admin.objecter.osdmap.osds
            if len(osds) == self.n_osds and \
                    all(o.up for o in osds.values()):
                return self
            time.sleep(0.2)
        raise RuntimeError("OSDs never all came up")

    def _spawn_osd(self, osd_id: int) -> subprocess.Popen:
        argv = ["osd", "--id", str(osd_id),
                "--mon", ",".join(f"{h}:{p}" for h, p in self.mon_addrs),
                "--objectstore", self.objectstore,
                "--data-dir", str(self.data_dir / f"osd.{osd_id}"),
                "--heartbeat", str(self.heartbeat_interval)]
        merged = {**self.conf, **self.osd_conf.get(osd_id, {})}
        for k, v in merged.items():
            argv += ["--conf", f"{k}={v}"]
        return self._spawn(argv)

    def set_osd_conf(self, osd_id: int, key: str, value) -> None:
        """Record a per-OSD conf override applied at every (re)spawn —
        the process analog of Cluster.set_osd_conf.  A running daemon
        picks it up on its next revive (live injection would need the
        asok injectargs path; spawn-time conf is what the thrasher
        needs to survive kill/revive)."""
        self.osd_conf.setdefault(osd_id, {})[key] = value

    def spawn_rgw(self) -> tuple[str, int]:
        p = self._spawn([
            "rgw", "--mon",
            ",".join(f"{h}:{p}" for h, p in self.mon_addrs)])
        self.extra_procs.append(p)
        addr = self._wait_ready(p, "rgw")
        host, _, port = addr.rpartition(":")
        return host, int(port)

    def spawn_mds(self, name: str = "a") -> tuple[str, int]:
        p = self._spawn([
            "mds", "--name", name, "--mon",
            ",".join(f"{h}:{p}" for h, p in self.mon_addrs)])
        self.extra_procs.append(p)
        addr = self._wait_ready(p, f"mds.{name}")
        host, _, port = addr.rpartition(":")
        return host, int(port)

    # -- cluster surface (vstart.Cluster-compatible subset) -----------------

    def client(self) -> RadosClient:
        c = RadosClient(self.mon_addrs).connect()
        self._clients.append(c)
        return c

    def admin(self) -> RadosClient:
        if not self._clients:
            return self.client()
        return self._clients[0]

    def kill_osd(self, osd_id: int) -> None:
        """SIGKILL — the real thing (reference ceph_manager kill_osd)."""
        p = self.osd_procs[osd_id]
        if p is not None:
            p.kill()
            p.wait()
            self.osd_procs[osd_id] = None

    def revive_osd(self, osd_id: int) -> None:
        assert self.osd_procs[osd_id] is None, "still running"
        p = self._spawn_osd(osd_id)
        self.osd_procs[osd_id] = p
        self._wait_ready(p, f"osd.{osd_id}")

    def mark_osd_down(self, osd_id: int) -> None:
        r, _ = self.admin().mon_command(
            {"prefix": "osd down", "id": osd_id})
        assert r == 0, f"osd down failed: {r}"

    def stop(self) -> None:
        for c in self._clients:
            try:
                c.shutdown()
            except Exception:  # noqa: BLE001
                pass
        for p in self.extra_procs + \
                [p for p in self.osd_procs if p is not None] + \
                self.mon_procs:
            p.terminate()
        for p in self.extra_procs + \
                [p for p in self.osd_procs if p is not None] + \
                self.mon_procs:
            try:
                p.wait(timeout=10)
            except subprocess.TimeoutExpired:
                p.kill()
                p.wait()

    def __enter__(self) -> "ProcCluster":
        return self.start()

    def __exit__(self, *exc) -> None:
        self.stop()
