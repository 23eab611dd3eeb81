"""Test configuration.

Tests run on the CPU platform — the kernels as their XLA twins or
through the Pallas interpreter — on a virtual 8-device CPU mesh, so
the multi-chip sharding logic is exercised without an accelerator.
JAX_PLATFORMS and XLA_FLAGS must be in the environment before jax
initialises a backend, so they are set here, ahead of the import.

The persistent compile cache is placed the way every caller places it
(ops/compile_cache.py): through JAX_COMPILATION_CACHE_DIR.  A run that
does not bring its own gets a throwaway directory, so tier-1 never
writes into the checkout's .jax_cache/ and child processes
(ProcCluster daemons) inherit the same one.
"""

import atexit
import os
import shutil
import sys
import tempfile

os.environ["JAX_PLATFORMS"] = "cpu"
flags = os.environ.get("XLA_FLAGS", "")
if "xla_force_host_platform_device_count" not in flags:
    os.environ["XLA_FLAGS"] = (
        flags + " --xla_force_host_platform_device_count=8").strip()
if "JAX_COMPILATION_CACHE_DIR" not in os.environ:
    _cache_dir = tempfile.mkdtemp(prefix="ceph_tpu_xla_")
    os.environ["JAX_COMPILATION_CACHE_DIR"] = _cache_dir
    atexit.register(shutil.rmtree, _cache_dir, ignore_errors=True)

import jax  # noqa: E402,F401

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(__file__))))

import pytest  # noqa: E402


@pytest.fixture()
def mesh_service():
    """The per-host MeshService on the virtual 8-device CPU mesh (the
    XLA_FLAGS force above ran in this process before jax initialized —
    the same trick `bench.py --multichip` / daemon_main use in their
    own subprocesses).  Reset afterwards so each test configures its
    own shape; production never resets a live service."""
    from ceph_tpu.parallel.service import MeshService
    MeshService.reset()
    try:
        yield MeshService.configure("4x2")
    finally:
        MeshService.reset()
