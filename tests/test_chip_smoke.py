"""chip_smoke.py on the CPU: it refuses to run without a chip, and its
phases are green at a tiny size when the platform check is passed in
as an argument (no environment switch in the script)."""

import json
import os
import subprocess
import sys

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, ROOT)

import chip_smoke  # noqa: E402

TINY = chip_smoke.Size(osds=12, pg_num=8, objects=10,
                       object_bytes=128 << 10, writers=4,
                       degraded_reads=6, degraded_writes=3,
                       degraded_overwrites=5, s3_object_bytes=16 << 10,
                       stripe_bytes=64 << 10, clean_timeout_s=120)


def test_refuses_to_run_without_a_chip():
    """`python chip_smoke.py` under JAX_PLATFORMS=cpu: non-zero exit,
    the no-chip line on stderr, and NO result line on stdout."""
    out = subprocess.run(
        [sys.executable, os.path.join(ROOT, "chip_smoke.py")],
        env={**os.environ, "JAX_PLATFORMS": "cpu"}, cwd=ROOT,
        capture_output=True, text=True, timeout=120)
    assert out.returncode != 0
    assert "JAX found no tpu device" in out.stderr
    assert out.stdout.strip() == ""


def test_phases_green_at_tiny_size_on_the_cpu_twin():
    """Every phase — cross-check, prewarm, boot, write, read back,
    degraded reads + writes + overwrites, recovery, deep scrub, S3
    ingest — at a tiny size,
    served by the XLA twin and saying so; the same counters the chip
    run gates on are zero here too."""
    rep = chip_smoke.run(TINY, seed=7, require_platform="cpu")
    assert rep["ok"] and rep["claim"] is None
    assert list(rep)[-1] == "claim"
    assert rep["device"]["platform"] == "cpu"
    assert rep["kernels"] == "xla-twin"
    assert set(rep["fused_paths"]) == {"xla"}
    assert rep["ops_acked"] == TINY.objects + TINY.degraded_writes
    assert rep["bytes_acked"] == rep["ops_acked"] * TINY.object_bytes
    c = rep["counters"]
    assert c["ec_reconstruct_reads"] > 0
    # the degraded read-modify-write: each 4 KiB overwrite of the
    # object that lost a data shard reconstructs its stripe first
    assert c["ec_rmw_reconstructs"] == TINY.degraded_overwrites
    assert rep["phases"]["degraded"]["degraded_overwrites"] == 5
    assert c["ec_host_decode_launches"] > 0
    assert c["ec_repair_reconstructed_bytes"] > 0
    for key in ("ec_drain_errors", "ec_mesh_errors",
                "ec_host_launch_retries", "ec_host_launch_errors"):
        assert c[key] == 0
    assert rep["scrub"]["errors"] == 0
    assert rep["scrub"]["objects"] == rep["ops_acked"]
    # the S3 step: an 11-shard bucket from the configuration, 8 signed
    # PUTs on EC k4m2 read back and listed, six RADOS ops a PUT
    s3 = rep["phases"]["s3"]
    assert (s3["ops"], s3["bytes"], s3["index_shards"],
            s3["rados_ops_per_put"]) == (
        8, 8 * TINY.s3_object_bytes, 11, 6.0)
    assert {p["window"] for p in rep["phases"].values()} == \
        {"setup", "serving"}
    assert rep["fused_point"]["source"] == "default (cpu)"


def test_wrong_platform_argument_fails_before_any_phase():
    with pytest.raises(chip_smoke.SmokeFailure,
                       match="JAX found no tpu device"):
        chip_smoke.run(TINY, require_platform="tpu")


def test_last_stdout_line_is_exactly_ok_and_device(
        monkeypatch, capsys, tmp_path):
    """The result line carries exactly `ok` and `device` (platform,
    kind, count); the run's facts are the line before it and the file."""
    device = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1}
    monkeypatch.setattr(chip_smoke, "run", lambda **kw: {
        "ok": True, "device": dict(device), "phases": {},
        "compile_ledger": {"buckets": []}, "claim": None})
    monkeypatch.setattr(chip_smoke, "OUT_DIR", str(tmp_path))
    monkeypatch.setattr(chip_smoke.faulthandler,
                        "dump_traceback_later", lambda *a, **k: None)
    assert chip_smoke.main([]) == 0
    facts, result = capsys.readouterr().out.splitlines()
    assert json.loads(result) == {"ok": True, "device": device}
    assert list(json.loads(facts))[-1] == "claim"
    assert "compile_ledger" in json.loads(
        (tmp_path / "chip_smoke.json").read_text())


@pytest.mark.parametrize("exc", [chip_smoke.SmokeFailure("byte 7 differs"),
                                 RuntimeError("phase blew up")])
def test_any_failing_phase_is_a_nonzero_exit_and_no_result(
        monkeypatch, capsys, exc):
    """A failed check or a raising phase: exit 1, one FAILED line on
    stderr, nothing on stdout — never a null field and exit 0."""
    def boom(**kw):
        raise exc
    monkeypatch.setattr(chip_smoke, "run", boom)
    monkeypatch.setattr(chip_smoke.faulthandler,
                        "dump_traceback_later", lambda *a, **k: None)
    assert chip_smoke.main([]) == 1
    cap = capsys.readouterr()
    assert cap.out == ""
    assert "chip_smoke: FAILED" in cap.err and str(exc.args[0]) in cap.err
