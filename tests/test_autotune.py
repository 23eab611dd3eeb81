"""Operating-point tests: the served path reads its fused-kernel point
from the committed file and never sweeps; the sweep (a tool) never
ships a variant that fails bit-exactness, reports WHY a candidate
failed, and treats a failing default point as fatal."""

import json

import numpy as np
import pytest

from ceph_tpu.ec import gf
from ceph_tpu.ops import autotune, device
from ceph_tpu.ops import bitsliced as bs
from ceph_tpu.ops import crc32c_linear as cl

K, M = 4, 2
V5E = {"platform": "tpu", "kind": "TPU v5 lite", "count": 1,
       "kernels": "pallas-mosaic"}


@pytest.fixture(autouse=True)
def _fresh_points_cache():
    """The points file is read once per process; tests that swap it
    must not leak their copy into (or inherit one from) another."""
    autotune._load_points.cache_clear()
    yield
    autotune._load_points.cache_clear()


def _mats():
    import jax.numpy as jnp
    mat = gf.cauchy_rs_matrix(K, M)[K:]
    return mat, jnp.asarray(bs._w32_bitmat(mat), dtype=jnp.int8)


def _as_v5e(monkeypatch):
    """Make the lookup believe it runs on the chip (nothing is
    launched: the point is only read)."""
    monkeypatch.setattr(device, "describe", lambda: V5E)


def test_committed_point_is_read_without_a_sweep(monkeypatch):
    """The committed file carries the chip's k=8,m=3 point; the lookup
    returns it with its source and runs no validation/measurement."""
    _as_v5e(monkeypatch)
    monkeypatch.setattr(autotune, "validate", pytest.fail)
    monkeypatch.setattr(autotune, "measure", pytest.fail)
    committed = json.loads(autotune.POINTS_FILE.read_text())
    ent = committed["TPU v5 lite"]["k8m3"]
    point = autotune.fused_operating_point(8, 3)
    assert {kk: point[kk] for kk in ("tile", "wb", "combine")} == \
        {kk: ent[kk] for kk in ("tile", "wb", "combine")}
    assert point["source"] == "fused_points.json[TPU v5 lite][k8m3]"
    # every committed point must be a legal candidate of its geometry
    for geo, e in committed["TPU v5 lite"].items():
        k, m = (int(v) for v in geo[1:].split("m"))
        assert {kk: e[kk] for kk in ("tile", "wb", "combine")} in \
            autotune.candidates(k, m), geo


def test_missing_entry_and_cpu_fall_back_visibly(monkeypatch):
    """No entry for this (device kind, geometry) -> the default point,
    and the source says so; the CPU twin always gets the default."""
    cpu = autotune.fused_operating_point(8, 3)
    assert cpu["source"] == "default (cpu)"
    assert {kk: cpu[kk] for kk in ("tile", "wb", "combine")} == \
        autotune.default_point()
    _as_v5e(monkeypatch)
    odd = autotune.fused_operating_point(5, 2)
    assert odd["source"].startswith("default (no 'TPU v5 lite' k5m2")
    assert odd["tile"] == bs.FUSED_TILE_HIER


def test_validate_reports_miscompiling_candidate(monkeypatch):
    """A deliberately-miscompiling crc extraction (returns a
    wrong-but-well-shaped L matrix, the signature of a bad Mosaic
    lowering) is reported with the reason; a raising one is reported
    with the exception text, not swallowed."""
    mat, bitmat32 = _mats()
    # fresh (tile, wb) per case so no earlier good compile is cached
    # for these static args (the jit cache would mask the corruption)
    good = {"tile": 1024, "wb": 64, "combine": "xla"}
    assert autotune.validate(mat, bitmat32, good, interpret=True) is None

    def _zeros(words, cmat_sub, wb):
        import jax.numpy as jnp
        r, wt = words.shape
        return jnp.zeros((r * (wt // wb), 32), dtype=jnp.int32)

    monkeypatch.setattr(cl, "subblock_crc_bits_w32", _zeros)
    bad = {"tile": 2048, "wb": 64, "combine": "xla"}
    err = autotune.validate(mat, bitmat32, bad, interpret=True)
    assert err is not None and "crc of shard" in err

    def _boom(words, cmat_sub, wb):
        raise ValueError("Shape mismatch in input, indices and output")

    monkeypatch.setattr(cl, "subblock_crc_bits_w32", _boom)
    worse = {"tile": 2048, "wb": 128, "combine": "xla"}
    err = autotune.validate(mat, bitmat32, worse, interpret=True)
    assert "Shape mismatch" in err and "ValueError" in err


def test_sweep_rejects_invalid_and_default_failure_is_fatal(
        monkeypatch, tmp_path):
    """The sweep flow with a corrupted variant that MEASURES fastest:
    rejected at validation with its error in the report, never the
    winner; the winner lands in the points file under this device
    kind; and when the DEFAULT point is the one that fails, the sweep
    raises instead of recording anything."""
    _as_v5e(monkeypatch)
    points = tmp_path / "fused_points.json"
    points.write_text("{}")
    monkeypatch.setattr(autotune, "POINTS_FILE", points)
    dflt = autotune.default_point()
    monkeypatch.setattr(
        autotune, "validate",
        lambda mat, bm, cand, interpret=False:
            "compile/launch failed: boom"
            if cand["combine"] == "kernel" else None)
    monkeypatch.setattr(
        autotune, "measure",
        lambda bm, k, m, cand:
            50e9 if cand["combine"] == "kernel"
            else 5e9 + cand["tile"])
    mat, bitmat32 = _mats()
    report = list(autotune.sweep(K, M, mat, bitmat32))
    entry = autotune.winner(report)
    assert entry["combine"] == "xla" and entry["gbps"] > 0
    bad_rows = [(r, e) for c, r, e, _ in report
                if c["combine"] == "kernel"]
    assert bad_rows and all(r is None and "boom" in e
                            for r, e in bad_rows)
    autotune.write_point(K, M, entry)
    assert json.loads(points.read_text())["TPU v5 lite"]["k4m2"] == entry
    got = autotune.fused_operating_point(K, M)
    assert got["tile"] == entry["tile"]
    assert got["source"] == "fused_points.json[TPU v5 lite][k4m2]"
    # the default point itself failing is fatal
    monkeypatch.setattr(
        autotune, "validate",
        lambda mat, bm, cand, interpret=False:
            "compile/launch failed: vmem" if cand == dflt else None)
    with pytest.raises(RuntimeError, match="default fused point"):
        list(autotune.sweep(K, M, mat, bitmat32))


def test_winner_breaks_rate_ties_by_compile_cost():
    """Within RATE_TIE of the fastest, the cheapest compile wins and
    the entry keeps both rates; outside it, the fastest wins however
    long it took to compile."""
    slow_big = {"tile": 131072, "wb": 512, "combine": "kernel"}
    quick_small = {"tile": 32768, "wb": 512, "combine": "kernel"}
    failed = {"tile": 65536, "wb": 512, "combine": "xla"}
    report = [(slow_big, 40.76e9, None, 127.0),
              (quick_small, 39.60e9, None, 15.0),
              (failed, None, "compile/launch failed: vmem", 180.0)]
    e = autotune.winner(report)
    assert {kk: e[kk] for kk in ("tile", "wb", "combine")} == quick_small
    assert (e["gbps"], e["best_gbps"], e["compile_wall_s"]) == \
        (39.6, 40.76, 15.0)
    report[1] = (quick_small, 30e9, None, 15.0)     # a real gap now
    e = autotune.winner(report)
    assert e["tile"] == 131072 and e["gbps"] == e["best_gbps"] == 40.76
    with pytest.raises(RuntimeError, match="no candidate"):
        autotune.winner([(failed, None, "boom", 1.0)])


def test_sweep_refuses_cpu():
    mat, bitmat32 = _mats()
    with pytest.raises(SystemExit, match="no accelerator"):
        list(autotune.sweep(K, M, mat, bitmat32))


def test_candidates_ordering_and_legality():
    """candidates(): every point satisfies the sublane rule and the
    static default leads."""
    cands = autotune.candidates(8, 3)
    for c in cands:
        s = (c["tile"] // 4) // c["wb"]
        assert (11 * s) % 8 == 0
    assert cands[0] == autotune.default_point()
    assert len(cands) == len({tuple(c.items()) for c in cands})


def test_unknown_device_has_no_peaks(monkeypatch):
    """The peaks table is keyed by device_kind; an unknown device is an
    error, not a default."""
    _as_v5e(monkeypatch)
    assert device.peaks()["hbm_bytes_per_s"] == 819e9
    monkeypatch.setattr(device, "describe",
                        lambda: {**V5E, "kind": "TPU v9 mega"})
    with pytest.raises(RuntimeError, match="no published peaks"):
        device.peaks()
