"""gap_attribution.py against sums worked out by hand: small cases
first, then the stretch of a real chip trace recorded in
testdata/host_rows.json (host-plane span rows and the device rows of
the same second, one time base).

    python3 -m pytest benchmark/tests/test_gap_attribution.py -q
"""

import json
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import gap_attribution as ga  # noqa: E402
import trace_reduce  # noqa: E402


def row(line, name, start, dur):
    return {"line": line, "name": name, "start_ns": start, "dur_ns": dur}


MS = 1_000_000

CASES = {
    # one thread, one span over half of the one idle gap
    "half_covered": (
        [(0, 10 * MS), (30 * MS, 40 * MS)],
        [row("t1", "lq.launch", 10 * MS, 10 * MS)],
        (0, 40 * MS),
        {"lq.launch": 0.010, "no_span": 0.010}),
    # a child does not count: the idle is its parent's
    "nested_is_the_parents": (
        [(0, 10 * MS)],
        [row("t1", "msgr.dispatch.MOSDECSubOpWrite", 10 * MS, 20 * MS),
         row("t1", "osd.sub_write_apply", 12 * MS, 10 * MS),
         row("t1", "store.commit", 13 * MS, 5 * MS)],
        (0, 30 * MS),
        {"msgr.dispatch.MOSDECSubOpWrite": 0.020}),
    # two threads inside spans at once share the instant equally;
    # time under a span while the device is BUSY is nobody's idle
    "two_threads_share": (
        [(0, 4 * MS), (20 * MS, 24 * MS)],
        [row("t1", "ec.assemble", 2 * MS, 10 * MS),      # idle 4..12
         row("t2", "osd.op_prepare", 8 * MS, 8 * MS),    # idle 8..16
         row("t2", "osd.tick.heartbeat", 21 * MS, 2 * MS)],  # busy
        (0, 24 * MS),
        # 4..8 assemble alone (4); 8..12 both (2 + 2); 12..16 prepare
        # alone (4); 16..20 nobody (4)
        {"ec.assemble": 0.006, "osd.op_prepare": 0.006,
         "no_span": 0.004}),
    # the same name on two threads adds up; the window clips a span
    "same_name_twice_and_clipped": (
        [],
        [row("t1", "msgr.send", -5 * MS, 10 * MS),       # 0..5 inside
         row("t2", "msgr.send", 3 * MS, 4 * MS)],        # 3..7
        (0, 10 * MS),
        # 0..3 one (3); 3..5 two (1 + 1); 5..7 one (2); 7..10 nobody
        {"msgr.send": 0.007, "no_span": 0.003}),
    # siblings on one thread, back to back, and a device that never
    # stops: no idle at all
    "always_busy": (
        [(0, 50 * MS)],
        [row("t1", "lq.launch", 0, 5 * MS),
         row("t1", "lq.finalize", 5 * MS, 5 * MS)],
        (0, 50 * MS),
        {}),
}


@pytest.mark.parametrize("case", sorted(CASES))
def test_hand_worked(case):
    busy, spans, (lo, hi), want = CASES[case]
    got = ga.attribute_idle(busy, spans, lo, hi)
    assert set(got) == set(want)
    for name, seconds in want.items():
        assert got[name] == pytest.approx(seconds, abs=1e-12), name
    idle = (hi - lo - trace_reduce.union_ns(
        [(max(s, lo), min(e, hi)) for s, e in busy])) / 1e9
    assert sum(got.values()) == pytest.approx(idle, abs=1e-12)


def test_top_level_drops_children_only():
    rows = [row("t1", "a", 0, 10), row("t1", "b", 2, 3),
            row("t1", "c", 10, 5), row("t2", "b", 2, 3),
            row("t1", "d", 0, 10)]      # same interval as "a"
    top = sorted(ga.top_level(rows))
    assert (2, 5, "b") in top                   # t2's, a root there
    assert sum(1 for t in top if t[2] == "b") == 1
    assert (10, 15, "c") in top
    assert len([t for t in top if t[:2] == (0, 10)]) == 1


# -- the recorded chip trace ---------------------------------------------------

@pytest.fixture(scope="module")
def recorded():
    with open(os.path.join(BENCH, "testdata", "host_rows.json")) as f:
        return json.load(f)


def _busy(recorded):
    return [(r["start_ns"], r["start_ns"] + r["dur_ns"])
            for r in recorded["device_rows"] if r["line"] == "XLA Ops"]


def test_recording_has_both_planes_on_one_time_base(recorded):
    names = {r["name"] for r in recorded["host_rows"]}
    for want in ("lq.launch", "lq.finalize",
                 "msgr.dispatch.MOSDECSubOpWrite"):
        assert want in names
    fam = trace_reduce.load_families()
    fused = [r for r in recorded["device_rows"]
             if r["line"] == "XLA Modules"
             and trace_reduce.family_of(r["name"], fam) == "fused_encode"]
    assert fused
    lo, hi = recorded["window_ns"]
    assert all(lo <= r["start_ns"] < hi for r in fused)
    # every fused module is preceded by an lq.launch that began at
    # most 50 ms before it: the same clock, and the dispatch latency
    launches = sorted(r["start_ns"] for r in recorded["host_rows"]
                      if r["name"] == "lq.launch")
    lead = []
    for r in fused:
        before = [s for s in launches if s <= r["start_ns"]]
        if before:
            lead.append(r["start_ns"] - before[-1])
    assert lead and max(lead) < 50 * MS and min(lead) > 0


def test_recording_sums_to_the_idle_time(recorded):
    lo, hi = recorded["window_ns"]
    busy = _busy(recorded)
    got = ga.attribute_idle(busy, recorded["host_rows"], lo, hi)
    idle = (hi - lo - trace_reduce.union_ns(
        [(max(s, lo), min(e, hi)) for s, e in busy
         if e > lo and s < hi])) / 1e9
    assert sum(got.values()) == pytest.approx(idle, rel=1e-9)
    for name, seconds in recorded["expect"]["idle_s"].items():
        assert got[name] == pytest.approx(seconds, rel=1e-9), name
    assert set(got) == set(recorded["expect"]["idle_s"])


def test_recording_excerpt_by_hand(recorded):
    """One gap of the recording, small enough to work out on paper:
    expect.excerpt names a window and the shares computed by hand
    from the rows listed beside it."""
    ex = recorded["expect"]["excerpt"]
    lo, hi = ex["window_ns"]
    got = ga.attribute_idle(_busy(recorded), recorded["host_rows"],
                            lo, hi)
    assert set(got) == set(ex["idle_ns"])
    for name, ns in ex["idle_ns"].items():
        assert got[name] * 1e9 == pytest.approx(ns, abs=1.0), name
