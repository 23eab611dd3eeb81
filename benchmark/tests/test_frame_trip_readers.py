"""The readers of PR 36's counters (benchmark/metrics/frame_phases.py,
reactor_loops.py, frames_per_op.py, object_lock.py) on hand-built
`perf dump`s: the numbers are the hand-worked ones, and on the dumps
of a program without the counters (the parent commit) every metric is
absent — not 0, and no exception.

    python3 -m pytest benchmark/tests/test_frame_trip_readers.py -q
"""

import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
BENCH = os.path.dirname(HERE)
sys.path.insert(0, BENCH)

import run  # noqa: E402

PHASES = run.load_module("metrics", "frame_phases")
LOOPS = run.load_module("metrics", "reactor_loops")
FRAMES = run.load_module("metrics", "frames_per_op")
LOCK = run.load_module("metrics", "object_lock")

NAMES = ("hop", "sendlock", "encode", "write", "transit", "body_read",
         "decode", "to_handler")


def ctx(before, after, ops=(), osd_sets=({}, {})):
    """Two snapshots 100 s apart.  The ledger's set is one object for
    the whole process: ONE OSD's dump carries it; `osd_sets` are the
    OSDs' own sets (before, after), the same on every OSD."""
    def snap(t, ledger, own):
        return {"t": t, "launch_queue": None, "compile": {},
                "osd_perf": [dict(own, msgr_ledger=ledger), dict(own)]}
    return {"before": snap(100.0, before, osd_sets[0]),
            "after": snap(200.0, after, osd_sets[1]),
            "run": {"ops": list(ops)}}


def hist(total_s, n):
    return {"sum": total_s, "count": n, "buckets": []}


PARENT = {"msgr_dispatches": 7, "msgr_frames_out": 900,
          "lat_msgr_qwait": hist(1.0, 3)}


# -- frame_phases -------------------------------------------------------------

def test_each_phase_is_its_own_histograms_mean_in_ms():
    before = {f"lat_frame_{p}": hist(1.0, 10) for p in NAMES}
    after = {f"lat_frame_{p}": hist(1.0 + 0.002 * (i + 1) * 40, 50)
             for i, p in enumerate(NAMES)}
    got = PHASES.read(ctx(before, after))
    assert got == {f"frame_{p}_ms_mean": pytest.approx(2.0 * (i + 1))
                   for i, p in enumerate(NAMES)}
    assert set(got) == set(PHASES.METRICS)


def test_a_phase_without_a_sample_is_absent_and_the_others_stay():
    before = {f"lat_frame_{p}": hist(0.0, 0) for p in NAMES}
    after = dict(before, lat_frame_hop=hist(0.5, 100))
    assert PHASES.read(ctx(before, after)) == {
        "frame_hop_ms_mean": pytest.approx(5.0)}


# -- reactor_loops ------------------------------------------------------------

def loops(rows, frames):
    out = {"msgr_frames_out": frames}
    for i, (wall, select, cpu, sleeps) in enumerate(rows):
        out.update({f"reactor_wall_s.{i}": wall,
                    f"reactor_select_s.{i}": select,
                    f"reactor_cpu_s.{i}": cpu,
                    f"reactor_sleeps.{i}": sleeps,
                    f"reactor_iterations.{i}": 2 * sleeps})
    return out


def test_busy_imbalance_stall_and_wakeups_by_hand():
    before = loops([(10.0, 9.0, 0.5, 100)] * 4, 1_000)
    # 50 s later: running 30 / 20 / 10 / 20 s of 50, on a CPU 24 /
    # 15 / 9 / 16 s of that; 60,000 sleeps for 40,000 frames
    after = loops([(60.0, 29.0, 24.5, 20_100),
                   (60.0, 39.0, 15.5, 15_100),
                   (60.0, 49.0, 9.5, 10_100),
                   (60.0, 39.0, 16.5, 15_100)], 41_000)
    got = LOOPS.read(ctx(before, after))
    assert got == {
        "reactor_busy_share_max": pytest.approx(0.6),
        "reactor_load_imbalance": pytest.approx(0.6 / 0.4),
        "reactor_stall_share": pytest.approx((80 - 64) / 80),
        "wire_wakeups_per_frame": pytest.approx(60_000 / 40_000),
    }
    assert set(got) == set(LOOPS.METRICS)


def test_cpu_ticks_past_the_running_time_floor_the_stall_at_zero():
    # /proc counts in 10 ms ticks: a reactor that ran 1.000 s can
    # read 1.01 s of CPU
    got = LOOPS.read(ctx(loops([(0.0, 0.0, 0.0, 0)], 0),
                         loops([(10.0, 9.0, 1.01, 50)], 100)))
    assert got["reactor_stall_share"] == 0.0
    assert got["reactor_load_imbalance"] == pytest.approx(1.0)


# -- frames_per_op ------------------------------------------------------------

def test_frames_per_op_counts_acked_ops_between_the_snapshots():
    ops = [(0, 90.0, 99.0, None),       # acked before the window
           (1, 101.0, 110.0, None), (2, 120.0, 150.0, None),
           (3, 130.0, 160.0, "EIO"),    # failed: no op
           (4, 150.0, 199.0, None),
           (5, 190.0, 201.0, None)]     # acked after it
    got = FRAMES.read(ctx({"msgr_frames_out": 1_000},
                          {"msgr_frames_out": 1_066}, ops))
    assert got == {"wire_frames_per_op": pytest.approx(22.0)}


# -- object_lock --------------------------------------------------------------

def test_lock_wait_and_hold_are_means_over_all_osds():
    def own(wait, hold):
        return {"optracker.osd.0": {"lat_obj_lock_wait": hist(*wait),
                                    "lat_obj_lock_hold": hist(*hold)}}
    # two OSDs with the same set each: 2 x (3.0 s over 60 waits),
    # 2 x (0.9 s over 60 holds)
    got = LOCK.read(ctx(PARENT, PARENT, osd_sets=(
        own((1.0, 40), (0.1, 40)), own((4.0, 100), (1.0, 100)))))
    assert got == {"obj_lock_wait_ms_mean": pytest.approx(50.0),
                   "obj_lock_hold_ms_mean": pytest.approx(15.0)}
    assert set(got) == set(LOCK.METRICS)


# -- a program without the counters ---------------------------------------------

@pytest.mark.parametrize("reader", [PHASES, LOOPS, LOCK],
                         ids=["frame_phases", "reactor_loops",
                              "object_lock"])
def test_the_parent_commit_gives_nothing(reader):
    tracker = {"optracker.osd.0": {"lat_total_osd_op": hist(2.0, 9)}}
    assert reader.read(ctx(PARENT, dict(PARENT, msgr_frames_out=1_900),
                           [(1, 101.0, 110.0, None)],
                           osd_sets=(tracker, tracker))) == {}


def test_frames_per_op_needs_the_frame_counter_and_an_op():
    one_op = [(1, 101.0, 110.0, None)]
    no_counter = {"msgr_dispatches": 7}
    assert FRAMES.read(ctx(no_counter, no_counter, one_op)) == {}
    assert FRAMES.read(ctx(PARENT, PARENT, one_op)) == {}       # no frame
    assert FRAMES.read(ctx(PARENT, dict(PARENT, msgr_frames_out=950),
                           [])) == {}                           # no op
