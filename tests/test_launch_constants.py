"""A fused launch is one upload, one program per kernel and one fetch
per result array (ops/bitsliced.py submit / finalize,
ops/const_cache.py).

What must hold on every path (`hier_acc`, `hier_lsub`, `w32_flat`,
`xla`, and the split big/small launch): the constants of a launch are
uploaded once and handed device-resident to every later launch — keyed
by what they depend on, so a new operating point or run layout misses
exactly once — a donated launch leaves them valid, the launch
dispatches no eager device operation between its upload and its
fetch, and finalize fetches at most the parity and ONE L array.
"""

import collections
import glob
import warnings

import jax
import numpy as np
import pytest

from ceph_tpu.common import crc32c as C
from ceph_tpu.ec import ErasureCodePluginRegistry, gf
from ceph_tpu.ops import bitsliced as bs
from ceph_tpu.ops import const_cache
from ceph_tpu.ops import crc32c_linear as cl
from ceph_tpu.osd.ec_backend import ECBackend, LocalShardBackend
from ceph_tpu.osd.ec_transaction import PGTransaction
from ceph_tpu.osd.ec_util import StripeInfo
from ceph_tpu.osd.types import eversion_t, hobject_t, pg_t
from ceph_tpu.parallel.launch_queue import ECLaunchQueue
from ceph_tpu.store import MemStore

K, M = 4, 2
TILE, WB = 4096, 128          # s = 8, (k+m)*s = 48: sublane-aligned
MAT = gf.cauchy_rs_matrix(K, M)[K:]

# path -> (submit arguments, run widths, the jitted programs a launch
# of it may dispatch, widths of another run layout).  Widths hold odd
# tails, an exact multiple and a run under one block; the split mixes
# hier-eligible and small runs.
_HIER = dict(use_w32=True, force_xla=False, interpret=True)
PATHS = {
    "hier_acc": (dict(_HIER, combine="kernel"),
                 [TILE * 2 + 513, TILE * 3, TILE + 1],
                 {"_hier_acc_core"}, [TILE, TILE * 4 + 7]),
    "hier_lsub": (dict(_HIER, combine="xla"),
                  [TILE * 2 + 513, TILE * 3, TILE + 1],
                  {"_hier_lsub_core", "_combine_run"},
                  [TILE, TILE * 4 + 7]),
    "w32_flat": (dict(_HIER, combine="xla"),
                 [2048 + 100, 600, 2048],
                 {"gf_encode_with_crc_pallas_w32", "_combine_run"},
                 [2048 * 2, 300]),
    "xla": (dict(use_w32=False, force_xla=True),
            [2048 * 2 + 100, 100, 2048 * 3],
            {"gf_encode_with_crc_xla", "_combine_run"},
            [2048, 2048 * 2 + 1]),
    "split": (dict(_HIER, combine="kernel"),
              [TILE * 2, 600, TILE + 513],
              {"_hier_acc_core", "gf_encode_with_crc_pallas_w32",
               "_combine_run"}, [TILE * 3, 100, TILE]),
}


@pytest.fixture(scope="module")
def bitmats():
    import jax.numpy as jnp
    return (jnp.asarray(bs.interleave_bitmatrix(MAT), dtype=jnp.int8),
            jnp.asarray(bs._w32_bitmat(MAT), dtype=jnp.int8))


def _runs(widths, seed):
    rng = np.random.default_rng(seed)
    return [rng.integers(0, 256, (K, w), dtype=np.uint8)
            for w in widths]


def _submit(bitmats, path, runs, tile=TILE, wb=WB, donate=False):
    with warnings.catch_warnings():
        # the CPU backend cannot use a donated buffer and says so
        warnings.simplefilter("ignore")
        return bs.gf_encode_extents_with_crc_submit(
            *bitmats, runs, M, tile=tile, wb=wb, donate=donate,
            **PATHS[path][0])


def _check(runs, results):
    """Every run's (parity, l, tail_bytes, body) against the reference
    fold: numpy GF parity and the byte-path crc32c of every shard."""
    assert len(results) == len(runs)
    for run, (par, l, tail, body) in zip(runs, results):
        np.testing.assert_array_equal(np.asarray(par),
                                      gf.gf_matvec(MAT, run))
        assert body + tail.shape[1] == run.shape[1]
        shards = np.concatenate([run, np.asarray(par)], axis=0)
        for s in range(K + M):
            got = cl.fold_run_crc(int(l[s]), body, 0xFFFFFFFF,
                                  tail[s].tobytes())
            assert got == C.crc32c(shards[s].tobytes(), 0xFFFFFFFF), \
                f"shard {s}"


def _launch(bitmats, path, runs, **kw):
    handle = _submit(bitmats, path, runs, **kw)
    _check(runs, bs.gf_encode_extents_with_crc_finalize(handle))
    return handle


@pytest.mark.parametrize("path", list(PATHS))
def test_second_launch_uploads_no_constants(bitmats, path):
    const_cache.reset_for_tests()
    widths = PATHS[path][1]
    first = _launch(bitmats, path, _runs(widths, 1))
    assert first["path"] == \
        ("hier_acc+w32_flat" if path == "split" else path)
    assert first["const_misses"] > 0 and first["const_hits"] == 0
    assert first["h2d_const_bytes"] > 0
    assert first["h2d_bytes"] == \
        first["padded_bytes"] + first["h2d_const_bytes"]
    # other data, the same operating point and layout: every constant
    # is handed over device-resident and the upload is the data alone
    second = _launch(bitmats, path, _runs(widths, 2))
    assert second["const_misses"] == 0
    assert second["const_hits"] == first["const_misses"]
    assert second["h2d_const_bytes"] == 0
    assert second["h2d_bytes"] == second["padded_bytes"]


@pytest.mark.parametrize("path,change", [
    (p, c) for p in PATHS for c in ("layout", "wb", "tile")
    if c == "layout" or p in ("hier_acc", "hier_lsub")])
def test_changed_key_misses_once(bitmats, path, change):
    """A constant is keyed by what it depends on: another run layout,
    sub-block or tile uploads what depends on it once, and only that."""
    const_cache.reset_for_tests()
    widths = PATHS[path][1]
    base = _launch(bitmats, path, _runs(widths, 3))
    if change == "layout":
        kw, widths = {}, PATHS[path][3]
    elif change == "wb":
        kw = {"wb": 256}
    else:
        kw = {"tile": 2048}
    changed = _launch(bitmats, path, _runs(widths, 4), **kw)
    assert 0 < changed["const_misses"] <= base["const_misses"]
    assert changed["h2d_const_bytes"] > 0
    if change == "layout":
        # the matrices of the operating point were hits
        assert changed["const_hits"] > 0
    again = _launch(bitmats, path, _runs(widths, 5), **kw)
    assert again["const_misses"] == 0 and again["h2d_const_bytes"] == 0
    # and the first key is still resident
    back = _launch(bitmats, path, _runs(PATHS[path][1], 6))
    assert back["const_misses"] == 0


@pytest.mark.parametrize("path", list(PATHS))
def test_donated_launch_leaves_the_constants_valid(bitmats, path):
    const_cache.reset_for_tests()
    widths = PATHS[path][1]
    _launch(bitmats, path, _runs(widths, 7))
    _launch(bitmats, path, _runs(widths, 8), donate=True)
    assert const_cache._cache
    assert not any(a.is_deleted() for a in const_cache._cache.values())
    third = _launch(bitmats, path, _runs(widths, 9))
    assert third["const_misses"] == 0


@pytest.mark.parametrize("twin", ["hier_acc", "hier_lsub"])
def test_donation_names_only_the_staged_words(bitmats, twin):
    """The CPU ignores donation, so read it off the lowering: of the
    donated twins' arguments only the staged words (the last) may be
    donated — every other array argument is a cached constant or the
    codec's resident matrix."""
    words = jax.ShapeDtypeStruct((K, 2 * TILE // 4), np.int32)
    cmat_sub = bs._crc_tile_w32_const(WB)
    statics = dict(m=M, tile=TILE, wb=WB, interpret=True)
    if twin == "hier_acc":
        run_map, first_map, adv, comb = bs._acc_launch_args(
            [2], TILE, WB)
        lowered = bs._hier_acc_donate.lower(
            bitmats[1], cmat_sub, adv, comb, run_map, first_map, words,
            nruns=1, **statics)
    else:
        lowered = bs._fused_hier_lsub_donate.lower(
            bitmats[1], cmat_sub, words, **statics)
    donated = [a.donated for a in
               jax.tree_util.tree_leaves(lowered.args_info)]
    assert donated == [False] * (len(donated) - 1) + [True]


def _traced_events(fn) -> collections.Counter:
    """Names of the host-plane events of a jax.profiler trace around
    fn(): every jit call is a `PjitFunction(<name>)` row (eager jnp
    operations are jit calls named after the operation), every program
    handed to the device a `PjRtCpuExecutable::Execute` row."""
    import tempfile
    with tempfile.TemporaryDirectory(prefix="launch_trace_") as d:
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        jax.profiler.start_trace(d, profiler_options=opts)
        try:
            fn()
        finally:
            jax.profiler.stop_trace()
        (pb,) = glob.glob(f"{d}/plugins/profile/*/*.xplane.pb")
        data = jax.profiler.ProfileData.from_file(pb)
    names = collections.Counter()
    for plane in data.planes:
        for line in plane.lines:
            names.update(ev.name for ev in line.events)
    return names


class _CountingNumpy:
    """numpy, with the conversions of a device array counted: each is
    a blocking fetch."""

    def __init__(self):
        self.fetches = 0

    def __getattr__(self, name):
        return getattr(np, name)

    def _counted(name):
        def convert(self, a, *args, **kw):
            self.fetches += isinstance(a, jax.Array)
            return getattr(np, name)(a, *args, **kw)
        return convert

    asarray = _counted("asarray")
    array = _counted("array")
    ascontiguousarray = _counted("ascontiguousarray")


@pytest.mark.parametrize("path", list(PATHS))
def test_launch_dispatches_only_its_programs(bitmats, path, monkeypatch):
    """Between the upload and the fetch a launch of N runs dispatches
    its kernel's program and at most the one run-combine program — no
    eager slice / reshape / transpose / pad / squeeze — and finalize
    fetches at most two device arrays a launch."""
    widths = PATHS[path][1]
    _launch(bitmats, path, _runs(widths, 10))       # compile, upload
    runs = _runs(widths, 11)
    counting = _CountingNumpy()
    out = {}

    def launch():
        handle = _submit(bitmats, path, runs)
        monkeypatch.setattr(bs, "np", counting)
        out["results"] = bs.gf_encode_extents_with_crc_finalize(handle)

    events = _traced_events(launch)
    _check(runs, out["results"])
    jits = {n[len("PjitFunction("):-1] for n in events
            if n.startswith("PjitFunction(")}
    allowed = PATHS[path][2]
    # the detector sees jit calls at all: the launch's own are there
    assert jits & allowed
    assert jits <= allowed, f"eager device operations: {jits - allowed}"
    launches = 2 if path == "split" else 1
    assert events["PjRtCpuExecutable::Execute"] <= 2 * launches
    assert 1 <= counting.fetches <= 2 * launches


def test_detector_sees_an_eager_operation():
    """The trace reading above is only as good as the rows it knows:
    an eager slice must show up as a jit call of its own."""
    import jax.numpy as jnp
    x = jnp.asarray(np.arange(24, dtype=np.int32).reshape(2, 3, 4))
    events = _traced_events(lambda: np.asarray(
        jnp.transpose(x[1:], (1, 0, 2))))
    assert any(n.startswith("PjitFunction(") for n in events)
    assert events["PjRtCpuExecutable::Execute"] >= 2


def test_queue_counts_constants_only_when_uploaded():
    """The launch queue's perf set: `ec_h2d_const_bytes` and
    `ec_const_cache_misses` stand still once the operating point and
    layout have been seen; `ec_h2d_bytes` then grows by the padded
    data alone."""
    const_cache.reset_for_tests()
    q = ECLaunchQueue(window_us=60_000_000.0)
    codec = ErasureCodePluginRegistry.instance().factory(
        "jax", {"k": str(K), "m": str(M)})
    store = MemStore()
    store.mount()
    backend = ECBackend(
        codec, StripeInfo(K * 64, 64),
        LocalShardBackend(store, pg_t(1, 0), K + M),
        launch_queue=q, perf_name="ec.1.0")
    dumps = []
    for v in range(3):
        txn = PGTransaction()
        txn.write(hobject_t(pool=1, name=f"o{v}"), 0,
                  np.full(16384, v + 1, dtype=np.uint8))
        done = []
        backend.submit_transaction(txn, eversion_t(1, v + 1),
                                   lambda: done.append(1))
        assert done == [1]
        dumps.append(q.perf.dump())
    first, second, third = dumps
    assert first["ec_const_cache_misses"] > 0
    assert first["ec_h2d_const_bytes"] > 0
    assert first["ec_h2d_bytes"] == \
        first["ec_host_launch_padded_bytes"] + first["ec_h2d_const_bytes"]
    for later in (second, third):
        assert later["ec_const_cache_misses"] == \
            first["ec_const_cache_misses"]
        assert later["ec_h2d_const_bytes"] == first["ec_h2d_const_bytes"]
    assert third["ec_const_cache_hits"] > second["ec_const_cache_hits"]
    assert third["ec_h2d_bytes"] - second["ec_h2d_bytes"] == \
        third["ec_host_launch_padded_bytes"] \
        - second["ec_host_launch_padded_bytes"]


@pytest.mark.parametrize("nblocks,cuts", [
    (1, [(0, 1)]),
    (8, [(0, 3), (4, 0), (5, 3)]),
    (13, [(0, 13)]),
    (16, [(1, 5), (8, 1), (9, 7)]),
])
def test_combine_crcs_runs_matches_per_run_fold(nblocks, cuts):
    """combine_crcs_runs against combine_crcs_pow2 run by run: the run
    layout is data, pad blocks and filler runs fall out."""
    bb = 64
    rng = np.random.default_rng(nblocks)
    lbits = rng.integers(0, 2, (3, nblocks, 32)).astype(np.int32)
    nruns = 4
    got = np.asarray(cl.combine_crcs_runs(
        lbits, bs._run_cuts(cuts, nblocks), nruns, bb))
    assert got.shape == (nruns, 3, 32)
    for i in range(nruns):
        boff, nb = cuts[i] if i < len(cuts) else (0, 0)
        want = np.asarray(cl.combine_crcs_pow2(
            lbits[:, boff:boff + nb], bb))
        np.testing.assert_array_equal(got[i], want)
