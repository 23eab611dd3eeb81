"""Operating point of the fused parity+crc kernel.

The fused hier kernel has three knobs with hardware-dependent optima:

  * `tile` — bytes per grid step (DMA granularity vs VMEM pressure);
  * `wb` — crc sub-block words (the crc matmul's M dimension is
    (k+m) * tile/4/wb, so wb trades MXU row utilization against matrix
    VMEM, and with the in-kernel combine also the accumulator size);
  * `combine` — the L combine depth: "xla" streams per-grid-step
    sub-block L-blocks to HBM and log-folds them in XLA (parallel grid
    semantics), "kernel" folds them into a VMEM-resident per-run
    accumulator inside the kernel (sequential grid, no HBM round-trip
    or relayout).

The SERVED path never measures anything.  `fused_operating_point`
reads the point for this device kind and geometry from
`fused_points.json`, a file in git beside this module, and falls back
to the static `default_point()` — saying which in the returned
`source`, so `perf dump`-level provenance (the launch bucket string,
chip_smoke.py's JSON) always names where the running kernel's shape
came from.  Two cold processes on the same checkout therefore run the
same program.

The sweep that produces the file is a tool, not a code path:
`python -m ceph_tpu.tools.fused_tile_sweep` on the chip validates every
candidate bit-exactly against the host parity and crc32c oracles,
times the valid ones, prints the table — every candidate that fails
to compile is a finding, with its error — and writes the winner into
the file for the builder to commit.  The default point failing is
fatal there.
"""

from __future__ import annotations

import functools
import json
import time
from pathlib import Path

import numpy as np

from . import device

POINTS_FILE = Path(__file__).with_name("fused_points.json")

# candidate space: tiles up to the headline kernel's W32_TILE, wb
# spanning crc-matmul M from ~(k+m)*32 to ~(k+m)*256.  Tile 262144 is
# gone: on TPU v5 lite (jax 0.9.0, libtpu 0.0.34) Mosaic runs out of
# scoped VMEM for it at every wb after a 3-5 minute compile (chip run,
# PR 21).  Compile cost grows with the tile (the kernel body is fully
# unrolled over it): ~6 s at 32768, ~16 s at 65536, ~60 s at 131072
# per launch shape at wb=512 (same run), which is why the sweep's
# report carries each candidate's compile seconds beside its rate.
SWEEP_TILES = (32768, 65536, 131072)
SWEEP_WBS = (256, 512, 1024)
SWEEP_COMBINES = ("xla", "kernel")

# measurement input: bytes per shard (multiple of every sweep tile)
MEASURE_BYTES = 1 << 21
MEASURE_ITERS = (5, 15)

# Selection: candidates within this fraction of the fastest rate count
# as tied, and the tie goes to the cheapest compile.  Why: on TPU v5
# lite every (tile, wb, combine) landed between 31 and 41 GB/s — three
# orders above what the served path feeds the kernel — while compile
# cost per launch shape spread 10x with the tile (PERF.md Findings, PR
# 21).  A first-seen launch shape compiles in-band, inside a client
# op with a 30 s timeout, and the boot prewarm pays the same cost per
# bucket; a few percent of kernel rate does not buy that back.
RATE_TIE = 0.05

_POINT_KEYS = ("tile", "wb", "combine")


def default_point() -> dict:
    """The static point: the frozen tile/wb with the XLA combine."""
    from . import bitsliced as bs
    return {"tile": bs.FUSED_TILE_HIER, "wb": bs.FUSED_WB,
            "combine": "xla"}


@functools.cache
def _load_points() -> dict:
    return json.loads(POINTS_FILE.read_text())


def fused_operating_point(k: int, m: int) -> dict:
    """The (tile, wb, combine) point the fused encode+crc path runs at
    on THIS device for a (k, m) code, plus `source`: the committed
    file entry, or the default when the file has none for this device
    kind and geometry (and always on the CPU twin, which never runs
    the hier kernels outside interpret-mode tests)."""
    if device.on_cpu():
        return {**default_point(), "source": "default (cpu)"}
    kind = device.describe()["kind"]
    ent = _load_points().get(kind, {}).get(f"k{k}m{m}")
    if ent is None:
        return {**default_point(),
                "source": f"default (no {kind!r} k{k}m{m} entry in "
                          f"{POINTS_FILE.name})"}
    return {**{kk: ent[kk] for kk in _POINT_KEYS},
            "source": f"{POINTS_FILE.name}[{kind}][k{k}m{m}]"}


def candidates(k: int, m: int, tiles=None, wbs=None) -> list[dict]:
    """Legal (tile, wb, combine) points, the default first."""
    r = k + m
    out = []
    for tile in tiles or SWEEP_TILES:
        wt = tile // 4
        for wb in wbs or SWEEP_WBS:
            if wt % wb:
                continue
            s = wt // wb
            if (r * s) % 8:      # lsub/lacc out-block sublane alignment
                continue
            for combine in SWEEP_COMBINES:
                out.append({"tile": tile, "wb": wb, "combine": combine})
    dflt = default_point()
    out.sort(key=lambda c: (c != dflt, c["tile"] != dflt["tile"],
                            c["wb"] != dflt["wb"]))
    return out


def validate(mat: np.ndarray, bitmat32, cand: dict,
             interpret: bool = False) -> str | None:
    """Bit-exactness gate: one small fused launch (TWO grid steps, so
    the accumulator's cross-step advance fold is exercised) vs the
    host parity and crc32c oracles.  Returns None when the candidate
    compiles and matches, else what went wrong — a lowering/compile
    error is reported with the compiler's message, never swallowed.
    `interpret` runs the same check through the Pallas interpreter
    (the CPU tier-1 gate, fused_tile_sweep --validate-only)."""
    import jax.numpy as jnp

    from ..common import crc32c as _crc
    from ..ec import gf
    from . import bitsliced as bs
    from . import crc32c_linear as cl
    m_, k = mat.shape
    tile, wb = cand["tile"], cand["wb"]
    rng = np.random.default_rng(0xC5C)
    chunks = rng.integers(0, 256, (k, 2 * tile), dtype=np.uint8)
    words = jnp.asarray(chunks.view("<u4").view(np.int32))
    cmat_sub = jnp.asarray(cl.crc_tile_matrix_w32(wb))
    try:
        par_w, lbits = bs.gf_encode_with_crc_w32_fold(
            bitmat32, cmat_sub, words, m_, tile=tile, wb=wb,
            interpret=interpret, combine=cand["combine"])
        parity = np.asarray(par_w).view("<u4").view(np.uint8) \
            .reshape(m_, 2 * tile)
        ls = cl.bits_to_u32(np.asarray(lbits))
    except Exception as e:  # noqa: BLE001 — the finding to report
        return f"compile/launch failed: {type(e).__name__}: {e}"
    if not np.array_equal(parity, gf.gf_matvec(mat, chunks)):
        return "parity differs from gf_matvec"
    allsh = np.concatenate([chunks, parity], axis=0)
    for s in range(k + m_):
        if cl.fold_run_crc(int(ls[s]), 2 * tile, 0xFFFFFFFF) != \
                _crc.crc32c(allsh[s].tobytes(), 0xFFFFFFFF):
            return f"crc of shard {s} differs from host crc32c"
    return None


def measure(bitmat32, k: int, m: int, cand: dict) -> float:
    """Short chained-loop slope timing: input bytes/sec on the live
    device (the median of two slopes; a slope above the device's HBM
    peak is an elided dispatch and is dropped — 0.0 when none
    survives).  ONE compile: the trip count is a traced argument."""
    import jax
    import jax.numpy as jnp
    from jax import lax

    from . import bitsliced as bs
    from . import crc32c_linear as cl
    tile, wb = cand["tile"], cand["wb"]
    hbm_peak = device.peaks()["hbm_bytes_per_s"]
    rng = np.random.default_rng(0x7E5)
    flat = rng.integers(0, 256, (k, MEASURE_BYTES), dtype=np.uint8)
    x0 = jnp.asarray(flat.view(np.int32))
    cmat_sub = jnp.asarray(cl.crc_tile_matrix_w32(wb))

    def step(x):
        par, lbits = bs.gf_encode_with_crc_w32_fold(
            bitmat32, cmat_sub, x, m, tile=tile, wb=wb,
            combine=cand["combine"])
        return par ^ jnp.sum(lbits)      # crc feeds the chain: no DCE

    @jax.jit
    def f(x, iters):
        def body(i, x):
            return x.at[:m, :].set(x[:m, :] ^ step(x))
        return lax.fori_loop(0, iters, body, x)

    lo_i, hi_i = MEASURE_ITERS
    jax.block_until_ready(f(x0, lo_i))              # compile
    rates = []
    for rep in range(2):
        v = jax.block_until_ready(x0 ^ (rep + 1))
        t0 = time.perf_counter()
        jax.block_until_ready(f(v, lo_i))
        lo = time.perf_counter() - t0
        t0 = time.perf_counter()
        jax.block_until_ready(f(v, hi_i))
        hi = time.perf_counter() - t0
        dt = (hi - lo) / (hi_i - lo_i)
        if dt > 0 and k * MEASURE_BYTES / dt < hbm_peak:
            rates.append(k * MEASURE_BYTES / dt)
    rates.sort()
    return rates[len(rates) // 2] if rates else 0.0


def sweep(k: int, m: int, mat: np.ndarray, bitmat32,
          tiles=None, wbs=None):
    """Validate and time every candidate on the live accelerator,
    yielding one report row per candidate as it finishes: (cand,
    bytes/sec | None, error | None, wall seconds spent on it —
    compiles dominate).  Raises when the default point does not
    validate: the served path has no other point to fall back to."""
    device.require_accelerator("fused operating-point sweep")
    for cand in candidates(k, m, tiles, wbs):
        t0 = time.perf_counter()
        err = validate(mat, bitmat32, cand)
        if err is not None:
            yield cand, None, err, time.perf_counter() - t0
            if cand == default_point():
                raise RuntimeError(
                    f"default fused point {cand} failed on "
                    f"{device.describe()['kind']}: {err}")
            continue
        rate = measure(bitmat32, k, m, cand)
        yield cand, rate, None, time.perf_counter() - t0


def winner(report: list) -> dict:
    """The points-file entry a sweep's report selects: of the valid
    candidates whose rate is within RATE_TIE of the fastest, the one
    that was cheapest to compile.  The entry keeps the fastest rate
    seen (`best_gbps`) beside its own, so the trade stays readable."""
    timed = [(rate, wall, cand) for cand, rate, _, wall in report
             if rate]
    if not timed:
        raise RuntimeError(f"no candidate could be timed: {report}")
    import jax
    best_rate = max(rate for rate, _, _ in timed)
    rate, wall, cand = min(
        (t for t in timed if t[0] >= (1 - RATE_TIE) * best_rate),
        key=lambda t: t[1])
    return {**cand, "gbps": round(rate / 1e9, 3),
            "best_gbps": round(best_rate / 1e9, 3),
            "compile_wall_s": round(wall, 1), "jax": jax.__version__,
            "when": time.strftime("%Y-%m-%dT%H:%M:%S")}


def write_point(k: int, m: int, entry: dict) -> None:
    """Record a sweep's winner for this device kind in POINTS_FILE (the
    builder commits the file)."""
    data = json.loads(POINTS_FILE.read_text())
    data.setdefault(device.describe()["kind"], {})[f"k{k}m{m}"] = entry
    POINTS_FILE.write_text(json.dumps(data, indent=1, sort_keys=True)
                           + "\n")
    _load_points.cache_clear()
