"""Tile/variant sweep for the w32 encode kernel on real TPU hardware.

Times gf_bitmatmul_pallas_w32 across per-chunk tile sizes for both the
all-planes kernel (stream=False) and the streaming-accumulation kernel
(stream=True), with the same chained-fori_loop slope method bench.py
uses (defeats dispatch elision; see bench.py docstring).  Verifies
bit-exactness of every variant against the XLA oracle before timing
it.  Prints one JSON line per configuration.

Usage: python -m ceph_tpu.tools.w32_sweep [--quick]
"""

from __future__ import annotations

import argparse
import json
import sys
import time

import numpy as np

K, M = 8, 3
PER_CHUNK = 4 << 20           # resident bytes per chunk (divides all tiles)
TILES = [1 << 16, 1 << 17, 1 << 18, 1 << 19, 1 << 20, 1 << 21, 1 << 22]


def slope_time(step, x0, rows, iters_lo=50, iters_hi=150, passes=3):
    """bench.py-style chained slope timing; returns sec/iteration."""
    import jax
    from jax import lax

    def make(iters):
        @jax.jit
        def f(x):
            def body(i, x):
                r = step(x)
                return x.at[:rows, :].set(x[:rows, :] ^ r)
            return lax.fori_loop(0, iters, body, x)
        return f

    f_lo, f_hi = make(iters_lo), make(iters_hi)
    reps = 3
    variants = [jax.block_until_ready(x0 ^ (i + 1)) for i in range(reps)]
    jax.block_until_ready(f_lo(x0))
    jax.block_until_ready(f_hi(x0))
    dts = []
    for _ in range(passes + 2):
        lo, hi = [], []
        for i in range(reps):
            t0 = time.perf_counter()
            jax.block_until_ready(f_lo(variants[i]))
            lo.append(time.perf_counter() - t0)
            t0 = time.perf_counter()
            jax.block_until_ready(f_hi(variants[i]))
            hi.append(time.perf_counter() - t0)
        dt = (min(hi) - min(lo)) / (iters_hi - iters_lo)
        if dt > 0:
            dts.append(dt)
            if len(dts) >= passes:
                break
        variants = [jax.block_until_ready(v ^ 0x5A) for v in variants]
    if not dts:
        raise RuntimeError("non-positive slope (timing noise)")
    dts.sort()
    return dts[len(dts) // 2]


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true",
                    help="fewer iterations (smoke)")
    ap.add_argument("--tiles", default=None,
                    help="comma-separated per-chunk tile bytes")
    ap.add_argument("--variants", default="0,1",
                    help="comma list: 0=all-planes, 1=streaming")
    args = ap.parse_args()

    import jax
    import jax.numpy as jnp

    from ..ec import gf
    from ..ops import bitsliced as bs

    backend = jax.default_backend()
    print(f"# backend: {backend}", file=sys.stderr)
    on_tpu = backend != "cpu"

    mat = gf.cauchy_rs_matrix(K, M)[K:]
    bitmat32 = jnp.asarray(bs._w32_bitmat(mat), dtype=jnp.int8)
    bitmat8 = jnp.asarray(bs.interleave_bitmatrix(mat), dtype=jnp.int8)

    rng = np.random.default_rng(7)
    per_chunk = PER_CHUNK if on_tpu and not args.quick else 1 << 18
    flat = rng.integers(0, 256, (K, per_chunk), dtype=np.uint8)
    words = jnp.asarray(flat.view("<u4").view(np.int32))
    total_bytes = K * per_chunk

    # oracle (small slice, byte path)
    small = flat[:, : 1 << 16]
    want = np.asarray(bs.gf_bitmatmul_xla(
        bitmat8, jnp.asarray(small), M))
    small_words = jnp.asarray(small.view("<u4").view(np.int32))

    tiles = ([int(t) for t in args.tiles.split(",")]
             if args.tiles else TILES)
    variants = [bool(int(v)) for v in args.variants.split(",")]
    iters = (10, 30) if args.quick else (30, 90)
    for stream in variants:
        # bit-exactness on hardware before any timing
        try:
            got = np.asarray(bs.gf_bitmatmul_pallas_w32(
                bitmat32, small_words, M, tile=1 << 15,
                interpret=not on_tpu, stream=stream))
            got_bytes = got.view("<u4").view(np.uint8).reshape(M, -1)
            exact = bool((got_bytes == want).all())
        except Exception as e:  # noqa: BLE001 - variant unsupported
            print(json.dumps({"stream": stream,
                              "error": str(e)[:200]}), flush=True)
            continue
        for tile in tiles:
            if tile > per_chunk:
                continue
            rec = {"stream": stream, "tile": tile, "exact": exact}
            try:
                def step(x, _t=tile, _s=stream):
                    return bs.gf_bitmatmul_pallas_w32(
                        bitmat32, x, M, tile=_t,
                        interpret=not on_tpu, stream=_s)
                dt = slope_time(step, words, M,
                                iters_lo=iters[0], iters_hi=iters[1])
                rec["gbps"] = round(total_bytes / dt / 1e9, 1)
            except Exception as e:  # noqa: BLE001 - report and continue
                rec["error"] = str(e)[:200]
            print(json.dumps(rec), flush=True)


if __name__ == "__main__":
    main()
