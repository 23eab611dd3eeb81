"""Sweep CLI for the fused parity+crc kernel's operating point.

The served path never sweeps: it reads its (tile, wb, combine) point
from ceph_tpu/ops/fused_points.json (ops/autotune.py).  This tool is
what produces that file.  Run on the chip, it validates every
candidate bit-exactly, times the valid ones, prints the per-candidate
table — a candidate that fails to compile prints with the compiler's
error, and the default point failing is fatal — and writes the winner
for this device kind and geometry into the file, which the builder
then commits.

Usage: python -m ceph_tpu.tools.fused_tile_sweep
           [--show | --validate-only] [--km K,M] [tiles...]

--show prints the point the served path would use here and where it
comes from, without measuring.

--validate-only runs ONLY the bit-exactness gate over every kernel
variant (no measurement, no file write) — compiled on an accelerator,
through the Pallas interpreter when the platform is CPU (the tier-1
hook, scripts/tier1.sh): a structural regression in any shipped
variant fails the gate.  Exits nonzero on any invalid candidate.
Defaults to one small tile (the variant grid is what matters); pass
tiles to widen.
"""
import sys

from ..ec.registry import ErasureCodePluginRegistry
from ..ops import autotune, device

VALIDATE_TILES = (32768,)


def _cand_tag(cand: dict) -> str:
    return (f"tile={cand['tile']:6d} wb={cand['wb']:5d} "
            f"combine={cand['combine']:6s}")


def validate_only(codec, bitmat32, tiles) -> int:
    k = codec.get_data_chunk_count()
    interpret = device.on_cpu()
    print(f"# validate-only ({'interpret' if interpret else 'compiled'}"
          f" on {device.describe()}): every kernel variant must stay "
          f"bit-exact vs gf_matvec + host crc32c")
    bad = 0
    cands = autotune.candidates(k, codec.get_coding_chunk_count(),
                                tiles=tiles or VALIDATE_TILES)
    for cand in cands:
        err = autotune.validate(codec.matrix[k:], bitmat32, cand,
                                interpret=interpret)
        print(f"{_cand_tag(cand)}  "
              + ("ok" if err is None else f"INVALID: {err}"))
        bad += err is not None
    if bad:
        print(f"# {bad}/{len(cands)} variants INVALID")
        return 1
    print(f"# all {len(cands)} variants bit-exact")
    return 0


def main():
    argv = sys.argv[1:]
    known = {"--show", "--validate-only", "--km"}
    unknown = [a for a in argv if a.startswith("-") and a not in known]
    if unknown:
        print(f"unknown option(s): {' '.join(unknown)}.  Usage: "
              "fused_tile_sweep [--show | --validate-only] [--km K,M] "
              "[tiles...]")
        raise SystemExit(2)
    k, m = 8, 3
    if "--km" in argv:
        i = argv.index("--km")
        k, m = (int(v) for v in argv[i + 1].split(","))
        del argv[i:i + 2]
    tiles = [int(t) for t in argv if not t.startswith("-")] or None
    codec = ErasureCodePluginRegistry.instance().factory(
        "jax", {"k": str(k), "m": str(m), "technique": "cauchy"})
    if "--show" in argv:
        print(f"{device.describe()}: {codec.fused_point()}")
        return
    import jax.numpy as jnp

    from ..ops import bitsliced as bs

    # the CPU plugin skips the w32 matrix build (no w32 kernel runs in
    # production there) — the interpret gate needs it regardless
    bitmat32 = codec._enc_bitmat32
    if bitmat32 is None:
        bitmat32 = jnp.asarray(bs._w32_bitmat(codec.matrix[k:]),
                               dtype=jnp.int8)
    if "--validate-only" in argv:
        raise SystemExit(validate_only(codec, bitmat32, tiles))
    report = []
    for row in autotune.sweep(k, m, codec.matrix[k:], bitmat32,
                              tiles=tiles):
        cand, rate, err, wall = row
        report.append(row)
        print(f"{_cand_tag(cand)}  "
              + (f"INVALID: {err}" if err is not None
                 else f"{rate / 1e9:7.2f} GB/s")
              + f"  ({wall:.0f}s incl. compiles)", flush=True)
    entry = autotune.winner(report)
    autotune.write_point(k, m, entry)
    print(f"best for {device.describe()['kind']} k{k}m{m}: {entry}")
    print(f"written to {autotune.POINTS_FILE}")


if __name__ == "__main__":
    main()
