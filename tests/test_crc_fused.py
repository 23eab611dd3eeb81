"""Fused parity+crc kernel tests: the linear-algebra crc32c must match
bufferlist::crc32c byte conventions exactly (north-star bit-exactness)."""

import numpy as np
import pytest

from ceph_tpu.common import crc32c as C
from ceph_tpu.ec import ErasureCodePluginRegistry
from ceph_tpu.ops import crc32c_linear as cl

REG = ErasureCodePluginRegistry.instance()


def test_tile_matrix_single_tile():
    tile = 64
    rng = np.random.default_rng(0)
    block = rng.integers(0, 256, tile, dtype=np.uint8)
    cmat = cl.crc_tile_matrix(tile)
    # reference: crc from seed 0
    want = C.crc32c(block.tobytes(), 0)
    # bits in bit-major layout for 1 "shard"
    bits = np.unpackbits(block[None, :], axis=0, bitorder="little")
    # rows: bit i of shard 0 -> (8*1, tile)
    import jax.numpy as jnp
    got_bits = np.asarray(cl.tile_crc_bits(
        jnp.asarray(bits.astype(np.int8)), jnp.asarray(cmat)))
    got = int(cl.bits_to_u32(got_bits)[0])
    assert got == want, f"{got:#x} != {want:#x}"


def test_fold_tiles_matches_direct():
    tile = 64
    rng = np.random.default_rng(1)
    data = rng.integers(0, 256, tile * 3 + 17, dtype=np.uint8)
    cmat = cl.crc_tile_matrix(tile)
    import jax.numpy as jnp
    ls = []
    for t in range(3):
        block = data[t * tile:(t + 1) * tile]
        bits = np.unpackbits(block[None, :], axis=0, bitorder="little")
        lb = np.asarray(cl.tile_crc_bits(
            jnp.asarray(bits.astype(np.int8)), jnp.asarray(cmat)))
        ls.append(int(cl.bits_to_u32(lb)[0]))
    got = cl.fold_tile_crcs(np.array(ls, dtype=np.uint32), tile,
                            0xFFFFFFFF, data[3 * tile:].tobytes())
    want = C.crc32c(data.tobytes(), 0xFFFFFFFF)
    assert got == want


@pytest.mark.parametrize("n_bytes", [2048, 4096 + 100, 2048 * 3])
def test_fused_encode_crc_matches_reference(n_bytes):
    k, m = 4, 2
    codec = REG.factory("jax", {"k": str(k), "m": str(m)})
    rng = np.random.default_rng(2)
    chunks = rng.integers(0, 256, (k, n_bytes), dtype=np.uint8)
    parity, crcs = codec.encode_chunks_with_crc(chunks)
    # parity identical to the unfused path
    np.testing.assert_array_equal(parity, codec.encode_chunks(chunks))
    # crcs identical to bufferlist::crc32c conventions
    allsh = np.concatenate([chunks, parity], axis=0)
    for s in range(k + m):
        want = C.crc32c(allsh[s].tobytes(), 0xFFFFFFFF)
        assert crcs[s] == want, f"shard {s}"


def test_fused_crc_custom_seeds():
    codec = REG.factory("jax", {"k": "2", "m": "1"})
    rng = np.random.default_rng(3)
    chunks = rng.integers(0, 256, (2, 2048), dtype=np.uint8)
    seeds = [0x1234, 0xDEAD, 0xFFFF]
    parity, crcs = codec.encode_chunks_with_crc(chunks, seeds=seeds)
    allsh = np.concatenate([chunks, parity], axis=0)
    for s in range(3):
        assert crcs[s] == C.crc32c(allsh[s].tobytes(), seeds[s])


def test_fused_pallas_kernel_interpret():
    """The actual fused Pallas kernel (interpret mode) vs the XLA twin."""
    import jax
    import jax.numpy as jnp
    from jax.experimental import pallas as pl
    from ceph_tpu.ops import bitsliced as bs
    from ceph_tpu.ec import gf

    k, m, tile, ntiles = 4, 2, 256, 2
    n = tile * ntiles
    mat = gf.cauchy_rs_matrix(k, m)[k:]
    bitmat = jnp.asarray(bs.interleave_bitmatrix(mat), dtype=jnp.int8)
    cmat = jnp.asarray(cl.crc_tile_matrix(tile))
    rng = np.random.default_rng(4)
    chunks = jnp.asarray(rng.integers(0, 256, (k, n), dtype=np.uint8))
    rows = -(-(k + m) // 8) * 8
    par, crcb = pl.pallas_call(
        bs._gf_crc_kernel,
        grid=(ntiles,),
        in_specs=[
            pl.BlockSpec((8 * m, 8 * k), lambda t: (0, 0)),
            pl.BlockSpec((8 * tile, 32), lambda t: (0, 0)),
            pl.BlockSpec((k, tile), lambda t: (0, t)),
        ],
        out_specs=[
            pl.BlockSpec((m, tile), lambda t: (0, t)),
            pl.BlockSpec((rows, 32), lambda t: (t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, n), jnp.uint8),
            jax.ShapeDtypeStruct((ntiles * rows, 32), jnp.int32),
        ],
        interpret=True,
    )(bitmat, cmat, chunks)
    par2, crcb2 = bs.gf_encode_with_crc_xla(bitmat, cmat, chunks, m,
                                            tile=tile)
    np.testing.assert_array_equal(np.asarray(par), np.asarray(par2))
    np.testing.assert_array_equal(
        np.asarray(crcb).reshape(ntiles, rows, 32)[:, :k + m],
        np.asarray(crcb2))


def test_w32_tile_crc_matrix_matches_reference():
    """crc_tile_matrix_w32's word-bit indexing vs direct crc32c."""
    import jax.numpy as jnp
    wt = 16                       # 64-byte tile
    rng = np.random.default_rng(5)
    block = rng.integers(0, 256, 4 * wt, dtype=np.uint8)
    words = jnp.asarray(block.view("<u4").view(np.int32)[None, :])
    cmat32 = jnp.asarray(cl.crc_tile_matrix_w32(wt))
    got_bits = np.asarray(cl.tile_crc_bits_w32(words, cmat32))
    got = int(cl.bits_to_u32(got_bits)[0])
    want = C.crc32c(block.tobytes(), 0)
    assert got == want, f"{got:#x} != {want:#x}"


def test_w32_fused_kernel_interpret():
    """The w32 fused parity+crc Pallas kernel (interpret mode): parity
    and folded crcs must match the byte-path host reference exactly."""
    import jax.numpy as jnp
    from ceph_tpu.ops import bitsliced as bs
    from ceph_tpu.ec import gf

    k, m = 4, 2
    tile = bs.FUSED_TILE
    n = tile * 2
    mat = gf.cauchy_rs_matrix(k, m)[k:]
    bitmat32 = jnp.asarray(bs._w32_bitmat(mat), dtype=jnp.int8)
    cmat32 = jnp.asarray(cl.crc_tile_matrix_w32(tile // 4))
    rng = np.random.default_rng(6)
    chunks = rng.integers(0, 256, (k, n), dtype=np.uint8)
    words = jnp.asarray(chunks.view("<u4").view(np.int32))
    par_w, crc_flat = bs.gf_encode_with_crc_pallas_w32(
        bitmat32, cmat32, words, m, interpret=True)
    parity = np.asarray(par_w).view("<u4").view(np.uint8).reshape(m, n)
    np.testing.assert_array_equal(parity, gf.gf_matvec(mat, chunks))
    rows = bs._crc_rows(k + m)
    crc_bits = np.asarray(crc_flat).reshape(-1, rows, 32)[:, :k + m]
    tile_ls = cl.bits_to_u32(crc_bits).T           # (k+m, ntiles)
    allsh = np.concatenate([chunks, parity], axis=0)
    for s in range(k + m):
        got = cl.fold_tile_crcs(tile_ls[s], tile, 0xFFFFFFFF)
        assert got == C.crc32c(allsh[s].tobytes(), 0xFFFFFFFF), f"shard {s}"


def test_hier_fused_kernel_interpret():
    """The hier-crc w32 fused kernel (interpret mode): per-sub-block
    level-1 L-vectors + XLA level-2 advance-combine must reproduce the
    byte-path host crc exactly (the round-5 kernel that unlocks the
    headline tile for the fused path; flat cmat capped it at 2 KiB)."""
    import jax.numpy as jnp
    from ceph_tpu.ops import bitsliced as bs
    from ceph_tpu.ec import gf

    k, m = 4, 2
    tile, wb = 4096, 128          # s = 8, (k+m)*s = 48: sublane-aligned
    n = tile * 2
    mat = gf.cauchy_rs_matrix(k, m)[k:]
    bitmat32 = jnp.asarray(bs._w32_bitmat(mat), dtype=jnp.int8)
    cmat_sub = jnp.asarray(cl.crc_tile_matrix_w32(wb))
    combine = jnp.asarray(cl.crc_combine_matrix(tile // 4 // wb, 4 * wb))
    rng = np.random.default_rng(8)
    chunks = rng.integers(0, 256, (k, n), dtype=np.uint8)
    words = jnp.asarray(chunks.view("<u4").view(np.int32))
    par_w, crc_flat = bs.gf_encode_with_crc_pallas_w32_hier(
        bitmat32, cmat_sub, combine, words, m, tile=tile, wb=wb,
        interpret=True)
    parity = np.asarray(par_w).view("<u4").view(np.uint8).reshape(m, n)
    np.testing.assert_array_equal(parity, gf.gf_matvec(mat, chunks))
    rows = bs._crc_rows(k + m)
    crc_bits = np.asarray(crc_flat).reshape(-1, rows, 32)[:, :k + m]
    tile_ls = cl.bits_to_u32(crc_bits).T           # (k+m, ntiles)
    allsh = np.concatenate([chunks, parity], axis=0)
    for s in range(k + m):
        got = cl.fold_tile_crcs(tile_ls[s], tile, 0xFFFFFFFF)
        assert got == C.crc32c(allsh[s].tobytes(), 0xFFFFFFFF), f"shard {s}"


def test_crc_combine_matrix_matches_fold():
    """Level-2 combine matrix == the host fold over equal sub-blocks."""
    import jax.numpy as jnp
    s, bb = 4, 64                 # 4 sub-blocks of 64 bytes
    rng = np.random.default_rng(9)
    data = rng.integers(0, 256, s * bb, dtype=np.uint8)
    cmat = cl.crc_tile_matrix(bb)
    ls = []
    for si in range(s):
        block = data[si * bb:(si + 1) * bb]
        bits = np.unpackbits(block[None, :], axis=0, bitorder="little")
        lb = np.asarray(cl.tile_crc_bits(
            jnp.asarray(bits.astype(np.int8)), jnp.asarray(cmat)))
        ls.append(lb[0])          # (32,) 0/1
    lsub = jnp.asarray(np.stack(ls).astype(np.int32))      # (s, 32)
    combine = jnp.asarray(cl.crc_combine_matrix(s, bb))
    out = cl.combine_subblock_crcs(lsub, combine, r=1, s=s)
    got = int(cl.bits_to_u32(np.asarray(out))[0, 0])
    assert got == C.crc32c(data.tobytes(), 0)


def test_multi_extent_hier_dispatch_interpret():
    """gf_encode_extents_with_crc's hier branch (runs >= the hier tile
    select the headline-tile hier kernel) driven end-to-end in interpret
    mode — the production TPU drain path for big sequential writes.
    The new contract: one device-combined L per shard per run plus a
    sub-BLOCK (not sub-tile) tail, folded in O(1) host combines."""
    import jax.numpy as jnp
    from ceph_tpu.ops import bitsliced as bs
    from ceph_tpu.ec import gf

    k, m = 4, 2
    tile, wb = 4096, 128          # s = 8, (k+m)*s = 48: sublane-aligned
    mat = gf.cauchy_rs_matrix(k, m)[k:]
    bitmat = jnp.asarray(bs.interleave_bitmatrix(mat), dtype=jnp.int8)
    bitmat32 = jnp.asarray(bs._w32_bitmat(mat), dtype=jnp.int8)
    rng = np.random.default_rng(10)
    widths = [tile * 2, tile + 513]       # second run: odd tail fold
    runs = [rng.integers(0, 256, (k, w), dtype=np.uint8) for w in widths]
    results = bs.gf_encode_extents_with_crc(
        bitmat, bitmat32, runs, m, use_w32=True, force_xla=False,
        interpret=True, tile=tile, wb=wb)
    seeds = [0xFFFFFFFF] * (k + m)
    for run, (par, l, tail, body) in zip(runs, results):
        w = run.shape[1]
        assert body == (w // (4 * wb)) * 4 * wb   # sub-block granular
        assert tail.shape[1] == w - body < 4 * wb
        np.testing.assert_array_equal(
            np.asarray(par), gf.gf_matvec(mat, run))
        allsh = np.concatenate([run, np.asarray(par)], axis=0)
        for s in range(k + m):
            got = cl.fold_run_crc(int(l[s]), body, seeds[s],
                                  tail[s].tobytes())
            assert got == C.crc32c(allsh[s].tobytes(), seeds[s]), \
                f"shard {s}"


def test_multi_extent_fused_launch():
    """gf_encode_extents_with_crc: several runs of different (unaligned,
    including odd and sub-block) lengths in one launch; per-run parity
    and seed-CHAINED crcs (each run folds onto the previous run's
    outputs, the hinfo append chain) must match the reference byte
    path byte-for-byte."""
    codec = REG.factory("jax", {"k": "4", "m": "2"})
    rng = np.random.default_rng(7)
    widths = [2048 * 2, 100, 2048 + 513, 4096, 1, 2048 * 3 + 1]
    runs = [rng.integers(0, 256, (4, w), dtype=np.uint8) for w in widths]
    results = codec.encode_extents_with_crc(runs)
    assert len(results) == len(runs)
    # chain crcs across runs as one object's appends
    seeds = [0xFFFFFFFF] * 6
    for run, (par, l, tail, body) in zip(runs, results):
        np.testing.assert_array_equal(
            np.asarray(par), codec.encode_chunks(run))
        crcs = codec.fold_extent_crcs(l, tail, seeds, body)
        allsh = np.concatenate([run, np.asarray(par)], axis=0)
        for s in range(6):
            want = C.crc32c(allsh[s].tobytes(), seeds[s])
            assert crcs[s] == want, f"shard {s}"
        seeds = crcs


@pytest.mark.parametrize("nblocks", [1, 2, 3, 5, 8, 13])
def test_combine_crcs_pow2_matches_host_fold(nblocks):
    """The device-side log-depth combine == the sequential host fold,
    for even AND odd block counts (odd levels prepend a virtual zero
    block, which must not change the combined L)."""
    import jax.numpy as jnp
    bb = 64
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, nblocks * bb, dtype=np.uint8)
    cmat = cl.crc_tile_matrix(bb)
    ls = []
    for t in range(nblocks):
        block = data[t * bb:(t + 1) * bb]
        bits = np.unpackbits(block[None, :], axis=0, bitorder="little")
        lb = np.asarray(cl.tile_crc_bits(
            jnp.asarray(bits.astype(np.int8)), jnp.asarray(cmat)))
        ls.append(lb[0])
    lbits = jnp.asarray(np.stack(ls)[None].astype(np.int32))
    comb = np.asarray(cl.combine_crcs_pow2(lbits, bb))
    l = int(cl.bits_to_u32(comb)[0])
    assert cl.fold_run_crc(l, nblocks * bb, 0xFFFFFFFF) == \
        C.crc32c(data.tobytes(), 0xFFFFFFFF)


def test_fold_run_crc_degenerate_cases():
    """O(1) host fold edge cases: empty body (tail-only run), empty
    tail, and both empty must all reduce to plain crc32c."""
    rng = np.random.default_rng(12)
    tail = rng.integers(0, 256, 100, dtype=np.uint8).tobytes()
    assert cl.fold_run_crc(0, 0, 0xFFFFFFFF, tail) == \
        C.crc32c(tail, 0xFFFFFFFF)
    assert cl.fold_run_crc(0, 0, 0x1234) == \
        C.crc32c(b"", 0x1234)


@pytest.mark.parametrize("combine", ["xla", "kernel"])
def test_device_fold_launch_interpret(combine):
    """gf_encode_with_crc_w32_fold (the bench/write-path launch): one
    L per shard per dispatch, multi-tile extents, through both combine
    depths (the XLA log-fold and the in-kernel VMEM accumulator),
    bit-exact against the host crc32c with a caller seed.  (The
    combine x wb grid at a real tile runs in tier-1 via
    `fused_tile_sweep --validate-only` — outside the pytest budget.)"""
    import jax.numpy as jnp
    from ceph_tpu.ops import bitsliced as bs
    from ceph_tpu.ec import gf

    k, m = 4, 2
    tile, wb = 4096, 128
    n = tile * 3                  # multi-tile extent
    mat = gf.cauchy_rs_matrix(k, m)[k:]
    bitmat32 = jnp.asarray(bs._w32_bitmat(mat), dtype=jnp.int8)
    cmat_sub = jnp.asarray(cl.crc_tile_matrix_w32(wb))
    rng = np.random.default_rng(13)
    chunks = rng.integers(0, 256, (k, n), dtype=np.uint8)
    words = jnp.asarray(chunks.view("<u4").view(np.int32))
    par_w, lbits = bs.gf_encode_with_crc_w32_fold(
        bitmat32, cmat_sub, words, m, tile=tile, wb=wb,
        interpret=True, combine=combine)
    assert lbits.shape == (k + m, 32)     # ONE L per shard per launch
    parity = np.asarray(par_w).view("<u4").view(np.uint8).reshape(m, n)
    np.testing.assert_array_equal(parity, gf.gf_matvec(mat, chunks))
    ls = cl.bits_to_u32(np.asarray(lbits))
    allsh = np.concatenate([chunks, parity], axis=0)
    for s in range(k + m):
        for seed in (0xFFFFFFFF, 0, 0xDEAD):
            got = cl.fold_run_crc(int(ls[s]), n, seed)
            assert got == C.crc32c(allsh[s].tobytes(), seed), \
                f"shard {s} seed {seed:#x}"


def _legal_points(k, m, tiles, wbs):
    """Every (tile, wb) the sublane rule (k+m)*(tile/4/wb) % 8 == 0
    allows from the given axes — the alignment edges the accumulator
    kernel must survive."""
    out = []
    for tile in tiles:
        for wb in wbs:
            wt = tile // 4
            if wt % wb == 0 and ((k + m) * (wt // wb)) % 8 == 0:
                out.append((tile, wb))
    return out


def test_acc_kernel_every_legal_alignment_edge():
    """The in-kernel combine accumulator at EVERY (tile, wb) alignment
    edge the sublane rule allows from the small-tile axes, three grid
    steps each (init + two advance folds), interpret mode, bit-exact
    vs the host crc."""
    import jax.numpy as jnp
    from ceph_tpu.ops import bitsliced as bs
    from ceph_tpu.ec import gf

    k, m = 4, 2
    points = _legal_points(k, m, (1024, 2048, 4096), (64, 128, 256))
    assert len(points) >= 5       # the rule must not silence the sweep
    mat = gf.cauchy_rs_matrix(k, m)[k:]
    bitmat32 = jnp.asarray(bs._w32_bitmat(mat), dtype=jnp.int8)
    rng = np.random.default_rng(17)
    for tile, wb in points:
        n = tile * 3
        chunks = rng.integers(0, 256, (k, n), dtype=np.uint8)
        words = jnp.asarray(chunks.view("<u4").view(np.int32))
        cmat_sub = jnp.asarray(cl.crc_tile_matrix_w32(wb))
        par_w, lbits = bs.gf_encode_with_crc_w32_fold(
            bitmat32, cmat_sub, words, m, tile=tile, wb=wb,
            interpret=True, combine="kernel")
        parity = np.asarray(par_w).view("<u4").view(np.uint8) \
            .reshape(m, n)
        np.testing.assert_array_equal(parity, gf.gf_matvec(mat, chunks))
        ls = cl.bits_to_u32(np.asarray(lbits))
        allsh = np.concatenate([chunks, parity], axis=0)
        for s in range(k + m):
            assert cl.fold_run_crc(int(ls[s]), n, 0xFFFFFFFF) == \
                C.crc32c(allsh[s].tobytes(), 0xFFFFFFFF), \
                f"tile={tile} wb={wb} shard {s}"


def test_multi_extent_acc_kernel_interpret():
    """The accumulator extents path (combine="kernel"): several runs of
    different multi-tile lengths INCLUDING odd sub-block tails in one
    launch — per-run L must cover the run's every byte (empty
    tail_bytes, body == width: the host tail fold is gone), runs are
    front-padded (prefix zeros are crc-free), parity and seed-CHAINED
    crcs byte-exact vs the reference."""
    import jax.numpy as jnp
    from ceph_tpu.ops import bitsliced as bs
    from ceph_tpu.ec import gf

    k, m = 4, 2
    tile, wb = 4096, 128
    mat = gf.cauchy_rs_matrix(k, m)[k:]
    bitmat = jnp.asarray(bs.interleave_bitmatrix(mat), dtype=jnp.int8)
    bitmat32 = jnp.asarray(bs._w32_bitmat(mat), dtype=jnp.int8)
    rng = np.random.default_rng(18)
    # odd tail, exact multiple, sub-block-odd tail, single tile
    widths = [tile * 2 + 513, tile * 3, tile + 1, tile]
    runs = [rng.integers(0, 256, (k, w), dtype=np.uint8)
            for w in widths]
    handle = bs.gf_encode_extents_with_crc_submit(
        bitmat, bitmat32, runs, m, use_w32=True, force_xla=False,
        interpret=True, tile=tile, wb=wb, combine="kernel")
    assert handle["path"] == "hier_acc"
    results = bs.gf_encode_extents_with_crc_finalize(handle)
    seeds = [0xFFFFFFFF] * (k + m)
    for run, (par, l, tail, body) in zip(runs, results):
        w = run.shape[1]
        assert body == w                  # L covers the whole run
        assert tail.shape[1] == 0         # no host tail fold
        np.testing.assert_array_equal(
            np.asarray(par), gf.gf_matvec(mat, run))
        allsh = np.concatenate([run, np.asarray(par)], axis=0)
        crcs = [cl.fold_run_crc(int(l[s]), body, seeds[s])
                for s in range(k + m)]
        for s in range(k + m):
            assert crcs[s] == C.crc32c(allsh[s].tobytes(), seeds[s]), \
                f"shard {s}"
        seeds = crcs                      # hinfo chain across runs


def test_acc_chained_seeds_across_pipelined_drains():
    """Two accumulator drains IN FLIGHT at once (submit A, submit B,
    then finalize in submit order — the dispatch-ahead window), with
    drain B's hinfo seeds chained off drain A's crcs: the projected-
    seed pipeline the ECBackend runs at depth 2."""
    import jax.numpy as jnp
    from ceph_tpu.ops import bitsliced as bs
    from ceph_tpu.ec import gf

    k, m = 4, 2
    tile, wb = 4096, 128
    mat = gf.cauchy_rs_matrix(k, m)[k:]
    bitmat = jnp.asarray(bs.interleave_bitmatrix(mat), dtype=jnp.int8)
    bitmat32 = jnp.asarray(bs._w32_bitmat(mat), dtype=jnp.int8)
    rng = np.random.default_rng(19)
    drains = [[rng.integers(0, 256, (k, tile + 257), dtype=np.uint8)],
              [rng.integers(0, 256, (k, tile * 2 + 99), dtype=np.uint8)]]
    handles = [bs.gf_encode_extents_with_crc_submit(
        bitmat, bitmat32, d, m, use_w32=True, force_xla=False,
        interpret=True, tile=tile, wb=wb,
        combine="kernel") for d in drains]       # both launched first
    seeds = [0xFFFFFFFF] * (k + m)
    streams = [b""] * (k + m)
    for d, h in zip(drains, handles):            # finalize in order
        [(par, l, tail, body)] = \
            bs.gf_encode_extents_with_crc_finalize(h)
        allsh = np.concatenate([d[0], np.asarray(par)], axis=0)
        crcs = [cl.fold_run_crc(int(l[s]), body, seeds[s],
                                tail[s].tobytes())
                for s in range(k + m)]
        for s in range(k + m):
            streams[s] += allsh[s].tobytes()
            assert crcs[s] == C.crc32c(streams[s], 0xFFFFFFFF), \
                f"shard {s}"
        seeds = crcs


@pytest.mark.parametrize("n_bytes", [2047, 2048 + 1, 2048 * 4 + 100])
def test_fused_odd_tails_chained_seeds(n_bytes):
    """Odd tail lengths through the plugin path with per-shard chained
    seeds (three consecutive appends of the same odd-sized extent, each
    seeded by the previous crcs — the HashInfo evolution)."""
    k, m = 4, 2
    codec = REG.factory("jax", {"k": str(k), "m": str(m)})
    rng = np.random.default_rng(15)
    seeds = [0xFFFFFFFF] * (k + m)
    streams = [b""] * (k + m)
    for _ in range(3):
        chunks = rng.integers(0, 256, (k, n_bytes), dtype=np.uint8)
        parity, crcs = codec.encode_chunks_with_crc(chunks, seeds=seeds)
        allsh = np.concatenate([chunks, parity], axis=0)
        for s in range(k + m):
            streams[s] += allsh[s].tobytes()
            assert crcs[s] == C.crc32c(streams[s], 0xFFFFFFFF), \
                f"shard {s}"
        seeds = crcs
