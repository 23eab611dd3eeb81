"""crc32c as GF(2) linear algebra — the fused-checksum half of the
north star (BASELINE.json: "crc32c for the same shards is fused into the
stripe kernel so checksum and parity come out of one launch").

Why this works: the crc32c byte-update  crc' = (crc >> 8) ^ T[(crc ^ b)
& 0xff]  is GF(2)-linear in (crc, b).  Hence for an N-byte block B,

    crc(B, seed) = A_N . seed  (+)  L(B)

where A_N is the 32x32 zero-advance matrix (ceph_tpu.common.crc32c
crc32c_zeros computes A_N . s) and the *linear part* L(B) = crc(B, 0) is
a GF(2)-linear map of B's bits: L(B) = C_T @ bits(B) mod 2 for a fixed
(32, 8T) 0/1 matrix per tile size T.  So the same bit-planes the GF(2^8)
encode kernel already holds in VMEM feed a second small matmul that
yields each shard's per-tile L-vector; tiles then fold on the host in
O(ntiles) 32-bit combines:  L(B1||B2) = A_{|B2|} L(B1) + L(B2).

Matches `bufferlist::crc32c` exactly (Castagnoli, caller seed, no final
xor) — verified against ceph_tpu.common.crc32c in tests.

Layout note: the encode kernel's bit rows are bit-major interleaved
(row i*r + s = bit i of shard s), so the tile matrix is exposed as
stacked C_i^T slices, shape (8T, 32), rows [i*T:(i+1)*T] = C_i^T:
L_shard = sum_i bits_i(shard) @ C_i^T.
"""

from __future__ import annotations

import functools

import numpy as np

from ..common import crc32c as _crc
from ..common.util import next_pow2


@functools.lru_cache(maxsize=8)
def crc_tile_matrix(tile: int) -> np.ndarray:
    """(8*tile, 32) int8: row [i*tile + t] = bits of L(block with only
    bit i of byte t set).  Flat 2-D so Pallas/Mosaic never sees a
    rank-3 operand."""
    out = np.zeros((8, tile, 32), dtype=np.int8)
    # contribution of byte v at position t in a T-byte block:
    # A_{T-1-t} . L1(v), with L1(v) = crc of the single byte from state 0
    l1 = np.zeros((8, 32), dtype=np.int8)
    for i in range(8):
        v = _crc.crc32c(bytes([1 << i]), 0)
        l1[i] = [(v >> j) & 1 for j in range(32)]
    # walk positions from the last byte backwards, advancing by one byte
    cur = l1.copy()           # A_0 . L1
    for t in range(tile - 1, -1, -1):
        out[:, t, :] = cur
        if t > 0:
            for i in range(8):
                val = sum(int(cur[i, j]) << j for j in range(32))
                adv = _crc.crc32c_zeros(val, 1)
                cur[i] = [(adv >> j) & 1 for j in range(32)]
    return out.reshape(8 * tile, 32)


@functools.lru_cache(maxsize=8)
def crc_tile_matrix_w32(wt: int) -> np.ndarray:
    """(32*wt, 32) int8 for the word-packed kernel: rows [i*wt + t] =
    L-contribution of word-bit i at word position t.  Word bit i of a
    little-endian i32 word is bit (i%8) of the byte at tile position
    4t + i//8, so this is a re-indexing of crc_tile_matrix(4*wt)."""
    base = crc_tile_matrix(4 * wt).reshape(8, 4 * wt, 32)
    out = np.zeros((32, wt, 32), dtype=np.int8)
    for i in range(32):
        out[i] = base[i % 8, (i // 8)::4, :]
    return out.reshape(32 * wt, 32)


def tile_crc_bits_w32(words, cmat32):
    """words: (r, Wt) i32 packed bytes; cmat32: (32*Wt, 32) from
    crc_tile_matrix_w32 -> (r, 32) int32 0/1 L-bit matrix per shard.
    i32 shifts legalize in Mosaic (i8 shifts don't), so the 32
    bit-plane extractions stay word-wide."""
    import jax
    import jax.numpy as jnp
    r, wt = words.shape
    acc = jnp.zeros((r, 32), dtype=jnp.float32)
    for i in range(32):
        plane = ((words >> i) & 1).astype(jnp.float32)   # (r, Wt)
        acc = acc + jax.lax.dot_general(
            plane, cmat32[i * wt:(i + 1) * wt].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return acc.astype(jnp.int32) & 1


@functools.lru_cache(maxsize=64)
def crc_advance_matrix(nbytes: int) -> np.ndarray:
    """(32, 32) int8: row j = bits of A_{nbytes} e_j, so advancing an
    L-vector over `nbytes` zero bytes is `lbits @ this` (mod 2) — the
    per-grid-step fold matrix of the in-kernel L accumulator
    (bitsliced._make_gf_crc_kernel_w32_hier_acc): a (rows, 32) x
    (32, 32) int8 matmul whose sublane layout never changes, so it
    lowers in Mosaic where the pairwise combine's sublane-to-lane
    relayout does not."""
    out = np.zeros((32, 32), dtype=np.int8)
    for j in range(32):
        v = _crc.crc32c_zeros(1 << j, nbytes)
        out[j] = [(v >> b) & 1 for b in range(32)]
    return out


@functools.lru_cache(maxsize=8)
def crc_combine_matrix(s: int, block_bytes: int) -> np.ndarray:
    """(s*32, 32) int8 level-2 matrix: row [si*32 + j] = bits of
    A^{block_bytes*(s-1-si)} e_j, so  L(B_0||...||B_{s-1}) =
    [L(B_0)..L(B_{s-1})] (flattened, 32 bits each) @ this matrix.

    This is the GF(2)-matrix form of the host fold (fold_tile_crcs):
    L(B1||B2) = A_{|B2|} L(B1) ^ L(B2), unrolled over s equal blocks."""
    out = np.zeros((s, 32, 32), dtype=np.int8)
    for si in range(s):
        nzeros = block_bytes * (s - 1 - si)
        for j in range(32):
            v = _crc.crc32c_zeros(1 << j, nzeros)
            out[si, j] = [(v >> b) & 1 for b in range(32)]
    return out.reshape(s * 32, 32)


def combine_crcs_pow2(lbits, block_bytes: int):
    """Log-depth GF(2) combine of per-block L-vectors into one L per
    shard — the device-side replacement for the host fold_tile_crcs
    loop (each launch returns ONE 32-bit L per shard; the host pays a
    single seed-advance per extent).

    lbits: (r, T, 32) int32 0/1, block t of shard r' in time order;
    block_bytes: bytes per block.  Returns (r, 32) int32 0/1 =
    L(B_0||...||B_{T-1}) per shard.

    Each level pairs adjacent equal-size blocks with ONE int8 matmul
    against crc_combine_matrix(2, bytes) — L(B1||B2) = A_{|B2|} L(B1)
    ^ L(B2) — then doubles the block size, so depth is ceil(log2 T)
    and total work is ~2T tiny (., 64)x(64, 32) MACs.  An odd level is
    evened by PREPENDING a virtual zero block: L(0^n) = 0 and
    L(0^n || B) = A_{|B|}·0 ^ L(B) = L(B), so a zero PREFIX never
    changes the combined L (a zero suffix would).  Runs as plain XLA
    (inside the launch's jit, outside the Pallas kernel: the
    (r*T, 32) -> (r, T*32) sublane-to-lane relayouts a log-depth
    combine needs do not lower in Mosaic, and at 32 bits per block the
    extra HBM round-trip is noise)."""
    import jax
    import jax.numpy as jnp
    r, t, _ = lbits.shape
    if t == 0:
        return jnp.zeros((r, 32), dtype=jnp.int32)
    lbits = lbits.astype(jnp.int8)
    bb = block_bytes
    while t > 1:
        if t % 2:
            lbits = jnp.concatenate(
                [jnp.zeros((r, 1, 32), dtype=lbits.dtype), lbits], axis=1)
            t += 1
        pairs = jnp.concatenate(
            [lbits[:, 0::2], lbits[:, 1::2]], axis=2)     # (r, t/2, 64)
        mat = jnp.asarray(crc_combine_matrix(2, bb), dtype=jnp.int8)
        prod = jax.lax.dot_general(
            pairs.reshape(r * (t // 2), 64), mat,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32) & 1
        lbits = prod.reshape(r, t // 2, 32).astype(jnp.int8)
        t //= 2
        bb *= 2
    return lbits[:, 0].astype(jnp.int32)


def combine_crcs_runs(lbits, cuts, nruns: int, block_bytes: int):
    """Combine the per-block L-vectors of a whole launch into one L
    per shard per RUN, in one program whose shape does not depend on
    where the runs lie.

    lbits: (r, T, 32) int32 0/1, block t of shard r' in stream order;
    cuts: (2, T) int32 — row 0 the number of blocks between block t
    and the end of its run (0 for a run's last block), row 1 the run
    block t belongs to (-1: no run — a run's pad blocks, the launch's
    bucket filler).  Returns (nruns, r, 32) int32 0/1.

    L(B_0||...||B_{n-1}) = XOR_t A^{(n-1-t) * block_bytes} L(B_t), so
    every block is advanced over the bytes that follow it in its run —
    level j advances by 2^j blocks where bit j of the distance is set,
    ceil(log2 T) masked (r*T, 32) x (32, 32) int8 matmuls — and the
    runs' blocks are then summed mod 2 by one matmul against the
    one-hot (nruns, T) run matrix.  The run layout is DATA here (a
    device-resident constant per layout, ops/const_cache.py), so the
    jit key is (T, nruns) — the launch's own pow2 bucket — and no
    slice, pad or transpose is dispatched per run around it."""
    import jax
    import jax.numpy as jnp
    r, t, _ = lbits.shape
    dist, run_id = cuts[0], cuts[1]
    x = lbits.astype(jnp.int8)
    for j in range((t - 1).bit_length()):
        mat = jnp.asarray(crc_advance_matrix(block_bytes << j))
        adv = jax.lax.dot_general(
            x, mat, dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.int32) & 1
        x = jnp.where((((dist >> j) & 1) == 1)[None, :, None],
                      adv.astype(jnp.int8), x)
    onehot = (run_id[None, :] ==
              jnp.arange(nruns, dtype=jnp.int32)[:, None])
    out = jax.lax.dot_general(
        onehot.astype(jnp.int8), x,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)              # (nruns, r, 32)
    return out & 1


def fold_run_crc(lbody: int, body_bytes: int, seed: int,
                 tail: bytes = b"") -> int:
    """O(1) host fold of one run: the device-combined body L plus an
    optional sub-block tail, re-seeded.  crc = A_{n}(seed) ^
    (A_{|tail|}(L_body) ^ L(tail)) — one seed-advance per extent,
    replacing the per-tile fold_tile_crcs Python loop."""
    acc = int(lbody) & 0xFFFFFFFF
    n = body_bytes
    if tail:
        acc = _crc.crc32c_zeros(acc, len(tail)) ^ _crc.crc32c(tail, 0)
        n += len(tail)
    return _crc.crc32c_zeros(seed & 0xFFFFFFFF, n) ^ acc


SCRUB_BLOCK = 2048       # bytes per L-block of the scrub rows path
SCRUB_WB = SCRUB_BLOCK // 4


def _rows_l(words, cmat_sub, wb: int):
    """(R, Wt) i32 word rows -> (R, 32) 0/1 L-bits per row: per-sub-
    block L matmuls + the log-depth device combine.  Pure jnp (no
    Pallas), so it runs on CPU XLA too — the deep-scrub verify core."""
    r, wt = words.shape
    s = wt // wb
    lsub = subblock_crc_bits_w32(words, cmat_sub, wb)     # (R*S, 32)
    return combine_crcs_pow2(lsub.reshape(r, s, 32), 4 * wb)


_rows_l_jit = None          # lazily-built jit (jax imported on demand)


def crc32c_rows_device(row_list, seeds,
                       block_bytes: int = SCRUB_BLOCK) -> list[int]:
    """crc32c of many independent byte rows in ONE device launch — the
    deep-scrub verify path (every shard of a scrub chunk hashed by one
    kernel dispatch instead of per-object host crc32c).

    Rows may have different lengths.  Each row splits into body (full
    `block_bytes` blocks) + tail; bodies are FRONT-padded with zeros to
    their power-of-two size bucket (L(0^n || B) = L(B), so prefix
    zeros are free AND the pow2 rounding bounds the jit-cache key
    space), one launch per bucket emits one L per row, and the host
    pays one seed-advance + tail fold per row (fold_run_crc).  Each
    launch's row count is padded to a power of two as well, so the jit
    key space is ~log2(rows) x log2(width)."""
    import jax
    import jax.numpy as jnp
    global _rows_l_jit
    import functools as _ft
    if _rows_l_jit is None:
        _rows_l_jit = _ft.partial(jax.jit,
                                  static_argnames=("wb",))(_rows_l)
    wb = block_bytes // 4
    rows = [np.ascontiguousarray(r, dtype=np.uint8).ravel()
            for r in row_list]
    bodies = [r.size - r.size % block_bytes for r in rows]
    ls = np.zeros(len(rows), dtype=np.uint64)
    # bucket rows by their pow2-padded width: padding every row to the
    # GLOBAL max would cost rows x max_width memory (one large object
    # in a chunk of small ones multiplies the footprint thousands of
    # times); per-bucket matrices keep the pad overhead < 2x per row
    # while still batching each size class into one launch
    buckets: dict[int, list[int]] = {}
    for i, b in enumerate(bodies):
        if b:
            nb = b // block_bytes
            buckets.setdefault(next_pow2(nb), []).append(i)
    for nb2, idxs in sorted(buckets.items()):
        w = block_bytes * nb2
        # the row COUNT is a jit axis too: pad it to a power of two
        # with zero rows (their L is never read) so a scrub of PGs
        # holding 11, 22, 33... shards compiles ~log2 programs per
        # width instead of one per distinct count
        mat = np.zeros((next_pow2(len(idxs)), w), dtype=np.uint8)
        for j, i in enumerate(idxs):
            mat[j, w - bodies[i]:] = rows[i][:bodies[i]]
        words = mat.view("<u4").view(np.int32)
        cmat_sub = jnp.asarray(crc_tile_matrix_w32(wb))
        lbits = _rows_l_jit(jnp.asarray(words), cmat_sub, wb)
        ls[idxs] = bits_to_u32(np.asarray(lbits))[:len(idxs)]
    return [fold_run_crc(int(ls[i]), bodies[i], int(seeds[i]),
                         rows[i][bodies[i]:].tobytes())
            for i in range(len(rows))]


def subblock_crc_bits_w32(words, cmat_sub, wb: int):
    """Level 1 of the hierarchical tile crc, MXU-friendly.

    words: (r, Wt) i32; cmat_sub: (32*wb, 32) from crc_tile_matrix_w32(wb).
    Returns (r*S, 32) int32 0/1: row r'*S + si = L-bits of shard r''s
    si-th wb-word sub-block.

    Why hierarchical: the flat formulation is a (r, 32*Wt) x (32*Wt, 32)
    matmul — M=r~11, N=32, huge K — a degenerate MXU shape (~2%
    utilization, measured 14-17 GB/s fused vs 159 bare encode), and its
    cmat needs 1 KiB of VMEM per tile byte, capping the fused tile at
    2 KiB.  Splitting the tile into S = Wt/wb sub-blocks makes level 1 a
    (r*S, wb) x (wb, 32) matmul per bit-plane — M grows with the tile —
    and shrinks the matrix VMEM to ~0.5 MiB regardless of tile,
    unlocking the headline kernel's 128 KiB tile.  Operands are int8
    with int32 accumulate (0/1 sums stay tiny), riding the MXU's int
    path like the parity matmul.  The tiny
    level-2 advance-combine (combine_subblock_crcs) runs OUTSIDE the
    kernel: its (r*S, 32) -> (r, S*32) sublane-to-lane reshape does not
    lower in Mosaic, and at 128 B of L-vectors per 128 KiB tile the
    extra HBM round-trip is ~0.1%."""
    import jax
    import jax.numpy as jnp
    r, wt = words.shape
    s = wt // wb
    w2 = words.reshape(r * s, wb)            # row = r'*s + si
    # 4 bit-planes per matmul, concatenated along the contraction axis
    # (cmat_sub is plane-major so the matching rows are contiguous);
    # int8 operands with int32 accumulate ride the MXU's int path like
    # the parity matmul (2x the bf16 rate; 0/1 sums stay tiny)
    acc = jnp.zeros((r * s, 32), dtype=jnp.int32)
    for g in range(8):
        cat = jnp.concatenate(
            [((w2 >> i) & 1).astype(jnp.int8)
             for i in range(4 * g, 4 * g + 4)], axis=1)   # (r*s, 4wb)
        acc = acc + jax.lax.dot_general(
            cat, cmat_sub[4 * g * wb:(4 * g + 4) * wb],
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32)
    return acc & 1


def combine_subblock_crcs(lsub, combine, r: int, s: int):
    """Level 2: fold per-sub-block L-vectors into per-tile L-vectors.

    lsub: (ntiles*r*s, 32) 0/1 i32 from subblock_crc_bits_w32 (row-major
    [tile, shard, sub-block]); combine: (s*32, 32) from
    crc_combine_matrix(s, sub_block_bytes).  Returns (ntiles, r, 32)
    0/1 i32.  Plain XLA (outside any kernel): a few MFLOP per MiB."""
    import jax
    import jax.numpy as jnp
    ntiles = lsub.shape[0] // (r * s)
    l2 = lsub.reshape(ntiles * r, s * 32).astype(jnp.bfloat16)
    out = jax.lax.dot_general(
        l2, combine.astype(jnp.bfloat16),
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.float32)
    return (out.astype(jnp.int32) & 1).reshape(ntiles, r, 32)


def bits_to_u32(bits: np.ndarray) -> np.ndarray:
    """(..., 32) 0/1 -> (...,) uint32, bit j = lsb weight 2^j."""
    weights = (1 << np.arange(32, dtype=np.uint64))
    return (bits.astype(np.uint64) @ weights).astype(np.uint32)


def fold_tile_crcs(tile_ls: np.ndarray, tile: int, seed: int,
                   tail: bytes = b"") -> int:
    """Fold per-tile L-vectors (ntiles, uint32) + optional tail bytes
    into the final crc with `seed`."""
    acc = 0
    for lv in tile_ls:
        acc = _crc.crc32c_zeros(acc, tile) ^ int(lv)
    n_bytes = len(tile_ls) * tile
    if tail:
        acc = _crc.crc32c_zeros(acc, len(tail)) ^ _crc.crc32c(tail, 0)
        n_bytes += len(tail)
    return _crc.crc32c_zeros(seed & 0xFFFFFFFF, n_bytes) ^ acc


# ----------------------------------------------------------------------------
# device-side tile CRC (jnp; callable inside the Pallas kernel too)
# ----------------------------------------------------------------------------

def tile_crc_bits_tiled(bits, cmat, tile: int):
    """Batched tile_crc_bits over EVERY tile of a launch in one rank-3
    dot per bit plane: bits (8r, ntiles*T) -> (ntiles, r, 32).  The
    per-tile Python loop this replaces unrolled O(ntiles) matmuls into
    the traced program, so XLA compile time scaled with the launch
    width — fatal once the per-host launch queue started bucketing
    cross-PG super-batches (one multi-minute compile per bucket);
    here the program size is width-independent."""
    import jax
    import jax.numpy as jnp
    r8, n = bits.shape
    r = r8 // 8
    nt = n // tile
    acc = jnp.zeros((nt, r, 32), dtype=jnp.float32)
    for i in range(8):
        plane = (bits[i * r:(i + 1) * r].astype(jnp.float32)
                 .reshape(r, nt, tile).transpose(1, 0, 2))
        acc = acc + jax.lax.dot_general(
            plane, cmat[i * tile:(i + 1) * tile].astype(jnp.float32),
            dimension_numbers=(((2,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return acc.astype(jnp.int32) & 1


def tile_crc_bits(bits, cmat):
    """bits: (8r, T) int8 bit-major rows; cmat: (8T, 32) with rows
    [i*T:(i+1)*T] = C_i^T -> (r, 32) int32 0/1 L-bit matrix for each of
    the r shards of this tile.  Rank-2 only (Mosaic-lowerable)."""
    import jax
    import jax.numpy as jnp
    r8, t = bits.shape
    r = r8 // 8
    # sum_i (r, T) @ (T, 32); f32 keeps 0/1 sums exact up to 2^24
    acc = jnp.zeros((r, 32), dtype=jnp.float32)
    for i in range(8):
        acc = acc + jax.lax.dot_general(
            bits[i * r:(i + 1) * r].astype(jnp.float32),
            cmat[i * t:(i + 1) * t].astype(jnp.float32),
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.float32)
    return acc.astype(jnp.int32) & 1
