"""Bit-sliced GF(2^8) linear algebra on TPU.

The hot loop of the whole framework.  The reference computes erasure-code
parity with per-coefficient Galois region ops (jerasure schedules /
ISA-L `ec_encode_data`, reference src/erasure-code/isa/ErasureCodeIsa.cc:129)
— a CPU-SIMD formulation.  TPU-first, the same math is one matmul:

  * multiply-by-constant in GF(2^8) is GF(2)-linear on the 8 bits, so a
    (r, k) coefficient matrix over GF(2^8) expands to an (8r, 8k) 0/1
    matrix (ceph_tpu/ec/gf.py expand_to_bitmatrix);
  * a chunk of N bytes unpacks to 8 bit-planes; stacking k chunks gives
    a (8k, N) 0/1 operand;
  * parity bits = bitmatrix @ bits mod 2 — an int8 matmul on the MXU
    with int32 accumulation (inner dim 8k <= 256 so sums stay tiny),
    followed by `& 1` and a pack on the VPU.

Layout: *bit-major interleaved*.  Row index bit*n + chunk (not
chunk*8+bit) so the in-kernel unpack `(block >> i) & 1` needs no
transpose: shifting a (k, T) byte tile by i in [0, 8) and stacking gives
exactly rows [i*k + j].  `interleave_bitmatrix` converts the math-layout
matrix from gf.expand_to_bitmatrix into this kernel layout.

Everything here is shape-static and jit-compatible; the Pallas kernel
tiles the byte axis and keeps unpack -> matmul -> pack fused in VMEM so
HBM traffic is just bytes-in + parity-out (the reason this beats an XLA
fallback, which materializes the 8x unpacked bit-planes in HBM).
"""

from __future__ import annotations

import functools
import threading

import jax
import jax.numpy as jnp
import numpy as np
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from ..common import spans
from ..common.util import next_pow2
from ..ec import gf
from . import const_cache, device
from .profiler import device_profiler

LANE = 128           # TPU lane width: byte-axis tiles must be multiples
DEFAULT_TILE = 8192  # bytes of each chunk processed per grid step


def _parallel_grid(n_dims: int, interpret: bool):
    """compiler_params marking every grid axis parallel: byte-axis grid
    steps are independent, and telling Mosaic so lets it double-buffer
    across steps (a pre-PR-1 kernel-only reading put it at up to
    ~1.7x encode on v5e vs the default sequential assumption; not
    re-measured since)."""
    if interpret:
        return {}
    return {"compiler_params": pltpu.CompilerParams(
        dimension_semantics=("parallel",) * n_dims)}


def interleave_bitmatrix(mat: np.ndarray) -> np.ndarray:
    """(r, k) GF(2^8) matrix -> (8r, 8k) 0/1 matrix in bit-major layout.

    out[i*r + ri, j*k + cj] = bit (i, j) of the 8x8 bit-matrix of
    mat[ri, cj]; i.e. rows grouped by output bit, columns by input bit.
    """
    r, k = mat.shape
    math_layout = gf.expand_to_bitmatrix(mat)          # (8r, 8k) chunk-major
    # pure index shuffle, vectorized: the CLAY repair lowering feeds
    # matrices of hundreds of rows/columns through here (81 x 272 at
    # k=8,m=3 vs the (m, k) encode matrices), where the elementwise
    # python loop costs seconds per plan build
    return np.ascontiguousarray(
        math_layout.reshape(r, 8, k, 8)
        .transpose(1, 0, 3, 2).reshape(8 * r, 8 * k))


def _unpack_bits(block: jnp.ndarray) -> jnp.ndarray:
    """(k, T) uint8 -> (8k, T) int8 bit-planes, bit-major rows.

    Strictly rank-2 (concat of shifted tiles): Mosaic on real TPUs
    cannot lower rank-3 reshapes with tiny leading dims.
    """
    # mask+compare stays in i8 end to end (4 bytes/lane-slot on the
    # VPU); i8 vector shifts don't legalize in Mosaic, and an i32
    # upcast would quadruple the elementwise work in the hot unpack
    rows = [(block & jnp.uint8(1 << i)).astype(jnp.bool_).astype(jnp.int8)
            for i in range(8)]
    return jnp.concatenate(rows, axis=0)


def _pack_bits(bits: jnp.ndarray, r: int) -> jnp.ndarray:
    """(8r, T) int32 0/1 bit-major rows -> (r, T) uint8 bytes."""
    out = bits[0:r]
    for i in range(1, 8):
        out = out + (bits[i * r:(i + 1) * r] << i)
    return out.astype(jnp.uint8)


# ----------------------------------------------------------------------------
# XLA (non-Pallas) path: correct everywhere, used on CPU and as the oracle
# ----------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("r",))
def gf_bitmatmul_xla(bitmat: jnp.ndarray, chunks: jnp.ndarray, r: int
                     ) -> jnp.ndarray:
    """Apply an interleaved (8r, 8k) bitmatrix to (k, N) uint8 chunks."""
    bits = _unpack_bits(chunks)
    prod = jax.lax.dot_general(
        bitmat.astype(jnp.int8), bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ) & 1
    return _pack_bits(prod, r)


# ----------------------------------------------------------------------------
# Pallas kernel: fused unpack -> MXU matmul -> mod2 -> pack
# ----------------------------------------------------------------------------

def _gf_kernel(bitmat_ref, in_ref, out_ref):
    r8 = bitmat_ref.shape[0]
    bits = _unpack_bits(in_ref[:])
    prod = jax.lax.dot_general(
        bitmat_ref[:], bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ) & 1
    out_ref[:] = _pack_bits(prod, r8 // 8)


@functools.partial(jax.jit, static_argnames=("r", "tile"))
def gf_bitmatmul_pallas(bitmat: jnp.ndarray, chunks: jnp.ndarray, r: int,
                        tile: int = DEFAULT_TILE) -> jnp.ndarray:
    """Pallas path of gf_bitmatmul.  chunks (k, N) with N % tile == 0."""
    k, n = chunks.shape
    assert n % tile == 0, (n, tile)
    grid = (n // tile,)
    return pl.pallas_call(
        _gf_kernel,
        grid=grid,
        in_specs=[
            pl.BlockSpec((8 * r, 8 * k), lambda t: (0, 0)),
            pl.BlockSpec((k, tile), lambda t: (0, t)),
        ],
        out_specs=pl.BlockSpec((r, tile), lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct((r, n), jnp.uint8),
        **_parallel_grid(1, False),
    )(bitmat.astype(jnp.int8), chunks)


# ----------------------------------------------------------------------------
# Word-packed Pallas kernel: 4 bytes per VPU op in the unpack/pack
# ----------------------------------------------------------------------------
#
# The plain kernel above is VPU-bound in the bit unpack (8 shift+mask
# passes over every byte).  Packing 4 bytes into an i32 word makes one
# `(w >> i) & 0x01010101` extract bit i of four bytes at once, and
# `pltpu.bitcast` reinterprets the result as byte sublanes for the MXU
# (measured ~3x on v5e).  Sublane layout of the bitcast (probed on
# hardware): i32 (r, W) <-> u8 (4r, W) with u8 row 4r+b = byte b
# (little-endian) of word row r, so the generator matrix is expanded
# block-diagonally over the byte offset b (`_w32_bitmat`).

def _w32_bitmat(mat: np.ndarray) -> np.ndarray:
    """(r, k) GF(2^8) matrix -> (32r, 32k) 0/1 matrix for the w32 kernel.

    out[i*4r + 4ri + b, j*4k + 4cj + b] = bit (i, j) of mat[ri, cj];
    zero for mismatched byte offsets b (bytes never mix positions in a
    linear code over byte streams).
    """
    r, k = mat.shape
    m8 = interleave_bitmatrix(mat)                     # (8r, 8k)
    out = np.zeros((32 * r, 32 * k), dtype=m8.dtype)
    # vectorized block-diagonal expansion (see interleave_bitmatrix on
    # why the elementwise loop can't serve the big repair matrices):
    # view as [i, ri, b_r, j, cj, b_c] and fill the b_r == b_c diagonal
    o6 = out.reshape(8, r, 4, 8, k, 4)
    m4 = m8.reshape(8, r, 8, k)
    for b in range(4):
        o6[:, :, b, :, :, b] = m4
    return out


def _words_to_bytes(x: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    """(r, W) i32 -> (4r, W) i8 with row 4r+b = byte b (little-endian)
    of word row r.  On hardware this is the free Mosaic sublane
    reinterpret (pltpu.bitcast); in interpret mode (CPU tests of the w32
    kernels — the ADVICE round-1 gap) an equivalent lax bitcast +
    transpose reproduces the same layout."""
    if not interpret:
        return pltpu.bitcast(x, jnp.int8)
    r, w = x.shape
    b = jax.lax.bitcast_convert_type(x, jnp.int8)      # (r, W, 4)
    return b.transpose(0, 2, 1).reshape(4 * r, w)


def _bytes_to_words(x: jnp.ndarray, interpret: bool) -> jnp.ndarray:
    """(4r, W) u8 -> (r, W) i32, inverse of _words_to_bytes."""
    if not interpret:
        return pltpu.bitcast(x, jnp.int32)
    r4, w = x.shape
    b = x.reshape(r4 // 4, 4, w).transpose(0, 2, 1)    # (r, W, 4)
    return jax.lax.bitcast_convert_type(b, jnp.int32)


def _w32_parity_words(bitmat, w, interpret: bool) -> jnp.ndarray:
    """Shared core of the w32 kernels: (k, W) i32 words -> (m, W) i32
    parity words via word-unpack, bitplane matmul, shift-accumulate."""
    m = bitmat.shape[0] // 32
    mask = jnp.int32(0x01010101)
    planes = [_words_to_bytes((w >> i) & mask, interpret)
              for i in range(8)]
    bits = jnp.concatenate(planes, axis=0)             # (32k, W) i8
    prod = jax.lax.dot_general(
        bitmat, bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ) & 1                                              # (32m, W)
    acc = prod[0:4 * m]
    for i in range(1, 8):
        acc = acc + (prod[i * 4 * m:(i + 1) * 4 * m] << i)
    return _bytes_to_words(acc.astype(jnp.uint8), interpret)


def _make_gf_kernel_w32(interpret: bool):
    def _gf_kernel_w32(bitmat_ref, in_ref, out_ref):
        out_ref[:] = _w32_parity_words(bitmat_ref[:], in_ref[:], interpret)
    return _gf_kernel_w32


@functools.partial(jax.jit, static_argnames=("r", "tile", "interpret"))
def gf_bitmatmul_pallas_w32(bitmat32: jnp.ndarray, words: jnp.ndarray,
                            r: int, tile: int = DEFAULT_TILE,
                            interpret: bool = False) -> jnp.ndarray:
    """Word-packed path: operates on i32 words end to end so no device
    relayout is ever paid (a host numpy `.view('<u4')` is free; an XLA
    u8<->i32 bitcast on TPU is a physical retiling copy that costs more
    than the whole encode).  words (k, W) int32 = little-endian packed
    chunk bytes, W % tile_words == 0; bitmat32 from _w32_bitmat.
    Returns (r, W) int32 parity words."""
    k, w = words.shape
    wt = tile // 4                                     # lane words per step
    assert w % wt == 0, (w, wt)
    return pl.pallas_call(
        _make_gf_kernel_w32(interpret),
        grid=(w // wt,),
        in_specs=[
            pl.BlockSpec((32 * r, 32 * k), lambda t: (0, 0)),
            pl.BlockSpec((k, wt), lambda t: (0, t)),
        ],
        out_specs=pl.BlockSpec((r, wt), lambda t: (0, t)),
        out_shape=jax.ShapeDtypeStruct((r, w), jnp.int32),
        interpret=interpret,
        **_parallel_grid(1, interpret),
    )(bitmat32.astype(jnp.int8), words)


W32_TILE = 131072  # bytes per grid step for the w32 kernel (VMEM-bound)


def _pick_wt(w: int) -> int:
    """Lane-words per grid step: divides w, multiple of LANE."""
    assert w % LANE == 0, w  # the max() clamp below relies on it
    wt = min(W32_TILE // 4, w)
    while w % wt:
        wt //= 2
    return max(wt, LANE)


def gf_bitmatmul_w32(bitmat32: jnp.ndarray, words: jnp.ndarray, r: int
                     ) -> jnp.ndarray:
    """Padding wrapper over gf_bitmatmul_pallas_w32: accepts any W,
    pads the word axis to a lane multiple (zero words make zero parity
    in a linear code), strips it after."""
    k, w = words.shape
    wpad = -w % LANE
    if wpad:
        words = jnp.pad(words, ((0, 0), (0, wpad)))
    out = _aot_dispatch("mm_w32", gf_bitmatmul_pallas_w32,
                        (bitmat32, words),
                        {"r": r, "tile": 4 * _pick_wt(w + wpad)})
    return out[:, :w] if wpad else out


FUSED_TILE = 2048  # fused parity+crc kernel tile (cmat VMEM footprint)


def _crc_rows(n_shards: int) -> int:
    """Per-tile rows of the fused kernel's flat crc output: n_shards
    sublane-padded to a multiple of 8.  Single source of truth for the
    producer (out_spec/padding in the kernel) and the consumer (the
    de-interleaving reshape in gf_encode_with_crc)."""
    return -(-n_shards // 8) * 8


def _gf_crc_kernel(bitmat_ref, cmat_ref, in_ref, par_ref, crc_ref):
    """Fused: parity tile + per-tile crc32c L-bits for every shard, one
    launch (the north-star fusion: checksum and parity from the same
    VMEM-resident bit-planes)."""
    from . import crc32c_linear as cl
    r8 = bitmat_ref.shape[0]
    m = r8 // 8
    bits = _unpack_bits(in_ref[:])                    # (8k, T)
    prod = jax.lax.dot_general(
        bitmat_ref[:], bits,
        dimension_numbers=(((1,), (0,)), ((), ())),
        preferred_element_type=jnp.int32,
    ) & 1
    par_ref[:] = _pack_bits(prod, m)
    data_crc = cl.tile_crc_bits(bits, cmat_ref[:])            # (k, 32)
    par_crc = cl.tile_crc_bits(prod.astype(jnp.int8),
                               cmat_ref[:])                   # (m, 32)
    crc = jnp.concatenate([data_crc, par_crc], axis=0)
    pad = crc_ref.shape[0] - crc.shape[0]   # sublane-align to 8 rows
    if pad:
        crc = jnp.concatenate(
            [crc, jnp.zeros((pad, 32), dtype=crc.dtype)], axis=0)
    crc_ref[:] = crc


@functools.partial(jax.jit, static_argnames=("m", "tile"))
def gf_encode_with_crc_pallas(bitmat, cmat, chunks, m: int,
                              tile: int = FUSED_TILE):
    k, n = chunks.shape
    assert n % tile == 0, (n, tile)
    grid = (n // tile,)
    rows = _crc_rows(k + m)
    return pl.pallas_call(
        _gf_crc_kernel,
        grid=grid,
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
            jax.ShapeDtypeStruct(((n // tile) * rows, 32), jnp.int32),
        ],
        **_parallel_grid(1, False),
    )(bitmat.astype(jnp.int8), cmat, chunks)


def _make_gf_crc_kernel_w32(interpret: bool):
    def _gf_crc_kernel_w32(bitmat_ref, cmat_ref, in_ref, par_ref, crc_ref):
        """w32 twin of _gf_crc_kernel: word-packed unpack feeds the MXU
        parity matmul AND the crc32c L-vector matmul from the same VMEM
        residency — the north-star fusion at the headline kernel's
        speed (the byte-path fused kernel runs ~4x slower)."""
        from . import crc32c_linear as cl
        w = in_ref[:]                                  # (k, Wt) i32
        par_words = _w32_parity_words(bitmat_ref[:], w, interpret)
        par_ref[:] = par_words
        allw = jnp.concatenate([w, par_words], axis=0)  # (k+m, Wt)
        crc = cl.tile_crc_bits_w32(allw, cmat_ref[:])   # (k+m, 32)
        pad = crc_ref.shape[0] - crc.shape[0]   # sublane-align to 8 rows
        if pad:
            crc = jnp.concatenate(
                [crc, jnp.zeros((pad, 32), dtype=crc.dtype)], axis=0)
        crc_ref[:] = crc
    return _gf_crc_kernel_w32


@functools.partial(jax.jit, static_argnames=("m", "tile", "interpret"))
def gf_encode_with_crc_pallas_w32(bitmat32, cmat32, words, m: int,
                                  tile: int = FUSED_TILE,
                                  interpret: bool = False):
    """Fused parity+crc over word-packed input.  words (k, W) i32,
    tile in BYTES (W words per grid step = tile/4); cmat32 from
    crc32c_linear.crc_tile_matrix_w32(tile//4).  Returns
    (parity (m, W) i32 words, crc L-bits (ntiles*rows, 32) i32)."""
    k, wtot = words.shape
    wt = tile // 4
    assert wtot % wt == 0, (wtot, wt)
    grid = (wtot // wt,)
    rows = _crc_rows(k + m)
    with jax.named_scope("ec.parity_crc_kernel"):
        return _fused_flat_w32_call(bitmat32, cmat32, words, m, wt,
                                    grid, rows, interpret)


def _fused_flat_w32_call(bitmat32, cmat32, words, m: int, wt: int, grid,
                         rows: int, interpret: bool):
    k, wtot = words.shape
    return pl.pallas_call(
        _make_gf_crc_kernel_w32(interpret),
        grid=grid,
        in_specs=[
            pl.BlockSpec((32 * m, 32 * k), lambda t: (0, 0)),
            pl.BlockSpec((32 * wt, 32), lambda t: (0, 0)),
            pl.BlockSpec((k, wt), lambda t: (0, t)),
        ],
        out_specs=[
            pl.BlockSpec((m, wt), lambda t: (0, t)),
            pl.BlockSpec((rows, 32), lambda t: (t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, wtot), jnp.int32),
            jax.ShapeDtypeStruct(((wtot // wt) * rows, 32), jnp.int32),
        ],
        interpret=interpret,
        **_parallel_grid(1, interpret),
    )(bitmat32.astype(jnp.int8), cmat32, words)


FUSED_WB = 512       # hier-crc sub-block, words (2 KiB); lane multiple
FUSED_TILE_HIER = W32_TILE   # hier matrices are tile-size-independent


def _hier_crc_step(bitmat_ref, cmat_sub_ref, in_ref, par_ref, wb: int,
                   interpret: bool):
    """Shared per-grid-step body of the hier fused kernels: parity +
    per-sub-block L-bits, with the crc extraction OVERLAPPED against
    the parity MXU work instead of run as a tail.

    The old kernel concatenated data and parity words before the crc
    extraction, which made even the data shards' VPU shift/mask passes
    data-dependent on the parity matmul — the whole crc half
    serialized behind the MXU.  Split per shard class, the data-shard
    extraction+matmuls depend only on the input block, so Mosaic is
    free to interleave them with the parity matmul (VPU and MXU run
    concurrently) and with the next block's HBM->VMEM DMA; only the
    parity-shard crc (m of k+m rows, ~27% of the crc work at k=8,m=3)
    still waits on the parity output.  Row order of the concatenated
    result is unchanged (shard*S + si, data shards first)."""
    from . import crc32c_linear as cl
    w = in_ref[:]                                      # (k, Wt) i32
    lsub_data = cl.subblock_crc_bits_w32(
        w, cmat_sub_ref[:], wb)                        # (k*S, 32)
    par_words = _w32_parity_words(bitmat_ref[:], w, interpret)
    par_ref[:] = par_words
    lsub_par = cl.subblock_crc_bits_w32(
        par_words, cmat_sub_ref[:], wb)                # (m*S, 32)
    return jnp.concatenate([lsub_data, lsub_par], axis=0)


def _make_gf_crc_kernel_w32_hier(interpret: bool, wb: int):
    def _kern(bitmat_ref, cmat_sub_ref, in_ref, par_ref, lsub_ref):
        """Fused parity + level-1 hierarchical crc at the headline
        kernel's tile: the same VMEM-resident words feed the MXU parity
        matmul and the sub-block crc matmuls (see
        crc32c_linear.subblock_crc_bits_w32 for why the flat crc matmul
        capped the fused tile at 2 KiB)."""
        lsub_ref[:] = _hier_crc_step(bitmat_ref, cmat_sub_ref, in_ref,
                                     par_ref, wb, interpret)
    return _kern


def _make_gf_crc_kernel_w32_hier_acc(interpret: bool, wb: int):
    """The VMEM-resident L accumulator kernel (the tentpole of the
    overlapped fused path): instead of writing every grid step's
    (r*S, 32) sub-block L-block to HBM and re-laying it out in XLA
    (combine_crcs_pow2's transpose + log-depth folds), the kernel
    folds each step's L-bits into a REVISITED output block that Mosaic
    keeps resident in VMEM for the whole run:

        acc[shard, si] <- A_tile . acc[shard, si]  ^  L(B_{t,si})

    — one (r*S, 32) x (32, 32) int8 matmul per step against the
    constant `tile`-byte advance matrix (crc_advance_matrix; advance
    powers commute, so per-si streams fold independently and the
    si-position advance is applied ONCE per run by the tiny XLA
    combine_subblock_crcs epilogue).  Each launch therefore writes one
    (r*S, 32) block per RUN, not per grid step, and the epilogue's
    input no longer scales with extent length.

    Run boundaries ride scalar prefetch: `run_map[t]` indexes the
    output block (monotonic, so Mosaic flushes an accumulator block
    exactly when its run's last step retires) and `first_map[t]` marks
    each run's first step (accumulator init).  The grid is sequential
    (no `parallel` dimension semantics — cross-step accumulation
    orders the steps), which trades the reorder freedom for the HBM
    round-trip; the autotuner's `combine` axis decides per device
    whether that trade wins."""
    def _kern(run_ref, first_ref, bitmat_ref, cmat_sub_ref, adv_ref,
              in_ref, par_ref, lacc_ref):
        t = pl.program_id(0)
        lsub = _hier_crc_step(bitmat_ref, cmat_sub_ref, in_ref,
                              par_ref, wb, interpret)

        @pl.when(first_ref[t] == 1)
        def _init():
            lacc_ref[:] = lsub

        @pl.when(first_ref[t] == 0)
        def _fold():
            adv = jax.lax.dot_general(
                lacc_ref[:].astype(jnp.int8), adv_ref[:],
                dimension_numbers=(((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32) & 1
            lacc_ref[:] = adv ^ lsub
    return _kern


def _fused_hier_call(bitmat32, cmat_sub, words, m: int, tile: int,
                     wb: int, interpret: bool):
    """Raw pallas_call of the hier fused kernel over a byte-axis grid
    with double-buffered input blocks (the `parallel` dimension
    semantics let Mosaic overlap each block's HBM->VMEM DMA with the
    previous block's MXU work — the launch is a pipeline, not one
    VMEM-resident tile).  Returns (parity (m, W) i32, lsub
    ((W*4//tile) * (k+m) * S, 32) i32 per-SUB-BLOCK L-bits, row-major
    [tile, shard, sub]) — callers choose the combine (per-tile level-2
    for the legacy contract, whole-extent log-fold for the device-side
    combine path)."""
    k, wtot = words.shape
    wt = tile // 4
    assert wtot % wt == 0, (wtot, wt)
    assert wt % wb == 0, (wt, wb)
    s = wt // wb
    r = k + m
    assert (r * s) % 8 == 0, (r, s)     # lsub out-block sublane align
    grid = (wtot // wt,)
    return pl.pallas_call(
        _make_gf_crc_kernel_w32_hier(interpret, wb),
        grid=grid,
        in_specs=[
            pl.BlockSpec((32 * m, 32 * k), lambda t: (0, 0)),
            pl.BlockSpec((32 * wb, 32), lambda t: (0, 0)),
            pl.BlockSpec((k, wt), lambda t: (0, t)),
        ],
        out_specs=[
            pl.BlockSpec((m, wt), lambda t: (0, t)),
            pl.BlockSpec((r * s, 32), lambda t: (t, 0)),
        ],
        out_shape=[
            jax.ShapeDtypeStruct((m, wtot), jnp.int32),
            jax.ShapeDtypeStruct(((wtot // wt) * r * s, 32), jnp.int32),
        ],
        interpret=interpret,
        **_parallel_grid(1, interpret),
    )(bitmat32.astype(jnp.int8), cmat_sub, words)


def _fused_hier_acc_call(bitmat32, cmat_sub, adv, run_map, first_map,
                         words, m: int, tile: int, wb: int, nruns: int,
                         interpret: bool):
    """Raw pallas_call of the accumulator hier kernel: sequential
    byte-axis grid, per-run VMEM-resident L accumulation (see
    _make_gf_crc_kernel_w32_hier_acc).  run_map/first_map are (ntiles,)
    i32 scalar-prefetch arrays (run index per grid step, monotonic;
    1 at each run's first step).  Returns (parity (m, W) i32, lacc
    (nruns * (k+m) * S, 32) i32 — ONE accumulator block per run,
    row-major [run, shard, sub])."""
    k, wtot = words.shape
    wt = tile // 4
    assert wtot % wt == 0, (wtot, wt)
    assert wt % wb == 0, (wt, wb)
    s = wt // wb
    r = k + m
    assert (r * s) % 8 == 0, (r, s)     # lacc out-block sublane align
    grid_spec = pltpu.PrefetchScalarGridSpec(
        num_scalar_prefetch=2,
        grid=(wtot // wt,),
        in_specs=[
            pl.BlockSpec((32 * m, 32 * k), lambda t, rm, fm: (0, 0)),
            pl.BlockSpec((32 * wb, 32), lambda t, rm, fm: (0, 0)),
            pl.BlockSpec((32, 32), lambda t, rm, fm: (0, 0)),
            pl.BlockSpec((k, wt), lambda t, rm, fm: (0, t)),
        ],
        out_specs=[
            pl.BlockSpec((m, wt), lambda t, rm, fm: (0, t)),
            pl.BlockSpec((r * s, 32), lambda t, rm, fm: (rm[t], 0)),
        ],
    )
    return pl.pallas_call(
        _make_gf_crc_kernel_w32_hier_acc(interpret, wb),
        grid_spec=grid_spec,
        out_shape=[
            jax.ShapeDtypeStruct((m, wtot), jnp.int32),
            jax.ShapeDtypeStruct((nruns * r * s, 32), jnp.int32),
        ],
        interpret=interpret,
    )(run_map, first_map, bitmat32.astype(jnp.int8), cmat_sub, adv,
      words)


def _crc_tile_const(tile: int, tally=None):
    """Device-resident crc_tile_matrix(tile) (byte-layout kernels)."""
    from . import crc32c_linear as cl
    return const_cache.get(("crc_tile", tile),
                           lambda: cl.crc_tile_matrix(tile), tally)


def _crc_tile_w32_const(wt: int, tally=None):
    """Device-resident crc_tile_matrix_w32(wt) (word-packed kernels:
    the flat kernel's tile matrix and the hier kernels' sub-block
    matrix are the same constant at the same word count)."""
    from . import crc32c_linear as cl
    return const_cache.get(("crc_tile_w32", wt),
                           lambda: cl.crc_tile_matrix_w32(wt), tally)


def _acc_launch_args(ntiles_run, tile: int, wb: int, tally=None):
    """Scalar-prefetch maps + fold matrices for one accumulator
    launch: run_map (run index per grid step, monotonic), first_map
    (1 at each run's first step), the per-step tile advance matrix and
    the per-run si-position combine matrix — device-resident, each
    uploaded once (ops/const_cache.py) and keyed by what it depends
    on: the maps by the run layout, the matrices by the operating
    point.  Single source of truth for the single-extent fold entry
    and the extents path — the two must never diverge on the
    accumulator contract."""
    from . import crc32c_linear as cl
    layout = tuple(int(n) for n in ntiles_run)
    counts = np.asarray(layout, dtype=np.int64)

    def build_run_map():
        return np.repeat(np.arange(len(counts), dtype=np.int32), counts)

    def build_first_map():
        first = np.zeros(int(counts.sum()), dtype=np.int32)
        starts = np.concatenate(([0], np.cumsum(counts)[:-1]))
        # zero-tile filler runs (launch-shape bucketing) have no first
        # step; their start index aliases the next run's (or falls off
        # the end) and must not set a flag
        first[starts[counts > 0]] = 1
        return first

    run_map = const_cache.get(("acc_run_map", layout), build_run_map,
                              tally)
    first_map = const_cache.get(("acc_first_map", layout),
                                build_first_map, tally)
    adv = const_cache.get(("crc_advance", tile),
                          lambda: cl.crc_advance_matrix(tile), tally)
    s = (tile // 4) // wb
    comb = const_cache.get(
        ("crc_combine", s, 4 * wb),
        lambda: cl.crc_combine_matrix(s, 4 * wb), tally)
    return run_map, first_map, adv, comb


def _hier_acc_core(bitmat32, cmat_sub, adv, combine, run_map, first_map,
                   words, m: int, tile: int, wb: int, nruns: int,
                   interpret: bool):
    """Accumulator launch + the per-run si-position fold: returns
    (parity (m, W) i32, L-bits (nruns, k+m, 32) i32 — one combined L
    per shard per run, covering every byte of the run including the
    sub-block tail).  The epilogue is ONE tiny combine_subblock_crcs
    matmul over (nruns * (k+m) * S, 32) — independent of extent
    length, vs the old per-step lsub round-trip + log-depth
    combine_crcs_pow2 chain."""
    from . import crc32c_linear as cl
    k = words.shape[0]
    s = (tile // 4) // wb
    # named scopes: the module names (kernel_families.json matches
    # those) stay; the scopes name the phases on the trace's XLA Ops
    with jax.named_scope("ec.parity_crc_kernel"):
        parity, lacc = _fused_hier_acc_call(
            bitmat32, cmat_sub, adv, run_map, first_map, words, m, tile,
            wb, nruns, interpret)
    with jax.named_scope("ec.crc_combine"):
        return parity, cl.combine_subblock_crcs(lacc, combine, k + m, s)


_hier_acc = functools.partial(jax.jit, static_argnames=(
    "m", "tile", "wb", "nruns", "interpret"))(_hier_acc_core)

# donated twin (see _fused_hier_lsub_donate): the staged drain words
# are single-use, so real accelerators may reuse their HBM for parity
_hier_acc_donate = functools.partial(jax.jit, static_argnames=(
    "m", "tile", "wb", "nruns", "interpret"),
    donate_argnums=(6,))(_hier_acc_core)


@functools.partial(jax.jit, static_argnames=("m", "tile", "wb",
                                             "interpret"))
def gf_encode_with_crc_pallas_w32_hier(bitmat32, cmat_sub, combine,
                                       words, m: int,
                                       tile: int = FUSED_TILE_HIER,
                                       wb: int = FUSED_WB,
                                       interpret: bool = False):
    """Hier-crc twin of gf_encode_with_crc_pallas_w32.  words (k, W)
    i32, tile in BYTES; cmat_sub from crc_tile_matrix_w32(wb), combine
    from crc_combine_matrix(tile//(4*wb), 4*wb).  Returns (parity (m, W)
    i32, crc L-bits (ntiles*rows, 32) i32) — same contract as the flat
    kernel, one L-row block per tile.  The kernel emits per-sub-block
    L-vectors (~0.1% of input bytes); the level-2 advance-combine runs
    as plain XLA here, inside the same jit."""
    from . import crc32c_linear as cl
    k, wtot = words.shape
    wt = tile // 4
    s = wt // wb
    r = k + m
    rows = _crc_rows(r)
    parity, lsub = _fused_hier_call(bitmat32, cmat_sub, words, m,
                                    tile, wb, interpret)
    crc = cl.combine_subblock_crcs(lsub, combine, r, s)  # (nt, r, 32)
    pad = rows - r
    if pad:
        crc = jnp.pad(crc, ((0, 0), (0, pad), (0, 0)))
    return parity, crc.reshape(-1, 32)


@functools.partial(jax.jit, static_argnames=("m", "tile", "wb",
                                             "interpret", "combine"))
def gf_encode_with_crc_w32_fold(bitmat32, cmat_sub, words, m: int,
                                tile: int = FUSED_TILE_HIER,
                                wb: int = FUSED_WB,
                                interpret: bool = False,
                                combine: str = "xla"):
    """The device-side-combine fused launch: parity AND one 32-bit
    crc32c L-vector per shard from a single dispatch.

    words (k, W) i32, W bytes a `tile` multiple; cmat_sub from
    crc_tile_matrix_w32(wb).  Returns (parity (m, W) i32, L-bits
    (k+m, 32) i32).  `combine` picks the combine depth (an axis of
    the operating-point sweep, ops/autotune.py):

      * combine="kernel": the accumulator kernel folds per-tile Ls in
        VMEM across grid steps (A_tile advance matmul per step, see
        _make_gf_crc_kernel_w32_hier_acc); the only epilogue is the
        tiny si-position fold.
      * combine="xla": the legacy shape — the kernel streams per-step
        (r*S, 32) L-blocks to HBM (parallel grid semantics) and the
        log-depth combine_crcs_pow2 runs as XLA inside this jit.

    Either way the host sees ONE L per shard and pays a single
    seed-advance per extent (fold_run_crc), never a per-tile loop."""
    from . import crc32c_linear as cl
    if combine == "kernel":
        wtot = words.shape[1]
        run_map, first_map, adv, comb = _acc_launch_args(
            [wtot // (tile // 4)], tile, wb)
        parity, lb = _hier_acc_core(
            bitmat32, cmat_sub, adv, comb, run_map, first_map, words,
            m, tile, wb, 1, interpret)
        return parity, lb[0]
    if combine != "xla":
        raise ValueError(f"unknown combine depth {combine!r}")
    parity, lb = _hier_lsub_core(bitmat32, cmat_sub, words, m,
                                 tile, wb, interpret)
    # fold the whole extent's sub-block Ls in log2(nsub) matmuls
    return parity, cl.combine_crcs_pow2(lb, 4 * wb)


@functools.partial(jax.jit, static_argnames=(
    "nruns", "block_bytes", "r_tot", "rows"))
def _combine_run(lbits, cuts, nruns: int, block_bytes: int,
                 r_tot: int = 0, rows: int = 0):
    """jit shell over combine_crcs_runs for the extents path: every
    run's full blocks fold to one L per shard in ONE program per
    launch, (nruns, k+m, 32).  `cuts` is the launch's run layout
    (_run_cuts), data and not shape, so the jit key is the launch's
    (blocks, runs) bucket.  With `rows` the L rows come as the per-tile
    kernels emit them — (ntiles * rows, 32) or (ntiles, rows, 32), the
    first `r_tot` rows of a tile real — and are re-laid to stream
    order (r, ntiles, 32) in here, not by eager dispatches around the
    call."""
    from . import crc32c_linear as cl
    if rows:
        with jax.named_scope("ec.crc_relayout"):
            lbits = jnp.transpose(
                lbits.reshape(-1, rows, 32)[:, :r_tot], (1, 0, 2))
    with jax.named_scope("ec.crc_combine"):
        return cl.combine_crcs_runs(lbits, cuts, nruns, block_bytes)


def _run_cuts(body_blocks, nblocks: int, tally=None):
    """The run layout _combine_run reads, device-resident per layout:
    (2, nblocks) i32 — per block its distance to its run's last body
    block, and its run (-1: none).  `body_blocks` is (first block,
    count) per run."""
    layout = tuple((int(b), int(n)) for b, n in body_blocks)

    def build():
        cuts = np.zeros((2, nblocks), dtype=np.int32)
        cuts[1] = -1
        for i, (boff, nb) in enumerate(layout):
            cuts[0, boff:boff + nb] = np.arange(nb - 1, -1, -1)
            cuts[1, boff:boff + nb] = i
        return cuts

    return const_cache.get(("run_cuts", layout, nblocks), build, tally)


@functools.partial(jax.jit, static_argnames=("m", "tile"))
def gf_encode_with_crc_xla(bitmat, cmat, chunks, m: int,
                           tile: int = FUSED_TILE):
    """XLA twin of the fused kernel (CPU tests / fallback)."""
    from . import crc32c_linear as cl
    k, n = chunks.shape
    ntiles = n // tile
    with jax.named_scope("ec.parity_matmul"):
        bits = _unpack_bits(chunks)                   # (8k, N)
        prod = jax.lax.dot_general(
            bitmat.astype(jnp.int8), bits,
            dimension_numbers=(((1,), (0,)), ((), ())),
            preferred_element_type=jnp.int32,
        ) & 1
        parity = _pack_bits(prod, m)
    # one batched crc contraction over every tile (program size
    # independent of ntiles — the old per-tile loop unrolled into the
    # program and made compile time scale with launch width)
    with jax.named_scope("ec.crc_extract"):
        d = cl.tile_crc_bits_tiled(bits, cmat, tile)         # (nt,k,32)
        p = cl.tile_crc_bits_tiled(prod.astype(jnp.int8), cmat,
                                   tile)                     # (nt,m,32)
        return parity, jnp.concatenate([d, p], axis=1)


def _hier_lsub_core(bitmat32, cmat_sub, words, m: int, tile: int,
                    wb: int, interpret: bool):
    """Hier launch + re-layout: (parity, per-sub-block L-bits reordered
    [tile, shard, sub] -> (k+m, total_sub_blocks, 32) stream order).
    Shared by the single-extent fold entry and the extents path."""
    k, wtot = words.shape
    wt = tile // 4
    s = wt // wb
    r = k + m
    nt = wtot // wt
    with jax.named_scope("ec.parity_crc_kernel"):
        parity, lsub = _fused_hier_call(bitmat32, cmat_sub, words, m,
                                        tile, wb, interpret)
    with jax.named_scope("ec.crc_relayout"):
        lb = lsub.reshape(nt, r, s, 32).transpose(1, 0, 2, 3) \
            .reshape(r, nt * s, 32)
    return parity, lb


_fused_hier_lsub = functools.partial(jax.jit, static_argnames=(
    "m", "tile", "wb", "interpret"))(_hier_lsub_core)

# donated twin for the dispatch-ahead pipeline: the staged device input
# words are single-use (one drain's concatenated runs), so XLA may
# reuse their HBM for the parity output instead of allocating fresh —
# only selected on real accelerators (CPU ignores donation and warns)
_fused_hier_lsub_donate = functools.partial(jax.jit, static_argnames=(
    "m", "tile", "wb", "interpret"),
    donate_argnums=(2,))(_hier_lsub_core)


def gf_encode_extents_with_crc(bitmat, bitmat32, runs, m: int,
                               use_w32: bool | None = None,
                               force_xla: bool | None = None,
                               interpret: bool = False,
                               tile: int | None = None,
                               wb: int | None = None,
                               combine: str = "xla"):
    """Multi-extent fused launch: parity + ONE device-combined crc
    L-vector per shard per run, for a whole pipeline drain in one
    kernel call (lifting the round-1 restriction that only a single-op
    drain could fuse).

    Each run (k, Wi) uint8 is zero-padded to a tile multiple and the
    padded runs concatenate along the byte axis, so every run starts
    tile-aligned.  The launch emits per-block L-vectors (hier kernel:
    2 KiB sub-blocks; flat/XLA: 2 KiB tiles); each run's full blocks
    fold ON DEVICE (combine_crcs_pow2, log-depth int8 matmuls) into a
    single L per shard, and only the sub-BLOCK tail (data rows from the
    input, parity rows from the launch output) reaches the host.  Zero
    padding is benign for parity (linear code) and the padded block's
    L-row is simply unused.

    `tile`/`wb`/`combine` override the hier kernel's operating point
    (fed by ops/autotune via the plugin); defaults keep the static
    FUSED_TILE_HIER/FUSED_WB constants with the XLA combine.

    Returns a list of (parity (m, Wi) uint8, l (k+m,) uint32 over the
    run's body, tail_bytes (k+m, tail_len) uint8, body_bytes) per run —
    fold with crc32c_linear.fold_run_crc seeded per shard: O(1) host
    combines per extent, no per-tile Python loop.  On the accumulator
    path (combine="kernel") the kernel's L covers the run's every byte,
    so tail_bytes is empty and body_bytes == Wi.
    """
    return gf_encode_extents_with_crc_finalize(
        gf_encode_extents_with_crc_submit(
            bitmat, bitmat32, runs, m, use_w32=use_w32,
            force_xla=force_xla, interpret=interpret, tile=tile,
            wb=wb, combine=combine))


# exact per-launch counts a submit handle carries for the launch
# queue's counters (a split launch carries the sums of its two halves)
_HANDLE_COUNTS = ("padded_bytes", "h2d_bytes", "h2d_const_bytes",
                  "const_hits", "const_misses", "d2h_bytes")


def gf_encode_extents_with_crc_submit(bitmat, bitmat32, runs, m: int,
                                      use_w32: bool | None = None,
                                      force_xla: bool | None = None,
                                      interpret: bool = False,
                                      tile: int | None = None,
                                      wb: int | None = None,
                                      combine: str = "xla",
                                      donate: bool | None = None):
    """Dispatch half of gf_encode_extents_with_crc: stages the drain's
    runs, launches parity + the per-run device L folds, and returns an
    opaque handle holding ONLY device arrays (futures) plus host
    metadata — no np.asarray anywhere, so the caller never blocks on
    the device.  `donate=True` (resolved to the backend: real
    accelerators only) hands the staged input words' HBM to XLA for
    reuse.  The handle records the kernel `path` that served the drain
    ("hier_acc" / "hier_lsub" / "w32_flat" / "bytes" / "xla") so bench
    and the backend can attribute a perf move to kernel vs dispatch
    changes.  Pair with gf_encode_extents_with_crc_finalize."""
    from . import crc32c_linear as cl
    if combine not in ("xla", "kernel"):
        # reject up front like the words-path twin — a malformed cache
        # entry must not silently demote to the legacy lsub path while
        # the backend still counts the drain as kernel-served
        raise ValueError(f"unknown combine depth {combine!r}")
    if force_xla is None:
        force_xla = device.on_cpu()
    if use_w32 is None:
        use_w32 = not force_xla
    if donate is None:
        donate = not device.on_cpu()
    runs = [np.ascontiguousarray(r, dtype=np.uint8) for r in runs]
    k = runs[0].shape[0]
    assert all(r.shape[0] == k for r in runs), \
        "all runs of one launch must share k (one codec per batch)"
    r_tot = k + m
    tile_hier = tile or FUSED_TILE_HIER
    wb = wb or FUSED_WB
    # Mixed-width batches (a cross-PG super-batch mixing big
    # sequential appends with small writes) must not demote EVERY run
    # off the hier kernel just because one run is under the hier tile:
    # split into a hier-eligible launch and a flat-tile launch, demuxed
    # back to the caller's run order at finalize.  Two launches instead
    # of one, but the big runs keep the headline kernel — the
    # occupancy-preserving trade continuous batching needs.
    if use_w32 and not force_xla:
        big_idx = [i for i, r in enumerate(runs)
                   if r.shape[1] >= tile_hier]
        if 0 < len(big_idx) < len(runs):
            small_idx = [i for i, r in enumerate(runs)
                         if r.shape[1] < tile_hier]
            parts = []
            for idxs in (big_idx, small_idx):
                parts.append((idxs, gf_encode_extents_with_crc_submit(
                    bitmat, bitmat32, [runs[i] for i in idxs], m,
                    use_w32=use_w32, force_xla=force_xla,
                    interpret=interpret, tile=tile, wb=wb,
                    combine=combine, donate=donate)))
            return {"split": parts, "n_runs": len(runs),
                    "path": "+".join(h["path"] for _, h in parts),
                    **{key: sum(h[key] for _, h in parts)
                       for key in _HANDLE_COUNTS}}
    # operating point: big sequential drains ride the hier-crc kernel at
    # the autotuned tile; small/mixed drains keep the flat 2 KiB tile
    # where padding waste would dominate
    tile = FUSED_TILE
    hier = False
    if use_w32 and not force_xla and \
            min(r.shape[1] for r in runs) >= tile_hier:
        tile = tile_hier
        hier = True
    acc = hier and combine == "kernel"
    meta = []           # width per run
    pads = []           # front pad per run (accumulator path only)
    padded = []
    # ec.h2d: the host-side pad/concatenate and the hand-over of the
    # staged words to the device (jnp.asarray returns once the runtime
    # holds the buffer; the DMA itself may still be in flight).  Like
    # the launch's own span, on when the device profiler is
    spans_on = device_profiler().enabled
    h2d = spans.begin("ec.h2d", spans_on, runs=len(runs))
    for r in runs:
        w = r.shape[1]
        pad = -w % tile
        meta.append(w)
        # accumulator path: pad each run at the FRONT — a zero prefix
        # is free for the crc (L(0^n || B) = L(B)), so the in-kernel
        # per-run accumulator covers the run's every byte (no host
        # tail fold at all); the legacy paths keep the back pad and
        # drop the padded tail blocks' L rows on the host instead
        pads.append(pad if acc else 0)
        if pad:
            padded.append(np.pad(r, ((0, 0), (pad, 0)) if acc
                          else ((0, 0), (0, pad))))
        else:
            padded.append(r)
    big = np.concatenate(padded, axis=1)               # (k, ntiles*tile)
    ntiles_total = big.shape[1] // tile
    # Launch-shape bucketing: continuous batching (the per-host launch
    # queue) makes every super-batch a different total width, and every
    # distinct width is a fresh XLA/Mosaic compile — seconds each,
    # paid per launch instead of once.  Zero-pad the concatenated
    # launch to the next power-of-two tile count so the jit key space
    # collapses to ~log2 shapes per path; the pad tiles sit AFTER
    # every real run (per-run demux never reaches them) and zero bytes
    # encode to zero parity, so the bucket is free for correctness.
    ntiles2 = next_pow2(ntiles_total)
    pad_tiles = ntiles2 - ntiles_total
    if pad_tiles:
        big = np.concatenate(
            [big, np.zeros((k, pad_tiles * tile), dtype=np.uint8)],
            axis=1)
        ntiles_total = ntiles2
    staged = jnp.asarray(
        big.view("<u4").view(np.int32) if use_w32 and not force_xla
        else big)
    h2d.end()
    w32_out = False
    # constants come from the device-resident cache (ops/const_cache):
    # the tally is what THIS launch uploaded of them — nothing, once
    # its operating point and run layout have been seen
    tally = const_cache.Tally()
    # ec.dispatch: the jitted call and the one run-combine program —
    # no eager device operation and no host sync anywhere inside
    dispatch = spans.begin("ec.dispatch", spans_on,
                           padded_bytes=int(big.size))
    lb_dev = None       # (pow2 runs, r, 32): ONE L array per launch
    lb_src = None       # per-block L rows for _combine_run
    rows = 0            # per-tile row count when lb_src is tile-major
    if force_xla:
        cmat = _crc_tile_const(tile, tally)
        parity_dev, lb_src = _aot_dispatch(
            "fused_xla", gf_encode_with_crc_xla,
            (bitmat, cmat, staged), {"m": m, "tile": tile})
        rows = r_tot                                   # (ntiles, r, 32)
        block_bytes = tile
        path = "xla"
    elif not use_w32:
        # byte-path Pallas kernel (TPU without the w32 layout): per-tile
        # L rows, device-combined per run below like the flat w32 path
        cmat = _crc_tile_const(tile, tally)
        parity_dev, lb_src = gf_encode_with_crc_pallas(
            bitmat, cmat, staged, m)
        rows = _crc_rows(r_tot)                     # (ntiles*rows, 32)
        block_bytes = tile
        path = "bytes"
    elif acc:
        # the overlapped accumulator kernel: one L block per RUN from
        # the launch itself — no per-step lsub round-trip, no combine
        # dispatch, no sub-block host tail
        cmat_sub = _crc_tile_w32_const(wb, tally)
        # the L out-block is keyed by run count: bucket it to a power
        # of two as well (pad tiles ride a dummy trailing run, empty
        # filler runs contribute no grid steps), so (tiles, runs) jit
        # keys stay ~log2 x log2 under cross-PG batching
        ntiles_run = [p.shape[1] // tile for p in padded]
        if pad_tiles:
            ntiles_run.append(pad_tiles)
        nruns_acc = next_pow2(len(ntiles_run))
        ntiles_run += [0] * (nruns_acc - len(ntiles_run))
        run_map, first_map, adv, comb = _acc_launch_args(
            ntiles_run, tile, wb, tally)
        acc_fn = _hier_acc_donate if donate else _hier_acc
        parity_dev, lb_dev = _aot_dispatch(
            "hier_acc_donate" if donate else "hier_acc", acc_fn,
            (bitmat32, cmat_sub, adv, comb, run_map, first_map,
             staged),
            {"m": m, "tile": tile, "wb": wb, "nruns": nruns_acc,
             "interpret": interpret})                  # (nruns, r, 32)
        block_bytes = 4 * wb
        w32_out = True
        path = "hier_acc"
    elif hier:
        cmat_sub = _crc_tile_w32_const(wb, tally)
        hier_fn = _fused_hier_lsub_donate if donate else _fused_hier_lsub
        parity_dev, lb_src = _aot_dispatch(
            "hier_lsub_donate" if donate else "hier_lsub", hier_fn,
            (bitmat32, cmat_sub, staged),
            {"m": m, "tile": tile, "wb": wb,
             "interpret": interpret})                  # (r, nsub, 32)
        block_bytes = 4 * wb
        w32_out = True
        path = "hier_lsub"
    else:
        cmat32 = _crc_tile_w32_const(tile // 4, tally)
        parity_dev, lb_src = _aot_dispatch(
            "fused_w32", gf_encode_with_crc_pallas_w32,
            (bitmat32, cmat32, staged),
            {"m": m, "interpret": interpret})
        rows = _crc_rows(r_tot)                     # (ntiles*rows, 32)
        block_bytes = tile
        w32_out = True
        path = "w32_flat"
    if lb_src is not None:
        # every run's full blocks fold to one L per shard on device,
        # all runs in ONE program dispatched NOW (still no host sync).
        # Run count bucketed to a power of two like the accumulator's,
        # so the jit key is the launch's (blocks, runs) bucket
        body_blocks = []
        coff = 0
        for w, pr in zip(meta, padded):
            body_blocks.append((coff // block_bytes, w // block_bytes))
            coff += pr.shape[1]
        if any(nb for _, nb in body_blocks):
            cuts = _run_cuts(body_blocks, big.shape[1] // block_bytes,
                             tally)
            lb_dev = _combine_run(
                lb_src, cuts, nruns=next_pow2(len(runs)),
                block_bytes=block_bytes, r_tot=r_tot, rows=rows)
    dispatch.end()
    return {"meta": meta, "padded": padded, "pads": pads,
            "parity_dev": parity_dev, "lb_dev": lb_dev,
            "block_bytes": block_bytes, "r_tot": r_tot, "m": m,
            "w32_out": w32_out, "big_width": big.shape[1],
            "path": path, "acc": acc,
            # exact counts for the launch queue's transfer counters:
            # a constant counts only when this launch uploaded it
            "padded_bytes": int(big.size),
            "h2d_bytes": int(big.size) + tally.nbytes,
            "h2d_const_bytes": tally.nbytes,
            "const_hits": tally.hits, "const_misses": tally.misses,
            "d2h_bytes": int(parity_dev.nbytes)
            + (int(lb_dev.nbytes) if lb_dev is not None else 0)}


def gf_encode_extents_with_crc_finalize(handle):
    """Completion half: blocks on the device results of one submit
    handle and materializes the per-run
    (parity, l, tail_bytes, body_bytes) tuples (the contract of
    gf_encode_extents_with_crc).  Accumulator-path handles
    (path "hier_acc") carry per-run Ls covering EVERY run byte, so
    body == run width and tail_bytes is empty — the host pays one
    seed-advance per extent and never touches a byte."""
    from . import crc32c_linear as cl
    if "split" in handle:
        # mixed-width batch: finalize both sub-launches and restore
        # the caller's run order
        out = [None] * handle["n_runs"]
        for idxs, sub in handle["split"]:
            for i, res in zip(idxs,
                              gf_encode_extents_with_crc_finalize(sub)):
                out[i] = res
        return out
    meta, padded = handle["meta"], handle["padded"]
    pads = handle.get("pads") or [0] * len(meta)
    r_tot = handle["r_tot"]
    block_bytes = handle["block_bytes"]
    acc = handle.get("acc", False)
    # ec.d2h_wait: blocks until the device is done, then copies to the
    # host — named for both, the host clock cannot part them.  Two
    # fetches a launch at most: the parity and the ONE L array of all
    # its runs, cut apart below in numpy
    with spans.span("ec.d2h_wait", device_profiler().enabled):
        parity_big = np.asarray(handle["parity_dev"])
        lb_dev = handle["lb_dev"]
        lb_host = None if lb_dev is None else np.asarray(lb_dev)
    if handle["w32_out"]:
        parity_big = parity_big.view("<u4").view(np.uint8) \
            .reshape(handle["m"], handle["big_width"])
    # (runs, k+m) u32; a launch none of whose runs holds a full block
    # dispatched no combine and every body L is 0 (the rows past the
    # real runs are the pow2 bucket's filler)
    l_all = cl.bits_to_u32(lb_host) if lb_host is not None \
        else np.zeros((len(meta), r_tot), dtype=np.uint32)
    out = []
    coff = 0
    for i, (w, pr, pad) in enumerate(zip(meta, padded, pads)):
        par = parity_big[:, coff + pad:coff + pad + w]
        if acc:
            body = w                     # kernel L covers the full run
        else:
            nb = w // block_bytes        # full blocks = run body
            body = nb * block_bytes
        tail_data = pr[:, pad + body:pad + w]
        tail_par = par[:, body:w]
        tail_bytes = np.concatenate([tail_data, tail_par], axis=0) \
            if w > body else np.zeros((r_tot, 0), dtype=np.uint8)
        out.append((par, l_all[i], tail_bytes, body))
        coff += pr.shape[1]
    return out


def _pick_tile(n: int) -> int:
    assert n % LANE == 0, n  # the max() clamp below relies on it
    tile = min(DEFAULT_TILE, n)
    while n % tile:
        tile //= 2
    return max(tile, LANE)


def gf_bitmatmul(bitmat: jnp.ndarray, chunks: jnp.ndarray, r: int,
                 force_xla: bool | None = None) -> jnp.ndarray:
    """Dispatch: Pallas on TPU, XLA elsewhere.  Pads N up to a lane/tile
    multiple and strips the pad (zero bytes encode to zero parity, so
    padding is benign for linear codes)."""
    k, n = chunks.shape
    use_xla = force_xla if force_xla is not None else device.on_cpu()
    npad = -n % LANE
    if npad:
        chunks = jnp.pad(chunks, ((0, 0), (0, npad)))
    if use_xla:
        out = _aot_dispatch("mm_xla", gf_bitmatmul_xla,
                            (bitmat, chunks), {"r": r})
    else:
        out = gf_bitmatmul_pallas(bitmat, chunks, r,
                                  tile=_pick_tile(n + npad))
    return out[:, :n] if npad else out


# ----------------------------------------------------------------------------
# AOT lowering: headline kernels compiled ahead of time
# ----------------------------------------------------------------------------
# The compile-stall fix's third leg (with the persistent compile cache
# and the boot-time prewarm plan): the headline entry points — the
# fused hier-acc encode+crc point, the plain/flat encode, and the flat
# decode — get jax.jit(...).lower().compile() executables built BEFORE
# any data exists, keyed by (entry name, input avals, static args).
# The dispatch sites below consult this registry first, so a
# steady-state launch of an AOT-covered shape calls the compiled
# executable directly and never touches jit dispatch (no trace-time,
# ever); uncovered shapes fall through to the jitted path unchanged.
# With the persistent cache enabled, an AOT lower+compile also lands
# the executable on disk — a restarted daemon's aot_compile() of the
# same shape is a cache read, not a compile.

_AOT_LOCK = threading.Lock()
_AOT: dict[tuple, object] = {}
_AOT_STATS = {"compiles": 0, "calls": 0, "errors": 0, "compile_s": 0.0}


def _aot_key(name: str, args, statics: dict) -> tuple:
    return (name,
            tuple((tuple(a.shape), str(np.dtype(a.dtype)))
                  for a in args),
            tuple(sorted(statics.items())))


def aot_compile(name: str, jitted, args, statics: dict) -> bool:
    """Lower+compile one jitted entry at the given arg shapes (arrays
    or ShapeDtypeStructs — only shape/dtype are read) and register the
    executable under (name, avals, statics).  Idempotent; returns
    whether the executable is (now) registered.  Failures degrade to
    the jitted path and are counted, never raised — AOT is an
    optimization, not a correctness dependency."""
    key = _aot_key(name, args, statics)
    with _AOT_LOCK:
        if key in _AOT:
            return True
    import time as _time
    avals = tuple(jax.ShapeDtypeStruct(tuple(a.shape),
                                       np.dtype(a.dtype))
                  for a in args)
    t0 = _time.perf_counter()
    try:
        exe = jitted.lower(*avals, **statics).compile()
    except Exception:  # noqa: BLE001 — unsupported backend/shape
        _AOT_STATS["errors"] += 1
        return False
    with _AOT_LOCK:
        _AOT.setdefault(key, exe)
        _AOT_STATS["compiles"] += 1
        _AOT_STATS["compile_s"] += _time.perf_counter() - t0
    return True


def _aot_dispatch(name: str, jitted, args, statics: dict):
    """Call the AOT executable registered for (name, arg shapes,
    statics) when one exists, else the jitted path.  A call-time
    mismatch (dtype drift, backend change) drops the stale executable
    and falls back — one failed call, never a wedged path."""
    exe = _AOT.get(_aot_key(name, args, statics))
    if exe is not None:
        try:
            out = exe(*args)
            _AOT_STATS["calls"] += 1
            return out
        except Exception:  # noqa: BLE001 — stale/mismatched executable
            _AOT_STATS["errors"] += 1
            with _AOT_LOCK:
                _AOT.pop(_aot_key(name, args, statics), None)
    return jitted(*args, **statics)


def aot_stats() -> dict:
    with _AOT_LOCK:
        out = dict(_AOT_STATS)
        out["executables"] = len(_AOT)
    return out


def aot_reset_for_tests() -> None:
    with _AOT_LOCK:
        _AOT.clear()
        _AOT_STATS.update(
            {"compiles": 0, "calls": 0, "errors": 0, "compile_s": 0.0})
