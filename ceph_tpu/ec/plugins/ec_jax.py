"""TPU erasure-code plugin ("jax"): bit-sliced GF(2^8) RS on the MXU.

The north-star codec (BASELINE.json): fills the same registry seam as the
reference's jerasure/ISA-L plugins but executes encode/decode as Pallas
bit-matrix matmuls (ceph_tpu/ops/bitsliced.py).  Parity is bit-identical
to the CPU plugins because both sides use the same generator matrices
(ceph_tpu/ec/gf.py) — the TPU path just evaluates them over GF(2)
bit-planes instead of GF(2^8) byte LUTs.

Techniques: `cauchy` (default; reference cauchy_good analog) and
`reed_sol_van` (matches ec_jerasure/ec_isa output bytes exactly).

Decode: the (survivors -> erased) coefficient matrix is computed on host
(tiny Gauss-Jordan, LRU-cached by erasure signature like the reference's
ISA-L table cache) and applied with the same TPU kernel.

Batching: `encode_stripes` folds a whole batch of stripes into one kernel
launch — the hook the OSD write pipeline uses to amortize launch latency
across in-flight transactions (reference analog: the per-stripe loop in
ECUtil::encode, src/osd/ECUtil.cc:120-150, hoisted into one call).
"""

from __future__ import annotations

import errno
import functools
import sys
import threading

import numpy as np

# the w32 host path reinterprets byte buffers as little-endian words
# (`.view('<u4').view(np.int32)`); on a big-endian host the int32 view
# would silently byte-swap relative to the kernel's layout, producing
# wrong parity rather than an error — fail loudly instead (ADVICE r1)
assert sys.byteorder == "little", \
    "ec_jax w32 paths assume a little-endian host"

from .. import gf
from ..base import ErasureCode
from ..interface import ErasureCodeError, Profile
from ..registry import ErasureCodePlugin, ErasureCodePluginRegistry

__erasure_code_version__ = ErasureCodePlugin.abi_version

_jax_state = threading.local()


def _ops():
    """Import jax lazily so merely loading the plugin registry never pulls
    in a TPU runtime (mirrors plugin dlopen being side-effect-light)."""
    import jax  # noqa: F401
    from ... import ops  # noqa: F401
    from ...ops import bitsliced
    return bitsliced


class ErasureCodeJax(ErasureCode):
    technique = "cauchy"
    # the codec's name on its launches' span rows (lq.*, ec.h2d,
    # ec.d2h_wait) and at the head of its coalescing key
    codec_tag = "jax"

    def __init__(self, technique: str = "cauchy"):
        super().__init__()
        self.technique = technique
        self.matrix: np.ndarray | None = None
        self._codec_sig: tuple | None = None
        self._enc_bitmat = None           # device array, interleaved layout
        self._decode_cache: dict[tuple, tuple] = {}
        self._lock = threading.Lock()

    # -- setup --------------------------------------------------------------

    def init(self, profile: Profile) -> None:
        self.k = profile.to_int("k", 8)
        self.m = profile.to_int("m", 3)
        if self.k < 1 or self.m < 1 or self.k + self.m > gf.GF_SIZE:
            raise ErasureCodeError(errno.EINVAL, f"bad k={self.k} m={self.m}")
        if self.technique == "reed_sol_van":
            matrix = gf.vandermonde_rs_matrix(self.k, self.m)
        else:
            matrix = gf.cauchy_rs_matrix(self.k, self.m)
        self._set_matrix(matrix)
        super().init(profile)

    @classmethod
    def from_matrix(cls, matrix: np.ndarray, codec_tag: str
                    ) -> "ErasureCodeJax":
        """A codec of the systematic (k+m, k) generator `matrix`, for a
        plugin whose code is linear but not named by a technique (an
        LRC whose layers compose to one matrix): the same kernels,
        launch paths and AOT hooks as a profile-built codec."""
        codec = cls(technique="matrix")
        codec.codec_tag = codec_tag
        codec.k = matrix.shape[1]
        codec.m = matrix.shape[0] - codec.k
        codec._set_matrix(np.ascontiguousarray(matrix, dtype=np.uint8))
        return codec

    def _set_matrix(self, matrix: np.ndarray) -> None:
        """The generator and its device-resident bitmatrices."""
        self.matrix = matrix
        bs = _ops()
        import jax.numpy as jnp
        from ...ops import device
        self._enc_bitmat = jnp.asarray(
            bs.interleave_bitmatrix(self.matrix[self.k:]), dtype=jnp.int8)
        # word-packed variant: ~4x the byte kernel on TPU (bit unpack
        # touches 4 bytes per VPU op); byte path retained for CPU/XLA
        self._use_w32 = not device.on_cpu()
        self._enc_bitmat32 = jnp.asarray(
            bs._w32_bitmat(self.matrix[self.k:]), dtype=jnp.int8) \
            if self._use_w32 else None
        self._fused_point: dict | None = None   # lazy, ops/autotune

    def get_alignment(self) -> int:
        return 64

    # flight-recorder hint (ops/profiler.py): encode/decode run jitted
    # XLA programs, so a first-seen launch shape IS a compile
    jit_backed = True

    def codec_signature(self) -> tuple:
        """Coalescing key for the per-host launch queue
        (parallel/launch_queue.py): two instances with equal
        signatures produce bit-identical parity via the same launch
        paths, so their runs may share one cross-PG super-batch.
        Plugin-typed on purpose — a jax instance never co-batches
        with a CPU plugin even when the matrices match, because the
        super-batch launches through the FIRST submitter's plugin and
        the capability sets (submit/finalize halves, device layout)
        must be uniform within a launch."""
        if self._codec_sig is None:
            from ...parallel.launch_queue import matrix_signature
            self._codec_sig = (self.codec_tag,) + matrix_signature(
                self.matrix, self.k, self.m)
        return self._codec_sig

    # -- encode -------------------------------------------------------------

    def encode_chunks(self, chunks: np.ndarray) -> np.ndarray:
        return self._apply_bitmat(self._enc_bitmat32 if self._use_w32
                                  else self._enc_bitmat, chunks, self.m)

    def _apply_bitmat(self, bitmat, chunks: np.ndarray, r: int) -> np.ndarray:
        """Host-side single point of byte-vs-w32 dispatch: `bitmat` must
        be in the format matching self._use_w32 (_w32_bitmat vs
        interleave_bitmatrix layout — both builders and this dispatch
        flip together on the backend probe in init)."""
        bs = _ops()
        chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
        if not self._use_w32:
            return np.asarray(bs.gf_bitmatmul(bitmat, chunks, r))
        # word-packed TPU path; host-side views are free (row-major)
        k, n = chunks.shape
        pad = -n % 4
        if pad:
            chunks = np.pad(chunks, ((0, 0), (0, pad)))
        words = chunks.view("<u4").view(np.int32)
        out = np.asarray(bs.gf_bitmatmul_w32(bitmat, words, r))
        out = out.view("<u4").view(np.uint8).reshape(r, n + pad)
        return out[:, :n] if pad else out

    def encode_chunks_device(self, chunks):
        """Device-resident encode: chunks (k, N) jnp uint8 -> (m, N).
        No host transfer; for the OSD pipeline and benchmarks."""
        bs = _ops()
        return bs.gf_bitmatmul(self._enc_bitmat, chunks, self.m)

    def encode_words(self, words):
        """Word-packed device-resident encode: (k, W) int32 words
        (little-endian packed chunk bytes) -> (m, W) int32 parity.
        The fastest TPU path — no byte<->word relayout on device."""
        bs = _ops()
        if not self._use_w32:
            raise RuntimeError(
                "encode_words requires a TPU backend (the w32 kernel "
                "uses Mosaic bitcasts); use encode_chunks_device on CPU")
        return bs.gf_bitmatmul_w32(self._enc_bitmat32, words, self.m)

    def fused_point(self) -> dict:
        """The fused kernel's (tile, wb, combine) operating point for
        this device and geometry plus its `source` — the committed
        ops/fused_points.json entry or the static default
        (ops/autotune.fused_operating_point; never a sweep)."""
        if self._fused_point is None:
            from ...ops import autotune
            self._fused_point = autotune.fused_operating_point(
                self.k, self.m)
        return self._fused_point

    def encode_words_with_crc(self, words, tile: int | None = None,
                              wb: int | None = None):
        """Device-resident fused parity + crc over word-packed input at
        the device's operating point (the overlapped hier-crc kernel
        with the device-side combine — in-kernel VMEM accumulator or
        XLA log-fold per the point's `combine` axis; see
        ops/bitsliced.gf_encode_with_crc_w32_fold).  words (k, W)
        int32; W bytes per shard must be a tile multiple.  Returns
        (parity (m, W) int32, crc L-bits (k+m, 32) int32 — ONE
        combined L per shard, fold with crc32c_linear.fold_run_crc) —
        the write path's checksum-and-parity-in-one-launch (reference
        analog: plugin encode + ECUtil.cc:172 HashInfo append, two
        separate passes there)."""
        bs = _ops()
        if not self._use_w32:
            raise RuntimeError(
                "encode_words_with_crc requires a TPU backend")
        point = self.fused_point()
        tile = tile or point["tile"]
        wb = wb or point["wb"]
        cmat_sub = bs._crc_tile_w32_const(wb)
        return bs.gf_encode_with_crc_w32_fold(
            self._enc_bitmat32, cmat_sub, words, self.m,
            tile=tile, wb=wb, combine=point["combine"])

    def encode_stripes(self, stripes):
        """Batched encode: (B, k, C) -> (B, m, C), one kernel launch.

        Internally reorders to (k, B*C) so every stripe's chunk j lands in
        the same row — the batch rides the byte axis the kernel already
        tiles.
        """
        import jax.numpy as jnp
        bs = _ops()
        stripes = jnp.asarray(stripes, dtype=jnp.uint8)
        b, k, c = stripes.shape
        assert k == self.k
        flat = jnp.transpose(stripes, (1, 0, 2)).reshape(k, b * c)
        par = bs.gf_bitmatmul(self._enc_bitmat, flat, self.m)
        return jnp.transpose(par.reshape(self.m, b, c), (1, 0, 2))

    def encode_extents_with_crc(self, runs: list[np.ndarray]):
        """Multi-extent fused launch: every run of a pipeline drain gets
        parity + ONE device-combined crc L per shard from ONE kernel
        call (w32 on TPU — the headline kernel, not the 4x-slower byte
        variant), at the autotuned operating point.

        Returns per-run (parity (m, Wi), l (k+m,) uint32, tail_bytes,
        body_bytes); fold each with fold_extent_crcs, chaining seeds
        per object.
        """
        from ...ops import bitsliced as bs
        point = self.fused_point() if self._use_w32 else None
        return bs.gf_encode_extents_with_crc(
            self._enc_bitmat, self._enc_bitmat32, runs, self.m,
            use_w32=self._use_w32,
            tile=point["tile"] if point else None,
            wb=point["wb"] if point else None,
            combine=point["combine"] if point else "xla")

    def encode_extents_with_crc_submit(self, runs: list[np.ndarray]):
        """Dispatch half of encode_extents_with_crc for the OSD's
        dispatch-ahead pipeline: launches the drain's fused parity+crc
        work and returns an opaque handle of device futures — the
        caller does NOT block on the device.  Materialize with
        encode_extents_with_crc_finalize (the pipeline's completion
        stage), in submit order."""
        from ...ops import bitsliced as bs
        point = self.fused_point() if self._use_w32 else None
        return bs.gf_encode_extents_with_crc_submit(
            self._enc_bitmat, self._enc_bitmat32, runs, self.m,
            use_w32=self._use_w32,
            tile=point["tile"] if point else None,
            wb=point["wb"] if point else None,
            combine=point["combine"] if point else "xla",
            codec=self.codec_tag)

    def launch_bucket(self, handle) -> str:
        """Flight-recorder jit-bucket key of one submit handle
        (ops/profiler.py): the axes XLA/Mosaic actually key their
        caches on — kernel path, the (tile, wb, combine) operating
        point, and the pow2-padded (width, run-count) launch shape —
        so the compile ledger's first-seen detection matches real
        compiles instead of guessing from raw widths."""
        from ...parallel.launch_queue import _extents_bucket
        base = _extents_bucket(handle)
        point = self._fused_point
        if point and self._use_w32:
            return (f"{base}:t{point['tile']}:wb{point['wb']}"
                    f":{point['combine']}")
        return base

    def encode_extents_with_crc_finalize(self, handle):
        """Completion half: blocks on one submit handle's device work
        and returns the per-run (parity, l, tail, body_bytes) tuples."""
        from ...ops import bitsliced as bs
        return bs.gf_encode_extents_with_crc_finalize(handle)

    def encode_chunks_submit(self, chunks: np.ndarray):
        """Plain-parity dispatch half (no crc): launch the encode of
        (k, N) uint8 chunks and return a handle without syncing — the
        pipeline's path for non-append (overwrite) extents whose
        incremental crc is dead anyway."""
        import jax.numpy as jnp
        from ...common.spans import span
        from ...ops.profiler import device_profiler
        bs = _ops()
        chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
        k, n = chunks.shape
        # the same rows as the fused launch's: staging under `ec.h2d`,
        # the jitted call under `ec.dispatch`
        on = device_profiler().enabled
        tag = self.codec_tag
        if not self._use_w32:
            with span("ec.h2d", on, codec=tag):
                staged = jnp.asarray(chunks)
            with span("ec.dispatch", on, codec=tag):
                return ("bytes", n,
                        bs.gf_bitmatmul(self._enc_bitmat, staged,
                                        self.m))
        with span("ec.h2d", on, codec=tag):
            pad = -n % 4
            if pad:
                chunks = np.pad(chunks, ((0, 0), (0, pad)))
            words = jnp.asarray(chunks.view("<u4").view(np.int32))
        with span("ec.dispatch", on, codec=tag):
            return ("w32", n,
                    bs.gf_bitmatmul_w32(self._enc_bitmat32, words,
                                        self.m))

    def encode_chunks_finalize(self, handle) -> np.ndarray:
        from ...common.spans import span
        from ...ops.profiler import device_profiler
        kind, n, dev = handle
        with span("ec.d2h_wait", device_profiler().enabled,
                  codec=self.codec_tag):
            out = np.asarray(dev)
        if kind == "w32":
            out = out.view("<u4").view(np.uint8).reshape(self.m, -1)
        return out[:, :n] if out.shape[1] != n else out

    def fold_extent_crcs(self, l, tail_bytes, seeds: list[int],
                         body_bytes: int) -> list[int]:
        """Host fold of one run's device-combined L-vectors into
        cumulative shard crcs with per-shard seeds (the hinfo chain):
        O(1) combines per shard — one seed-advance plus the sub-block
        tail — no per-tile Python loop."""
        from ...ops import crc32c_linear as cl
        return [cl.fold_run_crc(int(l[s]), body_bytes, seeds[s],
                                tail_bytes[s].tobytes())
                for s in range(self.k + self.m)]

    def encode_chunks_with_crc(self, chunks: np.ndarray,
                               seeds: list[int] | None = None
                               ) -> tuple[np.ndarray, list[int]]:
        """The fused north-star launch: parity AND per-shard crc32c from
        one kernel call (BASELINE.json; reference analog computes them
        separately: plugin encode_chunks + HashInfo::append crc loop,
        src/osd/ECUtil.cc:172).

        Returns (parity (m, N), crcs for all k+m shards seeded `seeds`
        (default 0xFFFFFFFF each, the HashInfo convention)).
        """
        chunks = np.ascontiguousarray(chunks, dtype=np.uint8)
        if seeds is None:
            seeds = [0xFFFFFFFF] * (self.k + self.m)
        [(parity, l, tail_bytes, body_bytes)] = \
            self.encode_extents_with_crc([chunks])
        crcs = self.fold_extent_crcs(l, tail_bytes, seeds, body_bytes)
        return np.asarray(parity), crcs

    # -- AOT lowering (boot-time prewarm, ops/prewarm.py) -------------------
    #
    # The headline kernels get jax.jit(...).lower().compile() paths so a
    # steady-state launch of a prewarmed shape dispatches the compiled
    # executable directly — no trace-time, ever (the jitted path still
    # retraces on the first call per process even when the persistent
    # cache serves the compile).  Shapes here MUST mirror the dispatch
    # sites in ops/bitsliced.py exactly (same pow2/lane padding), which
    # is why each method reproduces the corresponding wrapper's padding
    # arithmetic rather than guessing.  All three are best-effort: a
    # backend that can't lower the shape returns False and the jitted
    # path serves it.

    def _aot_spec(self, shape, dtype):
        import jax
        return jax.ShapeDtypeStruct(tuple(shape), np.dtype(dtype))

    def aot_compile_encode(self, width: int) -> bool:
        """AOT-lower the plain (no-crc) encode at byte width `width` —
        the gf_bitmatmul / gf_bitmatmul_w32 dispatch shapes."""
        bs = _ops()
        if not self._use_w32:
            w = width + (-width % bs.LANE)
            return bs.aot_compile(
                "mm_xla", bs.gf_bitmatmul_xla,
                (self._enc_bitmat, self._aot_spec((self.k, w), np.uint8)),
                {"r": self.m})
        w = (width + (-width % 4)) // 4            # packed word count
        wlane = w + (-w % bs.LANE)
        return bs.aot_compile(
            "mm_w32", bs.gf_bitmatmul_pallas_w32,
            (self._enc_bitmat32,
             self._aot_spec((self.k, wlane), np.int32)),
            {"r": self.m, "tile": 4 * bs._pick_wt(wlane)})

    def aot_compile_decode(self, width: int, n_erased: int = 1) -> bool:
        """AOT-lower the flat decode at byte width `width` for
        `n_erased` lost shards.  The executable is keyed by the decode
        bitmatrix SHAPE, which depends only on n_erased — one AOT
        compile covers every erasure pattern of that cardinality."""
        bs = _ops()
        n = self.get_chunk_count()
        e = max(1, min(n_erased, self.m))
        # representative pattern: last e shards lost (shape-equivalent
        # to any other pattern of e losses)
        erased = tuple(range(n - e, n))
        survivors = tuple(i for i in range(n) if i not in erased)[:self.k]
        _, bitmat = self._decode_plan(survivors, erased)
        if not self._use_w32:
            w = width + (-width % bs.LANE)
            return bs.aot_compile(
                "mm_xla", bs.gf_bitmatmul_xla,
                (bitmat, self._aot_spec((self.k, w), np.uint8)),
                {"r": e})
        w = (width + (-width % 4)) // 4
        wlane = w + (-w % bs.LANE)
        return bs.aot_compile(
            "mm_w32", bs.gf_bitmatmul_pallas_w32,
            (bitmat, self._aot_spec((self.k, wlane), np.int32)),
            {"r": e, "tile": 4 * bs._pick_wt(wlane)})

    def aot_compile_fused(self, widths: list[int]) -> bool:
        """AOT-lower the fused parity+crc launch for a drain whose runs
        have the given byte widths, at this codec's operating point —
        the gf_encode_extents_with_crc_submit dispatch shapes (tile
        padding, pow2 tile-count bucketing, pow2 run-count bucketing
        all reproduced)."""
        bs = _ops()
        from ...common.util import next_pow2
        k, m = self.k, self.m
        if not self._use_w32:                      # CPU: force_xla path
            tile = bs.FUSED_TILE
            nt = next_pow2(sum(-(-w // tile) for w in widths))
            cmat = bs._crc_tile_const(tile)
            return bs.aot_compile(
                "fused_xla", bs.gf_encode_with_crc_xla,
                (self._enc_bitmat, cmat,
                 self._aot_spec((k, nt * tile), np.uint8)),
                {"m": m, "tile": tile})
        # an accelerator (use_w32): the submit half donates its staged
        # words there, so the donated twins are what it dispatches
        point = self.fused_point()
        tile_hier, wb = point["tile"], point["wb"]
        hier = min(widths) >= tile_hier
        tile = tile_hier if hier else bs.FUSED_TILE
        ntiles_run = [-(-w // tile) for w in widths]
        ntiles_total = sum(ntiles_run)
        nt2 = next_pow2(ntiles_total)
        pad_tiles = nt2 - ntiles_total
        words = self._aot_spec((k, nt2 * tile // 4), np.int32)
        cmat_sub = bs._crc_tile_w32_const(wb)
        if hier and point["combine"] == "kernel":
            if pad_tiles:
                ntiles_run = ntiles_run + [pad_tiles]
            nruns_acc = next_pow2(len(ntiles_run))
            ntiles_run += [0] * (nruns_acc - len(ntiles_run))
            run_map, first_map, adv, comb = bs._acc_launch_args(
                ntiles_run, tile, wb)
            return bs.aot_compile(
                "hier_acc_donate", bs._hier_acc_donate,
                (self._enc_bitmat32, cmat_sub, adv, comb, run_map,
                 first_map, words),
                {"m": m, "tile": tile, "wb": wb, "nruns": nruns_acc,
                 "interpret": False})
        if hier:
            return bs.aot_compile(
                "hier_lsub_donate", bs._fused_hier_lsub_donate,
                (self._enc_bitmat32, cmat_sub, words),
                {"m": m, "tile": tile, "wb": wb, "interpret": False})
        cmat32 = bs._crc_tile_w32_const(tile // 4)
        return bs.aot_compile(
            "fused_w32", bs.gf_encode_with_crc_pallas_w32,
            (self._enc_bitmat32, cmat32, words),
            {"m": m, "interpret": False})

    # -- decode -------------------------------------------------------------

    def _decode_plan(self, survivors: tuple[int, ...],
                     targets: tuple[int, ...]):
        """Host-side: (survivors -> targets) GF matrix + device bitmatrix,
        cached by signature (reference ErasureCodeIsaTableCache role)."""
        key = (survivors, targets)
        with self._lock:
            hit = self._decode_cache.get(key)
        if hit is not None:
            return hit
        import jax.numpy as jnp
        bs = _ops()
        coeff = gf.recovery_matrix(self.matrix, self.k, survivors, targets)
        if self._use_w32:
            bitmat = jnp.asarray(bs._w32_bitmat(coeff), dtype=jnp.int8)
        else:
            bitmat = jnp.asarray(bs.interleave_bitmatrix(coeff),
                                 dtype=jnp.int8)
        plan = (coeff, bitmat)
        with self._lock:
            self._decode_cache[key] = plan
        return plan

    def decode_words(self, words, survivors, targets):
        """Device-resident word-packed decode: `words` is the survivors'
        packed chunk bytes (len(survivors)=k, W) int32; returns the
        reconstructed `targets` shards (len(targets), W) int32.  Same
        kernel as encode_words with the inverted bitmatrix — the repair
        hot loop (reference ECUtil::decode, src/osd/ECUtil.cc:9)."""
        bs = _ops()
        if not self._use_w32:
            raise RuntimeError("decode_words requires a TPU backend; "
                               "use decode_chunks on CPU")
        _, bitmat = self._decode_plan(tuple(survivors), tuple(targets))
        return bs.gf_bitmatmul_w32(bitmat, words, len(targets))

    def decode_chunks_device(self, chunks, survivors, targets):
        """Device-resident byte-path decode (CPU/XLA twin of
        decode_words): `chunks` (k, N) survivor rows in `survivors`
        order -> reconstructed (len(targets), N).  Public entry for
        benchmarks/pipelines holding device arrays."""
        bs = _ops()
        if self._use_w32:
            raise RuntimeError("backend is w32 (TPU): use decode_words")
        _, bitmat = self._decode_plan(tuple(survivors), tuple(targets))
        return bs.gf_bitmatmul(bitmat, chunks, len(tuple(targets)))

    def decode_chunks(self, dense: np.ndarray, erasures) -> np.ndarray:
        n = self.get_chunk_count()
        erased = tuple(sorted(set(erasures)))
        survivors = tuple(i for i in range(n) if i not in set(erased))[: self.k]
        if len(survivors) < self.k:
            raise ErasureCodeError(errno.EIO, "not enough survivors")
        _, bitmat = self._decode_plan(survivors, erased)
        rec = self._apply_bitmat(bitmat, dense[list(survivors)], len(erased))
        out = dense.copy()
        for idx, e in enumerate(erased):
            out[e] = rec[idx]
        return out

    def decode_chunks_from(self, dense: np.ndarray, survivors,
                           targets) -> np.ndarray:
        """decode_chunks from the k `survivors` rows given, rebuilding
        only the `targets` rows (the launch queue's run_decode): its
        program is decode_chunks' with len(targets) erased, whichever
        rows answered."""
        survivors = tuple(sorted(int(s) for s in survivors))
        targets = tuple(sorted(int(t) for t in targets))
        if len(survivors) != self.k or set(survivors) & set(targets):
            raise ErasureCodeError(
                errno.EIO, f"decode needs {self.k} survivors apart from "
                           f"the targets: {survivors} -> {targets}")
        _, bitmat = self._decode_plan(survivors, targets)
        rec = self._apply_bitmat(bitmat, dense[list(survivors)],
                                 len(targets))
        out = dense.copy()
        for idx, t in enumerate(targets):
            out[t] = rec[idx]
        return out


class ErasureCodePluginJax(ErasureCodePlugin):
    def factory(self, profile: Profile):
        technique = profile.get("technique", "cauchy") or "cauchy"
        if technique not in ("cauchy", "reed_sol_van"):
            raise ErasureCodeError(
                errno.ENOENT, f"unknown jax technique {technique!r}")
        return ErasureCodeJax(technique)


def __erasure_code_init__(name: str, directory: str | None) -> None:
    ErasureCodePluginRegistry.instance().add(name, ErasureCodePluginJax())
