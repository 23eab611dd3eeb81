"""Multi-chip sharding of the erasure-code data plane.

Where the reference scales with CRUSH placement over OSD hosts and ships
shard writes over its async messenger (reference src/osd/ECBackend.cc:2074
MOSDECSubOpWrite fan-out; recovery fan-in :570), the TPU-native data
plane scales over a `jax.sharding.Mesh` with XLA collectives riding ICI:

  axis 'shard' — tensor-parallel over the k data chunks.  Each device
      holds a slice of the chunk rows and the matching *columns* of the
      generator bit-matrix, runs the SAME fused Pallas kernel the
      single-chip path uses on its slice, and the cross-device GF(2)
      fan-in is an `all_gather` + XOR fold of the packed partial
      parities (mod-2 commutes with the sum, so per-device parities XOR
      to the total — the parity fan-in a messenger would carry becomes
      one collective of exactly the parity bytes).
  axis 'data' — data-parallel over the byte/stripe axis, no
      communication: stripes are independent, like separate PGs.

Round 1 shipped a psum-of-unpacked-bitplanes fan-in; that moves 32x the
parity bytes over ICI (8 bit-planes x int32) and forces the pack out of
the kernel.  The XOR-of-packed fold moves (n_shard-1) x m x W bytes and
lets each device run the full w32 Pallas kernel locally — both encode
and decode ride the headline kernel now.

Decode/repair is the same contraction with the inverted matrix: the k
survivor rows shard over 'shard', each device applies its column slice
of the (targets x k) recovery matrix, XOR fold completes the rebuild
(reference ECBackend recovery reads k shards to the primary and decodes
locally; here the gather IS the collective).

Everything is shape-static and jit-clean: one compiled program per
(r, geometry), cached; `jax.jit` re-specializes per byte-width.
"""

from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
import numpy as np
from jax.sharding import Mesh, NamedSharding, PartitionSpec as P

from ..common.util import concat_columns, split_columns
from ..ec import gf
from ..ops import bitsliced, device
from ..ops.profiler import device_profiler

LANE = bitsliced.LANE


def make_mesh(n_shard: int, n_data: int, devices=None) -> Mesh:
    """Build a ('shard', 'data') mesh from the first n_shard*n_data devices."""
    devices = np.asarray(devices if devices is not None else jax.devices())
    need = n_shard * n_data
    if devices.size < need:
        raise ValueError(f"need {need} devices, have {devices.size}")
    return Mesh(devices[:need].reshape(n_shard, n_data), ("shard", "data"))


class DistributedStripeCodec:
    """Sharded batched RS encode/decode over a device mesh.

    The flagship distributed computation.  Two entry families:

      encode_flat / decode_flat — (k, W) chunk rows, the OSD pipeline's
          native drain layout (ECBackend concatenates every extent of
          every in-flight transaction along the byte axis);
      encode — (B, k, C) stripe batches (benchmarks, tests).

    `use_w32` selects the word-packed Pallas kernel (the single-chip
    headline path) inside each device's shard of the contraction; the
    byte/XLA formulation remains for CPU meshes (the driver's virtual
    8-device dry run) and as the oracle.  `interpret=True` runs the w32
    Pallas kernel in interpret mode so the word-packed mesh path is
    exercised on CPU CI too.
    """

    def __init__(self, k: int, m: int, mesh: Mesh,
                 technique: str = "cauchy",
                 use_w32: bool | None = None,
                 interpret: bool | None = None):
        self.k, self.m, self.mesh = k, m, mesh
        on_cpu = device.on_cpu()
        self.use_w32 = use_w32 if use_w32 is not None else not on_cpu
        self.interpret = interpret if interpret is not None else on_cpu
        self.n_shard = mesh.shape["shard"]
        self.n_data = mesh.shape["data"]
        if k % self.n_shard:
            raise ValueError(
                f"k={k} not divisible by shard axis {self.n_shard}")
        self.k_local = k // self.n_shard
        self.matrix = (gf.cauchy_rs_matrix(k, m) if technique == "cauchy"
                       else gf.vandermonde_rs_matrix(k, m))
        self.enc_bitmats = self._column_bitmats(self.matrix[k:])
        self._apply_cache: dict[int, object] = {}
        self._decode_plans: dict[tuple, object] = {}
        self._clay_plans: dict[tuple, object] = {}

    # -- bitmatrix plumbing -------------------------------------------------

    def _column_bitmats(self, coeff: np.ndarray,
                        cols_per_shard: int | None = None):
        """(r, j) GF(2^8) matrix -> device-put stack of per-shard column
        slices in the kernel's layout: device s gets the columns for its
        cols_per_shard input rows ((n_shard, 32r, 32c) w32 or
        (n_shard, 8r, 8c) byte), 'shard'-sharded on dim 0.  Defaults to
        the k_local encode/decode split; the CLAY repair lowering passes
        its own (padded) split."""
        cps = self.k_local if cols_per_shard is None else cols_per_shard
        build = bitsliced._w32_bitmat if self.use_w32 \
            else bitsliced.interleave_bitmatrix
        mats = [build(np.ascontiguousarray(
                    coeff[:, s * cps:(s + 1) * cps]))
                for s in range(self.n_shard)]
        stacked = np.stack(mats).astype(np.int8)
        return jax.device_put(
            stacked, NamedSharding(self.mesh, P("shard", None, None)))

    def _sharded_apply(self, r: int):
        """shard_map'd contraction for r output rows: local kernel on
        each device's (k_local, W_local) slice, all_gather + XOR fold
        over 'shard'.  Cached per r; jit respecializes per width."""
        fn = self._apply_cache.get(r)
        if fn is not None:
            return fn
        n_shard = self.n_shard
        use_w32, interpret = self.use_w32, self.interpret

        def local(bitmat, x):
            # bitmat (1, R, C); x (k_local, W_local)
            if use_w32:
                part = bitsliced.gf_bitmatmul_pallas_w32(
                    bitmat[0], x, r,
                    tile=4 * bitsliced._pick_wt(x.shape[1]),
                    interpret=interpret)
            else:
                part = bitsliced.gf_bitmatmul_xla(bitmat[0], x, r)
            gath = jax.lax.all_gather(part, "shard")   # (n_shard, r, W)
            return functools.reduce(
                jnp.bitwise_xor, [gath[i] for i in range(n_shard)])

        # check_vma off: the checker can't statically infer that the
        # XOR fold of an all_gather over 'shard' is 'shard'-replicated
        # (it is: every member folds the same gathered operands)
        fn = jax.jit(jax.shard_map(
            local, mesh=self.mesh,
            in_specs=(P("shard", None, None), P("shard", "data")),
            out_specs=P(None, "data"), check_vma=False))
        self._apply_cache[r] = fn
        return fn

    def _quantum(self) -> int:
        """Byte-axis pad quantum: every device slice must be a LANE
        multiple (words for w32, bytes otherwise)."""
        per_dev = LANE * 4 if self.use_w32 else LANE
        return self.n_data * per_dev

    def _apply_flat_submit(self, bitmats, rows: np.ndarray, r: int):
        """Dispatch half of _apply_flat: stages rows onto the mesh and
        launches the sharded contraction, returning a handle of the
        device future + layout metadata — no host sync (the OSD's
        dispatch-ahead drains materialize in a later completion
        stage)."""
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        j, w = rows.shape
        pad = -w % self._quantum()
        if pad:
            rows = np.pad(rows, ((0, 0), (0, pad)))
        if self.use_w32:
            x = rows.view("<u4").view(np.int32)
        else:
            x = rows
        x = jax.device_put(
            jnp.asarray(x), NamedSharding(self.mesh, P("shard", "data")))
        return {"dev": self._sharded_apply(r)(bitmats, x),
                "r": r, "w": w, "pad": pad}

    def _apply_flat_finalize(self, handle) -> np.ndarray:
        out = np.asarray(handle["dev"])
        r, w, pad = handle["r"], handle["w"], handle["pad"]
        if self.use_w32:
            out = out.view("<u4").view(np.uint8).reshape(r, w + pad)
        return out[:, :w] if pad else out

    def _apply_flat(self, bitmats, rows: np.ndarray, r: int) -> np.ndarray:
        """rows (j, W) uint8 (j = k data rows or k survivor rows) ->
        (r, W) uint8 via the sharded contraction."""
        return self._apply_flat_finalize(
            self._apply_flat_submit(bitmats, rows, r))

    # -- device-resident entry (no host round-trip) -------------------------

    def apply_words(self, bitmats, words, r: int):
        """Fully device-resident contraction for callers that keep the
        data plane on device (benchmarks, chained pipelines): `words`
        (k, W) i32, already 'shard'x'data'-sharded or not (jit will
        reshard), W divisible by the device quantum.  Returns the
        (r, W) i32 result as a device array — zero host traffic.
        w32 codecs only (the device layout IS the word layout)."""
        if not self.use_w32:
            raise RuntimeError("apply_words requires a w32 mesh codec")
        assert words.shape[1] % (self.n_data * LANE) == 0
        return self._sharded_apply(r)(bitmats, words)

    def encode_words(self, words):
        """Device-resident sharded encode: (k, W) i32 -> (m, W) i32."""
        return self.apply_words(self.enc_bitmats, words, self.m)

    # -- encode (host byte API: the OSD pipeline entry) ---------------------

    def encode_flat(self, chunks: np.ndarray) -> np.ndarray:
        """(k, W) uint8 data rows -> (m, W) parity.  The OSD pipeline
        entry: ECBackend hands the whole batched drain here when a mesh
        is configured (reference analog: the per-shard MOSDECSubOpWrite
        fan-out, ECBackend.cc:2074, as one collective program)."""
        assert chunks.shape[0] == self.k
        return self._apply_flat(self.enc_bitmats, chunks, self.m)

    def encode_flat_submit(self, chunks: np.ndarray):
        """Dispatch half of encode_flat (no host sync); materialize
        with encode_flat_finalize.  The ECBackend dispatch-ahead drain
        entry for mesh-configured pools."""
        assert chunks.shape[0] == self.k
        return self._apply_flat_submit(self.enc_bitmats, chunks, self.m)

    def encode_flat_finalize(self, handle) -> np.ndarray:
        return self._apply_flat_finalize(handle)

    def encode(self, stripes):
        """stripes (B, k, C) uint8 -> parity (B, m, C): batch and byte
        axes ride 'data' together via the flat layout."""
        stripes = np.ascontiguousarray(stripes, dtype=np.uint8)
        b, k, c = stripes.shape
        assert k == self.k
        flat = stripes.transpose(1, 0, 2).reshape(k, b * c)
        par = self.encode_flat(flat)
        return par.reshape(self.m, b, c).transpose(1, 0, 2)

    # -- decode / repair ----------------------------------------------------

    def _decode_bitmats(self, survivors: tuple[int, ...],
                        targets: tuple[int, ...]):
        """Column-sharded bitmats of the (targets x survivors) recovery
        matrix (reference ECUtil::decode inversion, ECUtil.cc:9; the
        ISA-L table-cache role for the mesh)."""
        key = (survivors, targets)
        hit = self._decode_plans.get(key)
        if hit is not None:
            return hit
        coeff = gf.recovery_matrix(self.matrix, self.k, survivors, targets)
        mats = self._column_bitmats(coeff)
        self._decode_plans[key] = mats
        return mats

    def decode_flat(self, avail: np.ndarray, survivors, targets
                    ) -> np.ndarray:
        """Distributed reconstruct: `avail` (k, W) holds the survivor
        shards' bytes in `survivors` order; returns the rebuilt `targets`
        shards (len(targets), W).  Survivor rows shard over 'shard', so
        repair reads stay distributed end to end (reference
        continue_recovery_op gathers k shards to one node instead)."""
        survivors = tuple(survivors)
        targets = tuple(targets)
        if len(survivors) != self.k:
            raise ValueError(f"need exactly k={self.k} survivors")
        mats = self._decode_bitmats(survivors, targets)
        return self._apply_flat(mats, avail, len(targets))

    def decode_flat_batch(self, avail_list, survivors, targets
                          ) -> list[np.ndarray]:
        """Batched distributed repair: MANY objects' survivor rows
        (same survivor/target pattern — the common case in an OSD-loss
        storm, where every object of a PG misses the same shards) ride
        ONE sharded contraction.  avail_list: [(k, W_i) uint8] in
        `survivors` order; returns the rebuilt targets per object.
        The byte axes concatenate (stripes are independent), so a
        recovery queue of N objects costs one launch instead of N —
        the reference's per-object continue_recovery_op decode loop
        collapsed into a single collective program."""
        if not avail_list:
            return []
        widths = [a.shape[1] for a in avail_list]
        big = np.concatenate(avail_list, axis=1) \
            if len(avail_list) > 1 else avail_list[0]
        survivors = tuple(survivors)
        targets = tuple(targets)
        if len(survivors) != self.k:
            raise ValueError(f"need exactly k={self.k} survivors")
        # flight recorder (ops/profiler.py): one record per batched
        # repair collective, submit/finalize split preserved
        import time as _time
        prof = device_profiler()
        rec = prof.begin("mesh_decode",
                         codec=f"mesh:k{self.k}m{self.m}",
                         runs=len(avail_list), nbytes=int(big.size))
        mats = self._decode_bitmats(survivors, targets)
        handle = self._apply_flat_submit(mats, big, len(targets))
        tgt = "".join(str(t) for t in targets)
        prof.submitted(rec, f"mesh:d{tgt}:w{big.shape[1]}",
                       path="mesh")
        t0 = _time.perf_counter()
        out = self._apply_flat_finalize(handle)
        prof.materialized(rec, _time.perf_counter() - t0)
        res = []
        col = 0
        for w in widths:
            res.append(out[:, col:col + w])
            col += w
        return res

    # -- CLAY repair (docs/REPAIR.md) ---------------------------------------

    def clay_repair_batch(self, plan: "ClayRepairPlan",
                          rows_list) -> list[np.ndarray]:
        """Batched distributed CLAY repair: MANY objects lost the same
        chunk to the same helper set (the storm case), each object's
        stacked helper repair-plane rows (d*P, S_i) riding ONE sharded
        GF contraction — the coupled-layer host plane-solver collapsed
        to the same collective program shape as decode_flat_batch
        (input rows shard over 'shard', byte axes concatenate over
        'data').  The repair matrix's input rows pad with zero rows
        (and zero matrix columns) to divide over the shard axis; zero
        rows XOR-fold to nothing."""
        if not rows_list:
            return []
        j = plan.in_rows
        pad = -j % self.n_shard
        mats = self._clay_plans.get(plan.signature)
        if mats is None:
            coeff = plan.matrix
            if pad:
                coeff = np.concatenate(
                    [coeff, np.zeros((plan.out_rows, pad),
                                     dtype=np.uint8)], axis=1)
            mats = self._column_bitmats(
                coeff, cols_per_shard=(j + pad) // self.n_shard)
            self._clay_plans[plan.signature] = mats
        big, widths = concat_columns(rows_list)
        if pad:
            big = np.concatenate(
                [big, np.zeros((pad, big.shape[1]), dtype=np.uint8)],
                axis=0)
        import time as _time
        prof = device_profiler()
        rec = prof.begin("mesh_clay_repair",
                         codec=f"mesh:k{self.k}m{self.m}",
                         runs=len(rows_list), nbytes=int(big.size))
        handle = self._apply_flat_submit(mats, big, plan.out_rows)
        sig = abs(hash(plan.signature)) & 0xFFFFFF
        prof.submitted(rec, f"mesh:r{sig:x}:w{big.shape[1]}",
                       path="mesh")
        t0 = _time.perf_counter()
        out = self._apply_flat_finalize(handle)
        prof.materialized(rec, _time.perf_counter() - t0)
        return split_columns(out, widths)

    def decode(self, stripes_avail, survivors, targets):
        """(B, k, C) survivor stripes -> (B, len(targets), C)."""
        a = np.ascontiguousarray(stripes_avail, dtype=np.uint8)
        b, k, c = a.shape
        flat = a.transpose(1, 0, 2).reshape(k, b * c)
        out = self.decode_flat(flat, survivors, targets)
        return out.reshape(len(tuple(targets)), b, c).transpose(1, 0, 2)

    # -- oracle -------------------------------------------------------------

    def encode_reference(self, stripes) -> np.ndarray:
        """Single-host oracle for tests."""
        out = []
        coding = self.matrix[self.k:]
        for s in np.asarray(stripes, dtype=np.uint8):
            out.append(gf.gf_matvec(coding, s))
        return np.stack(out)


# ----------------------------------------------------------------------------
# CLAY repair on the device plane (docs/REPAIR.md)
# ----------------------------------------------------------------------------
#
# ec/plugins/ec_clay.py's repair() is GF(2^8)-linear in the helper
# symbols, so the whole coupled-layer contraction — pairwise decouple
# transforms, per-plane parity-check solves in score order, final
# re-coupling — collapses to ONE (sub_chunks x d*P) matrix per
# (lost chunk, helper set), extracted host-side by an identity probe
# (ErasureCodeClay.repair_matrix) and applied here as a batched GF
# matmul: the same bit-sliced contraction the encode/decode paths ride,
# on a single device (apply_device) or sharded over the mesh
# (DistributedStripeCodec.clay_repair_batch).  What used to be a
# per-object, per-plane host crawl during the exact storm CLAY was
# built for becomes a handful of device launches.


class ClayRepairPlan:
    """One (lost, helpers) repair lowering: the GF(2^8) matrix plus its
    lazily-built device bitmatrix.  Shareable across PGs/backends of
    the same geometry (the signature is the coalescing key the launch
    queue batches on)."""

    def __init__(self, matrix: np.ndarray, signature: tuple,
                 lost_chunk: int, helper_ids: tuple[int, ...]):
        self.matrix = np.ascontiguousarray(matrix, dtype=np.uint8)
        self.out_rows, self.in_rows = self.matrix.shape
        self.signature = signature
        self.lost_chunk = lost_chunk
        self.helper_ids = tuple(helper_ids)
        self._bitmat = None

    @classmethod
    def build(cls, plugin, lost_chunk: int,
              helper_ids=None) -> "ClayRepairPlan":
        """Lower one single-failure repair of a sub-chunked plugin
        (ErasureCodeClay.repair_matrix) into a plan."""
        helpers = plugin.repair_helper_order(lost_chunk, helper_ids)
        return cls(plugin.repair_matrix(lost_chunk, helpers),
                   plugin.repair_signature(lost_chunk, helpers),
                   lost_chunk, helpers)

    # -- host oracle ---------------------------------------------------------

    # flight-recorder hint (ops/profiler.py): apply_device() runs the
    # jitted XLA bitmatmul, so a first-seen width IS a compile
    jit_backed = True

    def apply_host(self, rows: np.ndarray) -> np.ndarray:
        """(in_rows, W) helper rows -> (out_rows, W) rebuilt sub-chunk
        rows via the host GF matvec (the tests' oracle)."""
        return gf.gf_matvec(self.matrix, rows)

    # -- single-device path (the launch-queue / smoke configuration) --------

    def apply_device(self, rows: np.ndarray) -> np.ndarray:
        """Same contraction through the jitted XLA bit-sliced matmul
        on the default jax device — the batched path a host without a
        configured mesh serves repair from (one launch for every
        object of a (lost, helpers) group, byte axes concatenated)."""
        if self._bitmat is None:
            self._bitmat = jnp.asarray(
                bitsliced.interleave_bitmatrix(self.matrix),
                dtype=jnp.int8)
        rows = np.ascontiguousarray(rows, dtype=np.uint8)
        return np.asarray(bitsliced.gf_bitmatmul_xla(
            self._bitmat, jnp.asarray(rows), self.out_rows))

