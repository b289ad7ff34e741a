"""The flagship `tpu` erasure-code plugin.

Registers alongside the CPU plugins in the same registry — the seam named
by the north star (BASELINE.json): a profile of
``plugin=tpu technique=reed_sol_van k=8 m=4`` yields a codec whose
encode_chunks/decode_chunks run as batched bit-plane GF matmuls on the
MXU (ceph_tpu/ops/jax_engine.py), bit-exact with the CPU `jerasure`
plugin because both build identical coding matrices.

All seven jerasure-compatible techniques are supported; every one reduces
to a binary matrix.  On a TPU the byte-layout w=8 codes (reed_sol_van,
reed_sol_r6_op) ride the fused bit-plane kernel ``gf8_mxu_pallas``, the
packet-layout codes (cauchy_orig, cauchy_good, liberation, blaum_roth,
liber8tion) the fused packet kernel ``packet_mxu_pallas``, and both go
through the OSD batcher's three lanes (encode, decode, delta) by the
same staged asynchronous dispatch.  On hosts
without a TPU (e.g. the monitor validating a profile, reference
mon/OSDMonitor.cc:7371-7392) the same code runs on JAX's CPU backend
with the XLA kernels — same results.  Which kernel serves is decided
by platform and geometry (jax_engine gf8_kernel / packet_kernel); on
a TPU a kernel that fails to compile raises, it is not replaced.

Beyond the reference's synchronous per-stripe API, this plugin exposes
the batched entry points the OSD write pipeline uses to amortize
host->device transfers across the PG queue (SURVEY.md section 3.1
"batching point"):

    encode_batch(data[B, k, L])  -> parity[B, m, L]
    decode_batch(present {id: [B, L]}, chunk_len) -> {id: [B, L]}
"""
from __future__ import annotations

from typing import Dict, Mapping

import numpy as np

from ...ops.jax_engine import JaxBackend
from ..interface import ErasureCodeProfile, ErasureCodeValidationError
from ..registry import ErasureCodePlugin
from . import jerasure as jr

_SHARED_BACKEND: JaxBackend = None

# (geometry, batch-shape) pairs already compiled+staged by
# prewarm_geometry — PG activation calls it per PG, the work is
# per-process
_PREWARMED_SHAPES: set = set()


def shared_backend() -> JaxBackend:
    """One backend per process so jit caches / device matrices are shared
    across codec instances (each PG constructs its own codec, reference
    osd/PGBackend.cc:555-591)."""
    global _SHARED_BACKEND
    if _SHARED_BACKEND is None:
        _SHARED_BACKEND = JaxBackend()
    return _SHARED_BACKEND


class _DecodeHandle:
    """AsyncBatch wrapper for a decode group: ``wait()`` splits the
    combined-recovery-row output [B, E, L] back into per-erased-chunk
    arrays — one for EVERY chunk id absent from what was handed in,
    whether a rider wants it or not (see decode_batch_async).  Exposes
    the underlying seven-phase DeviceLedger and h2d sample so the OSD
    batcher folds decode groups into the same waterfall/crossover
    machinery as encode groups."""

    __slots__ = ("_ab", "_erased")

    def __init__(self, ab, erased):
        self._ab = ab
        self._erased = tuple(erased)

    @property
    def ledger(self):
        return getattr(self._ab, "ledger", None)

    @property
    def ledgers(self):
        """Per-chip ledger clones on a mesh-sharded dispatch (one lane
        per device), None on single-chip — AsyncBatch.ledgers."""
        return getattr(self._ab, "ledgers", None)

    @property
    def h2d_bytes(self):
        return getattr(self._ab, "h2d_bytes", 0)

    @property
    def h2d_seconds(self):
        return getattr(self._ab, "h2d_seconds", 0.0)

    def wait(self) -> Dict[int, np.ndarray]:
        out = self._ab.wait()
        return {e: out[..., i, :] for i, e in enumerate(self._erased)}


class TpuCodecMixin:
    """Overrides the backend and adds the batched API."""

    def make_backend(self):
        return shared_backend()

    # -- batched entry points (the TPU value-add) -------------------------
    def encode_batch(self, data: np.ndarray) -> np.ndarray:
        """uint8 [B, k, L] -> parity uint8 [B, m, L]; one device call for
        the whole stripe batch."""
        data = np.asarray(data, dtype=np.uint8)
        if data.ndim != 3 or data.shape[1] != self.k:
            raise ValueError(f"expected [batch, k={self.k}, L] input")
        return self.core.encode_batch(data)

    def decode_batch(self, present: Mapping[int, np.ndarray],
                     chunk_len: int) -> Dict[int, np.ndarray]:
        """Reconstruct all missing chunk ids for a batch: present maps
        chunk id -> uint8 [B, L]."""
        arrays = {i: np.asarray(c, dtype=np.uint8)
                  for i, c in present.items()}
        return self.core.decode_chunks(arrays, chunk_len)

    def encode_batch_async(self, data: np.ndarray):
        """Non-blocking encode_batch: returns an AsyncBatch whose wait()
        yields parity [B, m, L].  Submitting the next batch before
        waiting overlaps transfers with MXU compute — the OSD write
        pipeline's double-buffering entry point.  On a multi-device
        host the backend lays the batch out with the sharded
        (dp, None, sp) NamedSharding and dispatches ONE sharded GF
        matmul over the mesh (jax_engine _staged_put + gf8_fn /
        _mesh_apply_fn routing), riding the same staging rings,
        h2d EWMA sampling, and per-device phase ledgers as the
        single-chip path."""
        data = np.asarray(data, dtype=np.uint8)
        if data.ndim != 3 or data.shape[1] != self.k:
            raise ValueError(f"expected [batch, k={self.k}, L] input")
        return self._encode_async(data)

    def _encode_async(self, block: np.ndarray):
        """The pool's one encode program over a [B, k, L] block, by
        the code's layout: what encode and delta dispatch."""
        core = self.core
        if core.layout == "packet":
            return core.backend.apply_packet_async(
                core.bitmatrix, block, core.w, core.packetsize)
        if core.gf8_encode_fast():
            return core.backend.apply_gf8_rows_async(
                core.coding_matrix, block)
        return core.backend.apply_bitmatrix_bytes_async(
            core.bitmatrix, block, self.w)

    def decode_async_supported(self) -> bool:
        """True when this geometry can ride the async device decode
        pipeline: byte-domain w=8 with a GF coding matrix (combined
        recovery rows through the GF kernel), or packet layout
        (combined recovery rows in the bit domain through the packet
        kernel)."""
        core = self.core
        return core.layout == "packet" or (
            core.layout == "byte" and core.w == 8
            and core.coding_matrix is not None)

    def decode_batch_async(self, present: Mapping[int, np.ndarray],
                           chunk_len: int) -> _DecodeHandle:
        """Non-blocking decode_batch: one staged device dispatch
        reconstructs EVERY chunk id absent from ``present`` — the
        entry takes no ``want``, so a read that gathered k of k+m
        shards gets m rows back where its riders asked for the one or
        two they lost (the batcher counts both, ``dec_rows_out`` and
        ``dec_rows_wanted``; the benchmark's
        ``decode.unwanted_row_share`` is their gap).  The
        per-erasure-signature combined recovery rows (CodecCore
        `_recovery_rows` — inverse map for data erasures, encode row
        composed through it for parity erasures) make reconstruction a
        single matmul whose rows are an operand of the kernel family's
        one program (jax_engine rows_program), so decode groups
        pipeline through the same StagingPool rings, executables and
        inflight-group machinery as encode — the decode twin of
        encode_batch_async."""
        if not self.decode_async_supported():
            raise ValueError("async device decode needs a byte-domain "
                             "w=8 GF coding matrix or a packet layout")
        core = self.core
        n = self.k + self.m
        avail = sorted(i for i in present if i < n)
        if len(avail) < self.k:
            raise ValueError(
                f"need {self.k} chunks, have {len(avail)}")
        erased = tuple(i for i in range(n) if i not in present)
        chosen = tuple(avail[:self.k])
        rows_gf, rows_bits = core._recovery_rows(chosen, erased)
        stack = np.stack(
            [np.asarray(present[i], dtype=np.uint8)
             .reshape(-1, int(chunk_len)) for i in chosen], axis=1)
        if core.layout == "packet":
            ab = core.backend.apply_packet_async(
                rows_bits, stack, core.w, core.packetsize)
        else:
            ab = core.backend.apply_gf8_rows_async(rows_gf, stack)
        return _DecodeHandle(ab, erased)

    def delta_async_supported(self) -> bool:
        """True when this geometry can ride the async device
        parity-delta pipeline (same gate as device decode)."""
        return self.decode_async_supported()

    def delta_encode_batch_async(self, delta: np.ndarray, dirty_cols):
        """Non-blocking parity delta: Δdata uint8 [B, D, L] for the
        D dirty data columns -> AsyncBatch whose wait() yields
        Δparity uint8 [B, m, L] (new_parity = old_parity XOR Δparity,
        applied shard-side via the store's xor_write op).

        The dirty columns are scattered into a zero [B, k, L] block
        and dispatched through the SAME per-pool compiled encode
        kernel as encode_batch_async — GF linearity makes the zero
        columns inert, so M·pad(Δ) == M[:, dirty]·Δ bit for bit (a
        packet code is linear over GF(2) region by region, and a
        chunk is a whole number of regions).  A
        per-dirty-signature kernel (M[:, dirty] baked into its own
        jit) would be cheaper per byte moved, but every fresh
        (signature, shape-bucket) pair pays a multi-second XLA
        compile, and overwrite traffic sprays signatures: measured
        on the rmw bench, first-touch compile stalls inverted the
        whole win (delta 0.1x full at 4 KiB).  One shared kernel
        means a delta dispatch NEVER compiles — the staging rings,
        mesh sharding, h2d EWMA and DeviceLedger are encode's own,
        already hot."""
        if not self.delta_async_supported():
            raise ValueError("async device delta needs a byte-domain "
                             "w=8 GF coding matrix or a packet layout")
        cols = [int(c) for c in dirty_cols]
        delta = np.asarray(delta, dtype=np.uint8)
        if delta.ndim != 3 or delta.shape[1] != len(cols):
            raise ValueError(
                f"expected [batch, D={len(cols)}, L] delta input")
        block = np.zeros((delta.shape[0], self.k, delta.shape[2]),
                         dtype=np.uint8)
        block[:, cols, :] = delta
        return self._encode_async(block)

    def delta_encode_batch(self, delta: np.ndarray,
                           dirty_cols) -> np.ndarray:
        """Synchronous parity delta (the CPU-twin / oracle route):
        Δdata [B, D, L] -> Δparity [B, m, L] via CodecCore."""
        return self.core.delta_parity(
            np.asarray(delta, dtype=np.uint8), dirty_cols)

    def _geometry(self, chunk_size: int) -> tuple:
        """What one compiled program serves: the _PREWARMED_SHAPES key."""
        return (type(self).__name__, self.k, self.m, self.w,
                self.core.packetsize, int(chunk_size))

    def _prewarm_rings(self, chunk_size: int, batches) -> None:
        """The backend's staging rings for this geometry's shapes."""
        self.core.backend.prewarm_geometry(
            self.k, chunk_size, batches=batches, w=self.w,
            packetsize=self.core.packetsize)

    def prewarm_delta(self, chunk_size: int, dirty_cols=None,
                      batches=(1,)) -> None:
        """Make the delta lane hot before the first sub-stripe
        overwrite.  Delta dispatches ride the per-pool encode kernel
        (dirty columns zero-padded to [B, k, L]), so there is no
        per-signature executable to warm — just the staging ring and
        the pool matrix at the encode shape.  Idempotent per
        (geometry, chunk_size); ``dirty_cols`` is accepted for API
        compatibility but no longer selects an executable.  A compile
        or dispatch failure raises (callers record it, see
        EncodeBatcher.note_prewarm_error)."""
        if not self.delta_async_supported():
            return
        self._prewarm_rings(chunk_size, batches)
        key = ("delta",) + self._geometry(chunk_size)
        if key in _PREWARMED_SHAPES:
            return
        z = np.zeros((1, 1, int(chunk_size)), dtype=np.uint8)
        self.delta_encode_batch_async(z, (0,)).wait()
        _PREWARMED_SHAPES.add(key)       # only once it really is warm

    def prewarm_decode(self, chunk_size: int, batches=(1,)) -> None:
        """Make recovery hot before the first rebuild window:
        host-side combined recovery rows for every single-erasure
        signature, the staging rings for the window shapes, and the
        decode entry run once per batch shape with k chunks present —
        what a degraded read or a recovery read gathers, so m rows
        out.  Where the row set is an operand (jax_engine
        rows_program) that is THE executable of every erasure
        signature at that shape, the pool's encode included; off a
        TPU a packet code's XOR schedule is still compiled per
        signature.  Idempotent per (geometry, chunk_size)."""
        if not self.decode_async_supported():
            return
        core = self.core
        n = self.k + self.m
        for e in range(n):
            chosen = tuple(i for i in range(n) if i != e)[:self.k]
            core._recovery_rows(chosen, (e,))
        self._prewarm_rings(chunk_size, batches)
        key = ("dec",) + self._geometry(chunk_size)
        if key in _PREWARMED_SHAPES:
            return
        for nb in batches:
            z = np.zeros((max(1, int(nb)), int(chunk_size)),
                         dtype=np.uint8)
            self.decode_batch_async(
                {i: z for i in range(1, self.k + 1)},
                int(chunk_size)).wait()
        _PREWARMED_SHAPES.add(key)       # only once it really is warm

    def prewarm_geometry(self, chunk_size: int,
                         batches=(1,)) -> None:
        """Make this pool geometry hot before the first client write:
        preallocate the persistent staging rings for the batch shapes
        the OSD coalescer dispatches (jax_engine StagingPool) and
        compile the encode executables by running one zero batch per
        shape through the real async path.  Idempotent per
        (geometry, shape) process-wide; synchronous — callers (PG
        activation) run it on a background thread."""
        self._prewarm_rings(chunk_size, batches)
        for nb in batches:
            key = self._geometry(chunk_size) + (int(nb),)
            if key in _PREWARMED_SHAPES:
                continue
            z = np.zeros((max(1, int(nb)), self.k, int(chunk_size)),
                         dtype=np.uint8)
            self.encode_batch_async(z).wait()
            _PREWARMED_SHAPES.add(key)   # only once it really is warm

    def stage_batch(self, data: np.ndarray):
        """Transfer a stripe batch to device HBM ahead of encode."""
        data = np.asarray(data, dtype=np.uint8)
        return self.core.backend.stage(data, self.w)

    def encode_batch_device(self, dev_data):
        """Device-resident encode: device array in, device array out (no
        host round trip) — the codec-kernel boundary.  w=8 byte-domain
        codes ride the fused bit-plane MXU pallas kernel (jax_engine
        gf8_fn routing), packet codes the fused MXU packet kernel
        (packet_chain_fn), others the bit-plane XLA path."""
        core = self.core
        if core.layout == "byte" and core.w == 8 \
                and core.coding_matrix is not None:
            return core.backend.apply_gf8_matrix_device(
                core.coding_matrix, dev_data)
        if core.layout == "packet":
            return core.backend.packet_chain_fn(
                core.bitmatrix, core.w, core.packetsize)(dev_data)
        return core.backend.apply_bitmatrix_bytes_device(
            core.bitmatrix, dev_data, self.w)

    def decode_batch_device(self, dev_stack, chosen, data_erased):
        """Device-resident per-erasure-signature decode: reconstruct
        ``data_erased`` chunk ids from the staged ``chosen`` chunk
        stack [B, k, L] (device array in/out).  Uses the same
        signature-cached compiled kernels the OSD recovery path does
        (jax_engine gf8_fn / packet_chain_fn — the analog of ISA-L's
        decode-table LRU, reference
        isa/ErasureCodeIsaTableCache.cc:253-306)."""
        core = self.core
        rows_gf, rows_bits = core._decode_rows(tuple(chosen),
                                               tuple(data_erased))
        if core.layout == "byte" and core.w == 8 and rows_gf is not None:
            return core.backend.gf8_fn(rows_gf)(dev_stack)
        if core.layout == "packet":
            return core.backend.packet_chain_fn(
                rows_bits, core.w, core.packetsize)(dev_stack)
        return core.backend.apply_bitmatrix_bytes_device(
            rows_bits, dev_stack, core.w)


class TpuReedSolomonVandermonde(TpuCodecMixin, jr.ReedSolomonVandermonde):
    DEFAULT_K, DEFAULT_M, DEFAULT_W = "8", "4", "8"  # north-star config


class TpuReedSolomonRAID6(TpuCodecMixin, jr.ReedSolomonRAID6):
    pass


class TpuCauchyOrig(TpuCodecMixin, jr.CauchyOrig):
    pass


class TpuCauchyGood(TpuCodecMixin, jr.CauchyGood):
    pass


class TpuLiberation(TpuCodecMixin, jr.Liberation):
    pass


class TpuBlaumRoth(TpuCodecMixin, jr.BlaumRoth):
    pass


class TpuLiber8tion(TpuCodecMixin, jr.Liber8tion):
    pass


TECHNIQUES = {
    "reed_sol_van": TpuReedSolomonVandermonde,
    "reed_sol_r6_op": TpuReedSolomonRAID6,
    "cauchy_orig": TpuCauchyOrig,
    "cauchy_good": TpuCauchyGood,
    "liberation": TpuLiberation,
    "blaum_roth": TpuBlaumRoth,
    "liber8tion": TpuLiber8tion,
}


class ErasureCodePluginTpu(ErasureCodePlugin):
    def factory(self, profile: ErasureCodeProfile):
        technique = profile.get("technique", "reed_sol_van")
        cls = TECHNIQUES.get(technique)
        if cls is None:
            raise ErasureCodeValidationError(
                f"technique={technique} is not a valid coding technique")
        codec = cls()
        codec.init(profile)
        return codec


def __erasure_code_init__(registry) -> None:
    registry.add("tpu", ErasureCodePluginTpu())
