"""Codec execution core: run a GF(2^w) matrix / GF(2) bitmatrix erasure
code over byte buffers, batched, with pluggable backends.

Two data layouts, matching the reference's two kernel families:

* ``byte`` — each chunk is a stream of GF(2^w) words (w/8 bytes each,
  little-endian); the code is a true GF(2^w) matrix multiply per word.
  This is jerasure_matrix_encode semantics (reed_sol_van / reed_sol_r6;
  reference ErasureCodeJerasure.cc:162).
* ``packet`` — each chunk is a sequence of super-words of w *packets* of
  ``packetsize`` bytes; the code XORs whole packets per a GF(2)
  bitmatrix.  This is jerasure_schedule_encode semantics (cauchy /
  liberation family; reference ErasureCodeJerasure.cc:265).

Both layouts reduce to one primitive — a 0/1 matrix applied over GF(2) to
a stack of bit-rows — which is exactly what the TPU engine
(ceph_tpu/ops/jax_engine.py) executes as one batched int8 matmul on the
MXU.  The numpy backend here is the bit-exact CPU reference oracle.
"""
from __future__ import annotations

import math
import threading
from collections import OrderedDict
from typing import Optional

import numpy as np

from ..utils.tracer import section
from .gf import gf
from .matrix import (bitmatrix_invert, make_decoding_matrix,
                     matrix_to_bitmatrix)


# ---------------------------------------------------------------------------
# byte-domain word helpers
# ---------------------------------------------------------------------------

def _as_words(data: np.ndarray, w: int) -> np.ndarray:
    """uint8[..., L] -> little-endian uint{w}[..., L/(w//8)] view-copy."""
    if w == 8:
        return data
    wb = w // 8
    dt = {16: np.uint16, 32: np.uint32}[w]
    if data.shape[-1] % wb:
        raise ValueError(f"chunk length must be a multiple of {wb} for w={w}")
    return np.ascontiguousarray(data).view(dt)


def _as_bytes(words: np.ndarray) -> np.ndarray:
    return np.ascontiguousarray(words).view(np.uint8)


def region_mul_xor(c: int, src: np.ndarray, dst: np.ndarray, w: int) -> None:
    """dst ^= c * src over GF(2^w) word regions (numpy arrays of uint{w})."""
    f = gf(w)
    if c == 0:
        return
    if c == 1:
        np.bitwise_xor(dst, src, out=dst)
        return
    if w == 8:
        np.bitwise_xor(dst, f._mul_row(c)[src], out=dst)
    elif w == 16:
        s = src.astype(np.int64)
        prod = f.exp_tbl[f.log_tbl[s] + f.log_tbl[c]]
        prod = np.where(s == 0, 0, prod).astype(np.uint16)
        np.bitwise_xor(dst, prod, out=dst)
    else:  # w == 32: vectorized shift-xor with constant multiplier
        acc = np.zeros_like(src)
        cur = src.astype(np.uint64)
        poly = np.uint64(f.poly & 0xFFFFFFFF)
        top = np.uint64(1 << 32)
        for b in range(32):
            if (c >> b) & 1:
                acc ^= cur.astype(np.uint32)
            cur <<= np.uint64(1)
            hi = (cur & top).astype(bool)
            cur = (cur & np.uint64(0xFFFFFFFF)) ^ np.where(hi, poly, 0).astype(np.uint64)
        np.bitwise_xor(dst, acc, out=dst)


# ---------------------------------------------------------------------------
# bit-plane layout helpers (shared contract with the JAX engine)
# ---------------------------------------------------------------------------

def bytes_to_bitplanes(data: np.ndarray, w: int) -> np.ndarray:
    """byte layout: uint8[..., k, L] -> uint8 bits [..., k*w, L*8//w].

    Word bits become the contraction axis: row j*w + b holds bit b of each
    GF word of chunk j."""
    words = _as_words(data, w)  # [..., k, Lw]
    shifts = np.arange(w, dtype=words.dtype if w < 32 else np.uint32)
    bits = (words[..., None] >> shifts) & 1  # [..., k, Lw, w]
    bits = np.moveaxis(bits, -1, -2)  # [..., k, w, Lw]
    s = bits.shape
    return bits.reshape(s[:-3] + (s[-3] * w, s[-1])).astype(np.uint8)


def bitplanes_to_bytes(bits: np.ndarray, w: int) -> np.ndarray:
    """Inverse of bytes_to_bitplanes: [..., m*w, Lw] -> uint8[..., m, L]."""
    s = bits.shape
    m = s[-2] // w
    bits = bits.reshape(s[:-2] + (m, w, s[-1]))
    dt = {8: np.uint8, 16: np.uint16, 32: np.uint32}[w]
    weights = (np.uint64(1) << np.arange(w, dtype=np.uint64))
    words = (bits.astype(np.uint64) *
             weights[None, :, None]).sum(axis=-2).astype(dt)
    out = _as_bytes(words)
    return out.reshape(s[:-2] + (m, -1))


def bytes_to_packets(data: np.ndarray, w: int, packetsize: int) -> np.ndarray:
    """packet layout: uint8[..., k, L] -> uint8[..., nw, k*w, packetsize]
    where L = nw * w * packetsize."""
    *lead, k, L = data.shape
    sw = w * packetsize
    if L % sw:
        raise ValueError(f"chunk length {L} not a multiple of w*packetsize={sw}")
    nw = L // sw
    x = data.reshape(*lead, k, nw, w, packetsize)
    x = np.moveaxis(x, -4, -3)  # [..., nw, k, w, ps]
    return x.reshape(*lead, nw, k * w, packetsize)


def packets_to_bytes(pk: np.ndarray, w: int, packetsize: int) -> np.ndarray:
    *lead, nw, mw, ps = pk.shape
    m = mw // w
    x = pk.reshape(*lead, nw, m, w, ps)
    x = np.moveaxis(x, -4, -3)  # [..., m, nw, w, ps]
    return x.reshape(*lead, m, nw * w * ps)


# ---------------------------------------------------------------------------
# numpy backend
# ---------------------------------------------------------------------------

class NumpyBackend:
    """Bit-exact CPU reference backend."""

    name = "numpy"
    supported_widths = None  # None = all widths

    def apply_matrix(self, M: np.ndarray, data: np.ndarray, w: int
                     ) -> np.ndarray:
        """byte layout: out[..., i, :] = XOR_j M[i,j]*data[..., j, :]."""
        rows, k = M.shape
        words = _as_words(data, w)
        out = np.zeros(words.shape[:-2] + (rows,) + words.shape[-1:],
                       dtype=words.dtype)
        for i in range(rows):
            for j in range(k):
                region_mul_xor(int(M[i, j]), words[..., j, :],
                               out[..., i, :], w)
        ob = _as_bytes(out)
        return ob.reshape(out.shape[:-1] + (-1,))

    def apply_bitmatrix_packets(self, B: np.ndarray, pk: np.ndarray
                                ) -> np.ndarray:
        """packet layout: XOR packets per B [R, C] over pk [..., nw, C, ps]."""
        R = B.shape[0]
        out = np.zeros(pk.shape[:-2] + (R,) + pk.shape[-1:], dtype=np.uint8)
        Bb = B.astype(bool)
        for r in range(R):
            sel = pk[..., Bb[r], :]
            if sel.shape[-2]:
                out[..., r, :] = np.bitwise_xor.reduce(sel, axis=-2)
        return out


# ---------------------------------------------------------------------------
# recovery rows, solved once a process
# ---------------------------------------------------------------------------

class RecoveryRowsCache:
    """The solved rows of ONE code, by (kind, chosen shards, erased
    shards): a bounded LRU shared by every codec of that code in the
    process — the host half of ISA-L's decode-table cache (reference
    isa/ErasureCodeIsaTableCache.cc: one table cache a process, not
    one a PG).  A PG builds its own codec, and a pool whose reads
    complete on whichever k shards answered first (fast_read) meets
    nearly every have-set of C(k+m, k) in every PG: solved per codec
    that is a k x k GF system inverted on the PG's thread for nearly
    every read; solved here it is once a process."""

    def __init__(self, cap: int):
        self.cap = cap
        self._d: OrderedDict = OrderedDict()
        self._lock = threading.Lock()
        self.hits = 0
        self.misses = 0

    def __contains__(self, key) -> bool:
        return key in self._d

    def __len__(self) -> int:
        return len(self._d)

    def get_or_solve(self, key: tuple, solve, *args):
        """The rows under ``key``, a pair (GF rows or None, bit rows).
        ``solve(*args)`` runs on a miss, outside the lock: two threads that
        meet a signature together may both solve it (equal rows, the
        first stored wins), which costs less than a PG thread parked
        behind another's solve."""
        with self._lock:
            hit = self._d.get(key)
            if hit is not None:
                self._d.move_to_end(key)
                self.hits += 1
                return hit
        val = solve(*args)
        for rows in val:
            if rows is not None:         # shared by every codec of the code
                rows.flags.writeable = False
        with self._lock:
            self.misses += 1
            val = self._d.setdefault(key, val)
            self._d.move_to_end(key)
            while len(self._d) > self.cap:
                self._d.popitem(last=False)
        return val


_ROWS_CACHES: dict = {}
_ROWS_CACHES_LOCK = threading.Lock()


def rows_cache_for(k: int, m: int, w: int,
                   bitmatrix: np.ndarray) -> RecoveryRowsCache:
    """The process's cache of the code (k, m, w, bit-matrix): codecs
    of one matrix share it, codecs of different matrices never share
    an entry.  Sized so that a pool's whole have-set space stays
    resident, each have-set under two erased-sets (a read's m absent
    chunks, prewarm's single erasures): 990 entries at k=8 m=4, 2,002
    at k=10 m=4, about 2.3 KiB each; never under 256 nor over 4,096
    (a k=20 m=10 code has 30 million have-sets)."""
    key = (k, m, w, bitmatrix.shape, bitmatrix.tobytes())  # copycheck: ok - cache key over a tiny coding bit-matrix, not payload
    with _ROWS_CACHES_LOCK:
        cache = _ROWS_CACHES.get(key)
        if cache is None:
            cap = max(256, min(2 * math.comb(k + m, k), 4096))
            cache = _ROWS_CACHES[key] = RecoveryRowsCache(cap)
        return cache


def rows_cache_stats() -> dict:
    """Hits, misses and resident entries over every code's cache
    (``dump_device``: recovery_rows_hits / _misses / _entries)."""
    with _ROWS_CACHES_LOCK:
        caches = list(_ROWS_CACHES.values())
    return {"recovery_rows_hits": sum(c.hits for c in caches),
            "recovery_rows_misses": sum(c.misses for c in caches),
            "recovery_rows_entries": sum(len(c) for c in caches)}


# ---------------------------------------------------------------------------
# codec core
# ---------------------------------------------------------------------------

class CodecCore:
    """Executes one erasure code: k data + m coding chunks, either from a
    GF(2^w) coding matrix (layout 'byte') or a GF(2) bitmatrix (layout
    'packet'), single-shot or batched, with decode-matrix caching per
    erasure signature in the process's cache of this code's rows
    (RecoveryRowsCache: the moral equivalent of ISA-L's table cache,
    reference src/erasure-code/isa/ErasureCodeIsaTableCache.cc)."""

    def __init__(self, k: int, m: int, w: int,
                 coding_matrix: Optional[np.ndarray] = None,
                 bitmatrix: Optional[np.ndarray] = None,
                 layout: str = "byte",
                 packetsize: int = 0,
                 backend=None):
        if layout not in ("byte", "packet"):
            raise ValueError(f"unknown layout {layout}")
        if layout == "packet" and packetsize <= 0:
            raise ValueError("packet layout requires packetsize > 0")
        self.k, self.m, self.w = k, m, w
        self.layout = layout
        self.packetsize = packetsize
        self.backend = backend or NumpyBackend()
        self.coding_matrix = None if coding_matrix is None \
            else np.asarray(coding_matrix, dtype=np.int64)
        if bitmatrix is None:
            if self.coding_matrix is None:
                raise ValueError("need coding_matrix or bitmatrix")
            bitmatrix = matrix_to_bitmatrix(self.coding_matrix, w)
        self.bitmatrix = np.asarray(bitmatrix, dtype=np.uint8)
        self._decode_cache = rows_cache_for(k, m, w, self.bitmatrix)

    def gf8_encode_fast(self) -> bool:
        """Single source of truth for the w=8 XOR-chain eligibility:
        byte-domain, a GF coding matrix in hand, and a backend whose
        platform makes per-matrix static compilation worthwhile."""
        return (self.layout == "byte" and self.w == 8
                and self.coding_matrix is not None
                and hasattr(self.backend, "apply_gf8_matrix")
                and self.backend.gf8_fast_path())

    def gf8_decode_fast(self) -> bool:
        """Decode twin of gf8_encode_fast: inverse rows vary per erasure
        signature, but the signature set is tiny (C(k+m, <=m)) and a
        rebuild hammers one signature, so per-signature compiled chains
        behind the backend's ChainLRU beat the runtime-argument
        bit-plane path (VERDICT r2: that gap was 64x)."""
        return (self.layout == "byte" and self.w == 8
                and self.coding_matrix is not None
                and hasattr(self.backend, "apply_gf8_rows")
                and self.backend.gf8_fast_path())

    def packet_static_fast(self) -> bool:
        """Packet-layout analog: static XOR schedules (smart-scheduling
        style) compiled per bitmatrix, for encode and decode."""
        return (self.layout == "packet"
                and hasattr(self.backend, "apply_packet_xor")
                and self.backend.gf8_fast_path())

    # -- encode -----------------------------------------------------------
    def encode_batch(self, data: np.ndarray) -> np.ndarray:
        """data uint8 [..., k, L] -> parity uint8 [..., m, L]."""
        if data.shape[-2] != self.k:
            raise ValueError(f"expected {self.k} data chunks")
        if data.shape[-1] == 0:      # empty object: parity is empty too
            return np.zeros(data.shape[:-2] + (self.m, 0),
                            dtype=np.uint8)
        if self.gf8_encode_fast():
            return self.backend.apply_gf8_matrix(self.coding_matrix,
                                                 data)
        return self._apply(self.bitmatrix, self.coding_matrix, data)

    def encode(self, data: np.ndarray) -> np.ndarray:
        return self.encode_batch(data)

    def delta_parity(self, delta: np.ndarray,
                     dirty_cols) -> np.ndarray:
        """Parity delta for a partial-stripe overwrite: linearity
        gives ``new_parity = old_parity XOR M[:,dirty]·Δdata``
        (Δdata = old XOR new), so only the dirty data columns ride the
        matmul.  delta uint8 [..., D, L] for D = len(dirty_cols) ->
        Δparity uint8 [..., m, L].  A packet-layout code is linear
        over GF(2) region by region, so the same holds for whole
        chunks of whole regions (w*packetsize bytes)."""
        cols = list(dirty_cols)
        if delta.shape[-2] != len(cols):
            raise ValueError(f"expected {len(cols)} dirty columns")
        if delta.shape[-1] == 0:
            return np.zeros(delta.shape[:-2] + (self.m, 0),
                            dtype=np.uint8)
        if self.gf8_encode_fast() or self.packet_static_fast():
            # compiled backends: scatter Δ into a zero [..., k, L]
            # block and reuse the per-pool encode kernel (zero
            # columns are inert) — a per-dirty-signature kernel
            # would pay a fresh XLA compile for every (signature,
            # shape) pair the overwrite mix sprays at it
            block = np.zeros(
                delta.shape[:-2] + (self.k, delta.shape[-1]),
                dtype=np.uint8)
            block[..., cols, :] = delta
            return self.encode_batch(block)
        if self.layout == "packet":
            bitcols = [c * self.w + j for c in cols
                       for j in range(self.w)]
            return self._apply(
                np.ascontiguousarray(self.bitmatrix[:, bitcols]), None,
                delta)
        if self.coding_matrix is None:
            raise ValueError("delta parity needs a GF coding matrix "
                             "or a packet-layout bit-matrix")
        sub = np.ascontiguousarray(self.coding_matrix[:, cols])
        return self._apply(matrix_to_bitmatrix(sub, self.w), sub,
                           delta)

    def _apply(self, B: np.ndarray, M: Optional[np.ndarray],
               data: np.ndarray) -> np.ndarray:
        if self.layout == "byte":
            widths = getattr(self.backend, "supported_widths", None)
            if widths is not None and self.w not in widths:
                return self._apply_bitmatrix_bytes(B, data)
            if hasattr(self.backend, "apply_bitmatrix_bytes"):
                return self.backend.apply_bitmatrix_bytes(B, data, self.w)
            if M is not None:
                return self.backend.apply_matrix(M, data, self.w)
            return self._apply_bitmatrix_bytes(B, data)
        if self.packet_static_fast():
            return self.backend.apply_packet_xor(B, data, self.w,
                                                 self.packetsize)
        if hasattr(self.backend, "apply_packet_chunks"):
            return self.backend.apply_packet_chunks(B, data, self.w,
                                                    self.packetsize)
        pk = bytes_to_packets(data, self.w, self.packetsize)
        out = self.backend.apply_bitmatrix_packets(B, pk)
        return packets_to_bytes(out, self.w, self.packetsize)

    def _apply_bitmatrix_bytes(self, B: np.ndarray, data: np.ndarray
                               ) -> np.ndarray:
        bits = bytes_to_bitplanes(data, self.w)
        out = np.matmul(B.astype(np.int64), bits.astype(np.int64)) & 1
        return bitplanes_to_bytes(out.astype(np.uint8), self.w)

    # -- decode -----------------------------------------------------------
    def chunk_size_multiple(self) -> int:
        """Chunk length must be a multiple of this for the layout."""
        if self.layout == "byte":
            return self.w // 8 if self.w >= 8 else 1
        return self.w * self.packetsize

    def decode_chunks(self, present: dict[int, np.ndarray],
                      chunk_len: int) -> dict[int, np.ndarray]:
        """Reconstruct every missing chunk id in 0..k+m-1.

        `present` maps chunk id -> uint8 array [..., L] (leading batch axes
        allowed but must agree); every chunk must be `chunk_len` long."""
        for i, c in present.items():
            if c.shape[-1] != chunk_len:
                raise ValueError(
                    f"chunk {i} length {c.shape[-1]} != {chunk_len}")
        n = self.k + self.m
        erased = [i for i in range(n) if i not in present]
        if not erased:
            return {}
        avail = sorted(present.keys())
        if len(avail) < self.k:
            raise ValueError("not enough chunks to decode")
        if chunk_len == 0:           # empty object: all chunks empty
            shape = next(iter(present.values())).shape
            return {e: np.zeros(shape, dtype=np.uint8) for e in erased}
        # combined recovery rows: ONE matrix maps the chosen k
        # survivors straight to every erased chunk (data AND parity),
        # so the whole reconstruction is a single apply — one device
        # dispatch per batch instead of a decode apply chained into a
        # re-encode apply
        chosen = tuple(avail[:self.k])
        rows_gf, rows_bits = self._recovery_rows(chosen, tuple(erased))
        stack = np.stack([present[i] for i in chosen], axis=-2)
        if rows_gf is not None and self.gf8_decode_fast():
            dec = self.backend.apply_gf8_rows(rows_gf, stack)
        else:
            dec = self._apply(rows_bits, rows_gf, stack)
        return {e: dec[..., idx, :] for idx, e in enumerate(erased)}

    def _recovery_rows(self, chosen: tuple, erased: tuple):
        """(GF rows or None, bit rows) mapping the chosen k survivors
        to EVERY erased chunk id — data rows come straight from the
        inverse map R (chosen -> data), parity row e >= k composes the
        encode row through it: coding_matrix[e-k] · R over GF(2^w),
        or, for a code that has only its bit-matrix, bitmatrix[e-k's w
        rows] · Rbits over GF(2).  Cached per erasure signature; this
        is the matrix the device decode pipeline jit-caches per
        (geometry, erasure-set)."""
        return self._decode_cache.get_or_solve(
            ("rec", chosen, erased), self._solve_recovery_rows, chosen,
            erased)

    def _solve_recovery_rows(self, chosen: tuple, erased: tuple):
        """A miss of _recovery_rows: the k x k system of ``chosen``
        inverted and expanded to bits, inside ``ec.solve_rows``."""
        w = self.w
        with section("ec.solve_rows", k=self.k, erased=len(erased)):
            if self.coding_matrix is not None:
                R = make_decoding_matrix(self.coding_matrix, w,
                                         list(chosen))
                f = gf(w)
                rows = [R[e] if e < self.k else
                        f.matmul(self.coding_matrix[e - self.k][None, :],
                                 R)[0]
                        for e in erased]
                rows_gf = np.stack(rows, axis=0).astype(np.int64)
                return rows_gf, matrix_to_bitmatrix(rows_gf, w)
            _, Rbits = self._solve_decode_rows(chosen,
                                               tuple(range(self.k)))
            # [I; B] · Rbits over GF(2): chunk e's w rows of it
            full = np.concatenate(
                [Rbits, (self.bitmatrix.astype(np.int64)
                         @ Rbits.astype(np.int64) & 1).astype(np.uint8)],
                axis=0)
            return None, np.concatenate(
                [full[e * w:(e + 1) * w] for e in erased], axis=0)

    def _decode_rows(self, chosen: tuple, data_erased: tuple):
        """(GF rows or None, bit rows) mapping chosen chunks -> erased data
        chunks; cached per erasure signature."""
        return self._decode_cache.get_or_solve(
            ("dec", chosen, data_erased), self._solve_decode_rows, chosen,
            data_erased)

    def _solve_decode_rows(self, chosen: tuple, data_erased: tuple):
        """A miss of _decode_rows (and the inverse a bit-matrix-only
        code's recovery rows are composed through)."""
        if self.coding_matrix is not None:
            R = make_decoding_matrix(self.coding_matrix, self.w, list(chosen))
            rows_gf = R[list(data_erased)]
            return rows_gf, matrix_to_bitmatrix(rows_gf, self.w)
        kw = self.k * self.w
        Gbits = np.concatenate([np.eye(kw, dtype=np.uint8),
                                self.bitmatrix], axis=0)
        A = np.concatenate(
            [Gbits[c * self.w:(c + 1) * self.w] for c in chosen], axis=0)
        Rbits = bitmatrix_invert(A)
        return None, np.concatenate(
            [Rbits[e * self.w:(e + 1) * self.w] for e in data_erased],
            axis=0)
