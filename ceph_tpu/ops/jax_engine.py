"""JAX/TPU codec engine: erasure codes as batched binary matmuls on the MXU.

The TPU-native design (SURVEY.md section 7, "hard parts"): every GF(2^8)
constant multiply is an 8x8 binary matrix over GF(2), so a k->m
Reed-Solomon code becomes one (8m x 8k) 0/1 matrix M, and encoding a
*batch* of stripes is a single int8 matmul

    parity_bits[b, r, l] = (sum_c M[r, c] * data_bits[b, c, l]) mod 2

which XLA tiles onto the MXU with int32 accumulation — exact, so chunks
are bit-identical to the CPU reference (ceph_tpu/ops/engine.py).  The
same kernel executes every codec family:

* byte-domain GF(2^w) matrix codes (reed_sol_van/r6): contraction axis =
  the w bits of each GF word (replaces jerasure_matrix_encode,
  reference ErasureCodeJerasure.cc:162);
* packet-domain bitmatrix codes (cauchy/liberation families):
  contraction axis = the k*w packets per super-word (replaces
  jerasure_schedule_encode, reference ErasureCodeJerasure.cc:265).

Decode uses the same kernel with per-erasure-signature inverse rows,
cached like ISA-L's decode-table LRU (reference
isa/ErasureCodeIsaTableCache.cc).  On a TPU a row set is an OPERAND of
its kernel family's one program (rows_program, BoundRows): the number
of executables follows the shapes dispatched, not the signatures.

Shapes are bucketed (batch to the next power of two, length to a lane
multiple) so the jit cache stays small while the OSD feeds variable-size
stripe batches from the PG write queue.
"""
from __future__ import annotations

import functools
import threading
import time
from collections import OrderedDict
from typing import Optional

import numpy as np

import jax
import jax.numpy as jnp

from ..utils.tracer import section

# Lane-friendly length quantum: last dim tiles of 128 on TPU.
LENGTH_QUANTUM = 128


class ChainLRU:
    """LRU of per-signature chains — the moral equivalent of ISA-L's
    decode-table cache (reference
    isa/ErasureCodeIsaTableCache.cc:253-306): erasure signatures are few
    (C(k+m, <=m)) and recovery hammers one signature for a whole rebuild.
    An entry is a one-argument callable: a row set bound to its family's
    shared program (a BoundRows with ``bits``; nothing compiles per
    entry) or, off a TPU and on a mesh, a static program of its own.
    ``cap`` bounds what costs memory, the static programs.  A binding
    is about 2 KiB of device memory and no executable, and a pool
    whose reads complete on whichever k shards answer first (fast_read)
    dispatches up to C(k+m, k) have-sets in a steady state, 495 at k=8
    m=4: ``bindings_cap`` is as wide as the process's cache of solved
    rows (engine.RecoveryRowsCache), so a pool's bindings stay
    resident where ``cap`` alone evicted and rebound them for ever."""

    def __init__(self, cap: int = 256, bindings_cap: int = 4096):
        self.cap = cap
        self.bindings_cap = bindings_cap
        self._d: OrderedDict = OrderedDict()
        self._count = {False: 0, True: 0}    # static programs, bindings
        self._lock = threading.Lock()
        # per-key in-progress markers: builder() is a full jit
        # trace+compile (seconds), so it must run OUTSIDE the lock —
        # one compile per key, but compiles of DIFFERENT signatures
        # (other pools/geometries) proceed concurrently instead of
        # serializing every first-use behind one lock
        self._building: dict = {}

    @staticmethod
    def _is_binding(val) -> bool:
        return getattr(val, "bits", None) is not None

    def _insert_locked(self, key, val) -> None:
        """Newest last; past its kind's bound the oldest of that kind
        goes (static programs by ``cap``, bindings by
        ``bindings_cap``)."""
        self._d[key] = val
        self._d.move_to_end(key)
        light = self._is_binding(val)
        self._count[light] += 1
        while self._count[light] > (self.bindings_cap if light
                                    else self.cap):
            oldest = next(k for k, v in self._d.items()
                          if self._is_binding(v) == light)
            del self._d[oldest]
            self._count[light] -= 1

    def get_or_build(self, key, builder):
        while True:
            with self._lock:
                hit = self._d.get(key)
                if hit is not None:
                    self._d.move_to_end(key)
                    return hit
                ev = self._building.get(key)
                if ev is None:
                    ev = threading.Event()
                    self._building[key] = ev
                    owner = True
                else:
                    owner = False
            if not owner:
                # another thread compiles this signature; wait and
                # re-check (it may have failed — then we take over)
                ev.wait()
                continue
            try:
                val = builder()
            except BaseException:
                with self._lock:
                    self._building.pop(key, None)
                ev.set()
                raise
            with self._lock:
                self._insert_locked(key, val)
                self._building.pop(key, None)
            ev.set()
            return val


def _bits_of_bytes(x: jnp.ndarray) -> jnp.ndarray:
    """uint8[..., L] -> int8 bits [..., 8, L] (bit b of each byte)."""
    shifts = jnp.arange(8, dtype=jnp.uint8).reshape((8,) + (1,) * 1)
    bits = (x[..., None, :] >> shifts) & jnp.uint8(1)
    return bits.astype(jnp.int8)


def _bytes_of_bits(bits: jnp.ndarray) -> jnp.ndarray:
    """int32/int8 bits [..., 8, L] -> uint8 [..., L]."""
    weights = (jnp.uint8(1) << jnp.arange(8, dtype=jnp.uint8))
    return jnp.sum(bits.astype(jnp.uint8) * weights[..., :, None],
                   axis=-2).astype(jnp.uint8)


def _words_from_bytes(x: jnp.ndarray, wbytes: int) -> jnp.ndarray:
    """uint8[..., L] -> uint{8*wbytes}[..., L/wbytes] little-endian,
    built arithmetically (portable across backends)."""
    if wbytes == 1:
        return x
    dt = {2: jnp.uint16, 4: jnp.uint32}[wbytes]
    parts = [x[..., i::wbytes].astype(dt) << (8 * i) for i in range(wbytes)]
    return functools.reduce(jnp.bitwise_or, parts)


def _bytes_from_words(words: jnp.ndarray, wbytes: int) -> jnp.ndarray:
    if wbytes == 1:
        return words
    parts = [((words >> (8 * i)) & 0xFF).astype(jnp.uint8)
             for i in range(wbytes)]
    stacked = jnp.stack(parts, axis=-1)  # [..., Lw, wbytes]
    return stacked.reshape(stacked.shape[:-2] + (-1,))


def _xtime(x: jnp.ndarray) -> jnp.ndarray:
    """GF(2^8) multiply-by-x modulo the jerasure polynomial 0x11D."""
    hi = x >> jnp.uint8(7)
    return ((x << 1) & jnp.uint8(0xFF)) ^ (hi * jnp.uint8(0x1D))


def _gf8_chain(data: jnp.ndarray, coeffs) -> jnp.ndarray:
    """GF(2^8) matrix apply as a fused XOR/xtime chain — the portable
    byte-domain w=8 kernel off-TPU (on TPU the fused bit-plane MXU
    pallas kernel serves — see gf8_kernel).

    Each constant multiply unrolls to xtime shifts + XORs on uint8
    lanes (one fused elementwise kernel; XLA CSEs the shared xtime
    powers of each data chunk across output rows), bit-exact with
    jerasure.  ``coeffs`` is a static tuple-of-tuples [rows][k]: coding
    matrices are per-pool constants and decode inverse rows are cached
    per erasure signature (ChainLRU), so each compiles once."""
    def gfmul_const(a: int, x):
        acc = None
        cur = x
        for j in range(8):
            if (a >> j) & 1:
                acc = cur if acc is None else acc ^ cur
            if j < 7:
                cur = _xtime(cur)
        return acc

    outs = []
    for row in coeffs:
        acc = None
        for c, a in enumerate(row):
            if a == 0:
                continue
            t = gfmul_const(int(a), data[..., c, :])
            acc = t if acc is None else acc ^ t
        outs.append(acc if acc is not None
                    else jnp.zeros_like(data[..., 0, :]))
    return jnp.stack(outs, axis=-2)


_apply_gf8_xor = functools.partial(jax.jit, static_argnames=("coeffs",))(
    _gf8_chain)


def build_xor_schedule(B: np.ndarray) -> tuple:
    """Greedy delta schedule for a GF(2) bitmatrix: output row i is
    either XOR-ed from scratch, or derived from an earlier output row
    XOR the differing inputs — jerasure's 'smart scheduling' for the
    cauchy/liberation families (reference ErasureCodeJerasure.cc:265
    jerasure_smart_bitmatrix_to_schedule), recast as a static compile
    schedule.  Entry = (prev_row_or_-1, (input cols to XOR...))."""
    sets = [frozenset(np.nonzero(np.asarray(r))[0].tolist()) for r in B]
    sched = []
    for i, s in enumerate(sets):
        best_j, best_cost = -1, len(s)
        for j in range(i):
            d = len(sets[j] ^ s) + 1
            if d < best_cost:
                best_cost, best_j = d, j
        if best_j >= 0:
            sched.append((best_j, tuple(sorted(sets[best_j] ^ s))))
        else:
            sched.append((-1, tuple(sorted(s))))
    return tuple(sched)


def _packet_xor_rows(pk: jnp.ndarray, schedule) -> jnp.ndarray:
    """Apply an XOR schedule over packet rows: pk [..., C, ps] ->
    [..., R, ps].  Pure uint8 XOR on the VPU — no bit expansion, no
    int32 accumulator; bit-exact with the bitmatrix matmul."""
    outs = []
    for prev, cols in schedule:
        acc = outs[prev] if prev >= 0 else None
        for c in cols:
            t = pk[..., c, :]
            acc = t if acc is None else acc ^ t
        if acc is None:
            acc = jnp.zeros_like(pk[..., 0, :])
        outs.append(acc)
    return jnp.stack(outs, axis=-2)


def _packet_chain(data: jnp.ndarray, schedule, w: int,
                  packetsize: int) -> jnp.ndarray:
    """data uint8 [batch, k, L] -> uint8 [batch, R/w, L] via a static
    XOR schedule in packet layout (L = nw * w * packetsize)."""
    batch, k, L = data.shape
    sw = w * packetsize
    nw = L // sw
    x = data.reshape(batch, k, nw, w, packetsize)
    x = jnp.transpose(x, (0, 2, 1, 3, 4)).reshape(batch, nw, k * w,
                                                  packetsize)
    out = _packet_xor_rows(x, schedule)  # [batch, nw, R, ps]
    R = len(schedule)
    m_out = R // w
    out = out.reshape(batch, nw, m_out, w, packetsize)
    out = jnp.transpose(out, (0, 2, 1, 3, 4))
    return out.reshape(batch, m_out, nw * sw)


def _packet_mxu_pallas(bits, data, *, w: int, packetsize: int,
                       interpret: bool = False):
    """Fused MXU kernel for packet-layout bitmatrix codes: the row set
    ``bits`` int8 [R, k*w] (an OPERAND: the pool's coding bit-matrix or
    one erasure signature's recovery rows) applied to ``data`` uint8
    [batch, k, L] -> uint8 [batch, R/w, L], with L = nw * w * ps.

    The packet apply is out_row[r] = XOR of the k*w input packets
    selected by bitmatrix row r — per OUTPUT BIT j that is a mod-2
    matmul of B [R, k*w] against bit-plane j of the packets.  One
    VMEM-resident pass per super-word: extract the 8 bit-planes of the
    [k*w, ps] packet block, ONE int8 dot_general over all planes at
    once ([R, k*w] @ [k*w, 8*ps], mod 2 via the int32 accumulator's
    low bit), repack to bytes.  On TPU it replaces the static
    XOR-schedule chain (_packet_chain), which serializes ~fan-in short
    VPU ops per output row; cauchy-family decode (and with it rebuild
    MB/s) is bound by exactly this kernel.  Bit-exact with the CPU
    oracle: bit j of an XOR of bytes is the mod-2 sum of the operands'
    bit j (reference jerasure_schedule_encode / jerasure_matrix_decode,
    erasure-code/jerasure/ErasureCodeJerasure.cc:170,265 — same
    transform, dense instead of scheduled)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, KW = bits.shape
    m_out = R // w
    ps = packetsize
    batch, k_, L = data.shape
    sw = w * ps
    nw = L // sw
    # tile a contiguous RUN of super-words per grid step (largest
    # divisor of nw within the VMEM budget): a one-super-word
    # block would fragment every HBM read into k*w strided
    # ``ps``-byte pieces, which measured ~2.5x below the device's
    # streaming rate — the contiguous run keeps reads at
    # TB*w*ps-byte granularity, same idea as the byte-domain
    # kernel's _pick_block_len
    budget = max(1, (4 << 20) // (k_ * sw))
    TB = 1
    for t in range(1, min(nw, budget) + 1):
        if nw % t == 0:
            TB = t
    xin = data.reshape(batch, k_, nw, w, ps)

    def kernel(b_ref, in_ref, out_ref):
        for t in range(TB):
            x = in_ref[0, :, t, :, :].reshape(KW, ps)  # [k*w, ps]
            planes = [((x & jnp.uint8(1 << j)) != 0).astype(jnp.int8)
                      for j in range(8)]
            bits_in = jnp.concatenate(planes, axis=1)  # [k*w, 8*ps]
            pb = jax.lax.dot_general(
                b_ref[:, :], bits_in, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)      # [R, 8*ps]
            acc = None
            for j in range(8):
                v = (pb[:, j * ps:(j + 1) * ps] & 1) << j
                acc = v if acc is None else acc | v
            out_ref[0, :, t, :, :] = acc.astype(jnp.uint8).reshape(
                m_out, w, ps)

    out = pl.pallas_call(
        kernel,
        grid=(batch, nw // TB),
        in_specs=[pl.BlockSpec((R, KW), lambda b, i: (0, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, k_, TB, w, ps),
                               lambda b, i: (b, 0, i, 0, 0),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, m_out, TB, w, ps),
                               lambda b, i: (b, 0, i, 0, 0),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((batch, m_out, nw, w, ps),
                                       jnp.uint8),
        interpret=interpret,
    )(bits, xin)
    return out.reshape(batch, m_out, L)


def _pick_block_len(L: int, cap: int = 1 << 19) -> int:
    """Largest 128-multiple divisor of L that is <= cap (VMEM budget)."""
    best = 128
    t = 128
    while t <= min(L, cap):
        if L % t == 0:
            best = t
        t *= 2
    return best


def gf_plane_bits(B: np.ndarray, k: int, w: int) -> np.ndarray:
    """A byte-domain bit-matrix [R, k*w] in the order _gf_mxu_pallas
    takes it (built on the host, once per row set): cols
    (c*w+j)->(j*k+c), rows (e*w+i)->(i*m_out+e), so the kernel
    extracts/packs whole [k, T] planes instead of skinny rows."""
    m_out = B.shape[0] // w
    colp = [c * w + j for j in range(w) for c in range(k)]
    rowp = [e * w + i for i in range(w) for e in range(m_out)]
    return np.asarray(B[np.ix_(rowp, colp)], dtype=np.int8)


def _gf_mxu_pallas(bits, data, *, w: int, interpret: bool = False):
    """Fused bit-plane MXU kernel for byte-domain GF(2^w) codes: the
    row set ``bits`` int8 [R, k*w] in plane order (gf_plane_bits; an
    OPERAND, like _packet_mxu_pallas's) applied to ``data`` uint8
    [batch, k, L] -> uint8 [batch, R/w, L].

    One VMEM-resident pass per block: extract bit-planes (wide [k, T]
    compares), one int8 dot_general on the MXU (mod-2 via the int32
    accumulator's low bit), pack parity bits back to bytes — no HBM
    round trips for the 8x-inflated bit tensors that make the unfused
    XLA path traffic-bound.
    Bit-exact with the CPU oracle; serves encode (per-pool coding
    bitmatrix) and decode (per-erasure-signature inverse rows)."""
    from jax.experimental import pallas as pl
    from jax.experimental.pallas import tpu as pltpu

    R, KW = bits.shape
    m_out = R // w
    TB = 16384
    batch, k_, L = data.shape
    # pad to a 128-multiple so the block length always divides L
    # (zeros are harmless: the code is GF-linear); callers that
    # pre-pad (host entry points, stage()) hit the no-op branch
    Lp = _round_up(max(L, 128), 128)
    if Lp != L:
        data = jnp.pad(data, ((0, 0), (0, 0), (0, Lp - L)))
    Lb = _pick_block_len(Lp)
    tb = min(TB, Lb)

    def kernel(b_ref, in_ref, out_ref):
        for t in range(Lb // tb):
            x = in_ref[0, :, t * tb:(t + 1) * tb]       # [k, tb] u8
            planes = [((x & jnp.uint8(1 << j)) != 0).astype(jnp.int8)
                      for j in range(w)]
            bits_in = jnp.concatenate(planes, axis=0)   # [w*k, tb]
            pb = jax.lax.dot_general(
                b_ref[:, :], bits_in, (((1,), (0,)), ((), ())),
                preferred_element_type=jnp.int32)       # [R, tb]
            acc = None
            for i in range(w):
                v = (pb[i * m_out:(i + 1) * m_out, :] & 1) << i
                acc = v if acc is None else acc | v
            out_ref[0, :, t * tb:(t + 1) * tb] = acc.astype(jnp.uint8)

    out = pl.pallas_call(
        kernel,
        grid=(batch, Lp // Lb),
        in_specs=[pl.BlockSpec((R, KW), lambda b, i: (0, 0),
                               memory_space=pltpu.VMEM),
                  pl.BlockSpec((1, k_, Lb), lambda b, i: (b, 0, i),
                               memory_space=pltpu.VMEM)],
        out_specs=pl.BlockSpec((1, m_out, Lb), lambda b, i: (b, 0, i),
                               memory_space=pltpu.VMEM),
        out_shape=jax.ShapeDtypeStruct((batch, m_out, Lp), jnp.uint8),
        interpret=interpret,
    )(bits, data)
    return out[:, :, :L] if Lp != L else out


@functools.lru_cache(maxsize=None)
def rows_program(kernel: str, w: int, packetsize: int = 0,
                 donate: bool = False, interpret: bool = False):
    """THE jitted program of a Pallas kernel family: ``(bits, data) ->
    out`` with the row set an operand, so jit builds one executable
    per (family, row-set shape, input shape) and every erasure
    signature of that shape runs it — a pool's encode matrix and the
    recovery rows of a read that gathered k shards (m rows either way)
    share one.  The closures' names are the XLA modules':
    jit_gf8_mxu_pallas, jit_packet_mxu_pallas (benchmark/kernels)."""
    if kernel == "gf_mxu_pallas":
        def gf8_mxu_pallas(bits, data):
            return _gf_mxu_pallas(bits, data, w=w, interpret=interpret)
        fn = gf8_mxu_pallas
    elif kernel == "packet_mxu_pallas":
        def packet_mxu_pallas(bits, data):
            return _packet_mxu_pallas(bits, data, w=w,
                                      packetsize=packetsize,
                                      interpret=interpret)
        fn = packet_mxu_pallas
    else:
        raise ValueError(f"no row-operand program for kernel {kernel!r}")
    return jax.jit(fn, donate_argnums=(1,) if donate else ())


def gf8_kernel() -> str:
    """Which kernel serves a byte-domain w=8 row set here.  Kernel
    choice is by platform (and, for packet codes, geometry) only: on a
    TPU the Pallas kernel is THE path, and a Mosaic refusal at some
    block shape raises to the caller instead of quietly handing the
    geometry to an XLA chain."""
    return "gf_mxu_pallas" if jax.default_backend() == "tpu" \
        else "gf8_xor_chain"


def packet_kernel(packetsize: int) -> str:
    """Which kernel serves a packet-layout bitmatrix here: the fused
    MXU kernel on TPU for lane-aligned packets, the XLA XOR-schedule
    chain otherwise (same rule as gf8_kernel: no probe, no silent
    switch)."""
    if jax.default_backend() == "tpu" and packetsize % 128 == 0:
        return "packet_mxu_pallas"
    return "packet_xor_chain"


def gf8_inner(rows: np.ndarray):
    """Unjitted traceable kernel for ONE GF(2^8) row set [.., C, L] ->
    [.., R, L], the rows a constant of the caller's trace: what the
    mesh data plane wraps in shard_map (parallel/mesh.py
    sharded_rows_fn, one program per row set still) and what serves a
    row set off a TPU.  Same routing as JaxBackend.gf8_fn: the fused
    MXU pallas kernel on TPU, the XOR/xtime elementwise chain
    elsewhere."""
    rows = np.asarray(rows, dtype=np.int64)
    if gf8_kernel() == "gf_mxu_pallas":
        from .matrix import matrix_to_bitmatrix
        bits = jnp.asarray(gf_plane_bits(matrix_to_bitmatrix(rows, 8),
                                         rows.shape[1], 8))
        return functools.partial(_gf_mxu_pallas, bits, w=8)
    coeffs = tuple(tuple(int(v) for v in row) for row in rows)
    return functools.partial(_gf8_chain, coeffs=coeffs)


def _matmul_mod2(B: jnp.ndarray, bits: jnp.ndarray) -> jnp.ndarray:
    """B int8 [R, C] @ bits int8 [batch, C, L] -> int8 [batch, R, L] mod 2.
    int8 x int8 -> int32 rides the MXU on TPU."""
    out = jax.lax.dot_general(
        B, bits,
        dimension_numbers=(((1,), (1,)), ((), ())),
        preferred_element_type=jnp.int32)  # [R, batch, L]
    out = jnp.transpose(out, (1, 0, 2))
    return (out & 1).astype(jnp.int8)


@functools.partial(jax.jit, static_argnames=("w",), donate_argnums=())
def _apply_byte_domain(B: jnp.ndarray, data: jnp.ndarray, w: int
                       ) -> jnp.ndarray:
    """data uint8 [batch, k, L] -> uint8 [batch, R/w, L] for a GF(2^w)
    matrix code expanded to bit-planes."""
    batch, k, L = data.shape
    wbytes = max(1, w // 8)
    words = _words_from_bytes(data, wbytes)  # [batch, k, Lw]
    shifts = jnp.arange(w, dtype=words.dtype)
    bits = (words[..., None, :] >> shifts[:, None]) & 1  # [batch, k, w, Lw]
    bits = bits.astype(jnp.int8).reshape(batch, k * w, -1)
    out_bits = _matmul_mod2(B, bits)  # [batch, R, Lw]
    R = out_bits.shape[1]
    m = R // w
    out_bits = out_bits.reshape(batch, m, w, -1)
    weights = (jnp.uint32(1) << jnp.arange(w, dtype=jnp.uint32))
    out_words = jnp.sum(out_bits.astype(jnp.uint32) * weights[:, None],
                        axis=-2)
    dt = {8: jnp.uint8, 16: jnp.uint16, 32: jnp.uint32}[w]
    return _bytes_from_words(out_words.astype(dt), wbytes)


@functools.partial(jax.jit, static_argnames=("w", "packetsize"))
def _apply_packet_domain(B: jnp.ndarray, data: jnp.ndarray, w: int,
                         packetsize: int) -> jnp.ndarray:
    """data uint8 [batch, k, L] -> uint8 [batch, R/w, L] for a packet-layout
    bitmatrix code (L = nw * w * packetsize)."""
    batch, k, L = data.shape
    sw = w * packetsize
    nw = L // sw
    x = data.reshape(batch, k, nw, w, packetsize)
    x = jnp.transpose(x, (0, 2, 1, 3, 4)).reshape(batch * nw, k * w,
                                                  packetsize)
    bits = _bits_of_bytes(x)  # [batch*nw, k*w, 8, ps]
    bits = jnp.transpose(bits, (0, 1, 3, 2)).reshape(batch * nw, k * w,
                                                     packetsize * 8)
    out = _matmul_mod2(B, bits)  # [batch*nw, R, ps*8]
    R = out.shape[1]
    out = out.reshape(batch * nw, R, packetsize, 8)
    out = jnp.transpose(out, (0, 1, 3, 2))  # [.., R, 8, ps]
    ob = _bytes_of_bits(out)  # [batch*nw, R, ps]
    m = R // w
    ob = ob.reshape(batch, nw, m, w, packetsize)
    ob = jnp.transpose(ob, (0, 2, 1, 3, 4))
    return ob.reshape(batch, m, L)


def _round_up(x: int, q: int) -> int:
    return ((x + q - 1) // q) * q


def _bucket_batch(b: int) -> int:
    if b <= 1:
        return 1
    return 1 << (b - 1).bit_length()


def _fetch(out, batch: int, L: int) -> np.ndarray:
    """Join a dispatched output and bring it to the host, trimmed."""
    with section("dispatch.wait"):
        out.block_until_ready()
    with section("dispatch.d2h", bytes=out.nbytes):
        return np.asarray(out)[:batch, :, :L]


def _call_section(kernel: str, fn):
    """The ``dispatch.call`` section of one dispatch, with whether the
    row set's binding has run before (``bound=hit``) or is called here
    for the first time (``new``: a BoundRows made by this lookup — not
    a compile; what is not a binding reads ``hit``).  The caller adds
    ``rows``, the chunk rows out a stripe, once the call has returned
    its output's shape."""
    return section("dispatch.call", kernel=kernel,
                   bound="hit" if getattr(fn, "calls", 1) else "new")


def _run_sync(kernel: str, fn, padded: np.ndarray, batch: int,
              L: int) -> np.ndarray:
    """The synchronous twin of _staged_put + AsyncBatch.wait: put,
    call, join, fetch."""
    with section("dispatch.h2d", bytes=padded.nbytes,
                 live_bytes=batch * padded.shape[1] * L,
                 batch=padded.shape[0]):
        dev = jnp.asarray(padded)
    with _call_section(kernel, fn) as sec:
        out = fn(dev)
        sec.set_metadata(rows=out.shape[-2])
    return _fetch(out, batch, L)


class BoundRows:
    """What JaxBackend._chain_lru holds for one row set (a pool's
    coding matrix, one erasure signature's recovery rows): the
    one-argument callable [batch, C, L] -> [batch, R, L].

    For a Pallas family it binds the row set's device-resident bits to
    the family's shared program (rows_program), so making one compiles
    nothing and every signature of one shape runs one executable.  A
    static chain (the XOR schedules off a TPU, the mesh's shard_map
    wrapper) is a program of its own per row set: ``bits`` is None and
    ``program`` takes the data alone."""

    __slots__ = ("program", "bits", "calls", "_note")

    def __init__(self, program, bits, note):
        self.program = program
        self.bits = bits
        self.calls = 0          # 0: bound, never run (dispatch.call)
        self._note = note       # JaxBackend._note_program

    def __call__(self, data):
        self.calls += 1
        if self.bits is None:
            self._note((self, data.shape))
            return self.program(data)
        self._note((self.program, self.bits.shape, data.shape))
        return self.program(self.bits, data)


class _StageSlot:
    """One reusable host staging array plus the fence that guards it.

    ``fence`` is the device value computed FROM this slot's last h2d —
    once it is ready the transfer has necessarily consumed the host
    bytes, so the array may be overwritten (correct even when the
    device input buffer was donated to the kernel)."""

    __slots__ = ("host", "fence", "max_l", "max_b")

    def __init__(self, host: np.ndarray):
        self.host = host
        self.fence = None
        self.max_l = 0          # column high-water mark (pad hygiene)
        self.max_b = 0          # row (stripe) high-water mark — mesh
                                # dispatch needs dp-padding rows to be
                                # zero-stripes, not stale stripes


class StagingPool:
    """Persistent per-shape host staging rings (double-buffered h2d).

    Every batched encode used to pay a fresh ``np.zeros`` + a fresh
    ``jax.device_put`` allocation.  The pool keeps ``depth`` reusable
    host arrays per padded [batch, k, L] shape: while slot A's batch
    is still being consumed on device, slot B is filled and staged —
    and re-acquiring A blocks only on A's compute fence, which by then
    has long retired.  Geometry shapes are few (bucketed), so the ring
    set is bounded; a shape LRU caps worst-case footprint.

    The pool also owns the h2d link estimate: every ``sample_every``-th
    staging is fenced end-to-end and folded into a warm-transfer EWMA
    (``h2d_bps``) that the OSD batcher reads for its crossover model —
    replacing the old one-shot cold ``device_put`` measurement that
    folded allocator/jit warmup into the link rate.
    """

    MAX_SHAPES = 16
    STALL_S = 5.0               # acquire() stall cap before the pool
                                # assumes a slot leaked and grows

    def __init__(self, depth: int = 2, sample_every: int = 16):
        self.depth = max(1, int(depth))
        self.sample_every = max(1, int(sample_every))
        self._free: "OrderedDict[tuple, list]" = OrderedDict()
        self._made: dict = {}
        self._cv = threading.Condition()
        self._puts = 0
        self.hits = 0            # stagings served from a reused array
        self.allocs = 0          # host staging arrays ever allocated
        self.stall_allocs = 0    # ring grown after an acquire stall
        self.h2d_bps = 0.0       # warm-transfer EWMA (fenced samples)
        self.h2d_samples = 0
        self.host_bytes = 0      # live host-ring footprint (all rings)
        self.host_bytes_peak = 0

    # -- slot checkout -----------------------------------------------
    def acquire(self, shape: tuple) -> _StageSlot:
        deadline = None
        with self._cv:
            while True:
                free = self._free.get(shape)
                if free is None:
                    free = self._free[shape] = []
                self._free.move_to_end(shape)
                if free:
                    slot = free.pop()
                    self.hits += 1
                    break
                if self._made.get(shape, 0) < self.depth:
                    self._made[shape] = self._made.get(shape, 0) + 1
                    slot = _StageSlot(np.zeros(shape, dtype=np.uint8))
                    self.allocs += 1
                    self._note_alloc_locked(slot.host.nbytes)
                    self._evict_locked()
                    break
                # both slots in flight: wait for a release (bounded
                # wait so a lost notify can't wedge the encode path).
                # Callers release on failure too, but a ring stalled
                # past any plausible fence latency means a slot leaked
                # anyway (e.g. a crashed dispatch path) — grow the
                # ring by one rather than wedge the OSD write path.
                now = time.monotonic()
                if deadline is None:
                    deadline = now + self.STALL_S
                elif now >= deadline:
                    self._made[shape] = self._made.get(shape, 0) + 1
                    slot = _StageSlot(np.zeros(shape, dtype=np.uint8))
                    self.allocs += 1
                    self.stall_allocs += 1
                    self._note_alloc_locked(slot.host.nbytes)
                    self._evict_locked()
                    break
                self._cv.wait(timeout=0.5)
        fence = slot.fence
        if fence is not None:
            slot.fence = None
            try:
                fence.block_until_ready()
            except Exception:
                pass             # deleted/donated fence == retired
        return slot

    def release(self, shape: tuple, slot: _StageSlot, fence) -> None:
        slot.fence = fence
        with self._cv:
            self._free.setdefault(shape, []).append(slot)
            self._cv.notify_all()

    def _note_alloc_locked(self, nbytes: int) -> None:
        self.host_bytes += int(nbytes)
        if self.host_bytes > self.host_bytes_peak:
            self.host_bytes_peak = self.host_bytes

    def _evict_locked(self) -> None:
        # drop the least-recently-used shape's idle ring when the
        # shape set outgrows the cap (only fully-idle shapes qualify)
        while len(self._free) > self.MAX_SHAPES:
            for shape in list(self._free):
                if len(self._free[shape]) >= self._made.get(shape, 0):
                    for s in self._free[shape]:
                        self.host_bytes -= s.host.nbytes
                    del self._free[shape]
                    self._made.pop(shape, None)
                    break
            else:
                return

    # -- h2d link estimate -------------------------------------------
    def should_sample(self) -> bool:
        self._puts += 1
        return self._puts % self.sample_every == 1

    def note_h2d(self, nbytes: int, seconds: float) -> None:
        if seconds <= 0 or nbytes <= 0:
            return
        bps = nbytes / seconds
        self.h2d_bps = bps if self.h2d_bps <= 0 else (
            0.7 * self.h2d_bps + 0.3 * bps)
        self.h2d_samples += 1

    def stats(self) -> dict:
        """Telemetry snapshot for the ``ec_device`` perf subsystem
        (ring occupancy, stall grows, link EWMA).  ``in_flight`` is
        the number of checked-out slots across every shape ring —
        the live h2d/compute occupancy of the staging pool."""
        with self._cv:
            made = sum(self._made.values())
            free = sum(len(v) for v in self._free.values())
            return {"hits": self.hits, "allocs": self.allocs,
                    "stall_allocs": self.stall_allocs,
                    "h2d_bps": self.h2d_bps,
                    "h2d_samples": self.h2d_samples,
                    "shapes": len(self._made),
                    "slots": made,
                    "in_flight": max(0, made - free),
                    "host_bytes": self.host_bytes,
                    "host_bytes_peak": self.host_bytes_peak}

    def set_depth(self, depth: int) -> None:
        """Retarget the per-shape ring depth live (the
        ``ec_tpu_staging_depth`` autotuner seam).  Raising it only
        admits more allocations on future acquires; lowering it only
        stops further growth — slots already made keep cycling
        through the free lists untouched, so in-flight stagings (and
        the encoded bytes) are unaffected.  Waiters are woken since a
        deeper ring may unblock a stalled acquire."""
        depth = max(1, int(depth))
        with self._cv:
            if depth == self.depth:
                return
            self.depth = depth
            self._cv.notify_all()

    def ensure(self, shape: tuple) -> None:
        """Preallocate a full ring for ``shape`` (prewarm path)."""
        with self._cv:
            free = self._free.setdefault(shape, [])
            self._free.move_to_end(shape)
            while self._made.get(shape, 0) < self.depth:
                self._made[shape] = self._made.get(shape, 0) + 1
                slot = _StageSlot(np.zeros(shape, dtype=np.uint8))
                free.append(slot)
                self.allocs += 1
                self._note_alloc_locked(slot.host.nbytes)
            self._evict_locked()


class AsyncBatch:
    """Handle to an in-flight batched encode: the device computation and
    the device->host copy are both dispatched; wait() joins and returns
    the trimmed host array.  Lets the OSD batching layer (and the bench)
    overlap host->device staging, MXU compute, and device->host parity
    fetch across consecutive stripe batches."""

    def __init__(self, dev_out, batch: int, L: int, lead: tuple,
                 ledger: Optional[dict] = None):
        self._dev = dev_out
        self._batch = batch
        self._L = L
        self._lead = lead
        # fenced h2d link sample from the staging pool, when this
        # batch happened to be the sampled one (batcher EWMA feed)
        self.h2d_bytes = 0
        self.h2d_seconds = 0.0
        # device-phase ledger (utils/device_ledger): absolute stamps,
        # finalized by wait(); keyed by JAX device id so lanes are
        # mesh-ready for the multichip promotion
        self.ledger = ledger
        # mesh dispatch: one ledger clone per chip the output is
        # sharded over (same stamps — every chip shares the dispatch
        # window — bytes split per chip), built by wait(); None until
        # then, and None forever on single-device dispatch
        self.ledgers = None
        self._mesh_device_ids = None
        if ledger is not None and "device" not in ledger:
            try:
                ids = sorted(d.id for d in dev_out.sharding.device_set)
            except Exception:
                ids = []
            if len(ids) > 1:
                self._mesh_device_ids = ids
                ledger["device"] = ids[0]
            else:
                try:
                    ledger["device"] = next(iter(dev_out.devices())).id
                except Exception:
                    ledger["device"] = 0

    @property
    def device_ids(self) -> list:
        """Ids of the devices the output is laid out on (one on a
        single chip, every mesh device on a sharded dispatch)."""
        return sorted(d.id for d in self._dev.sharding.device_set)

    def wait(self) -> np.ndarray:
        led = self.ledger
        if led is not None:
            # split the join into its real phases: compute fence,
            # then the d2h materialisation, then the zero-copy trim
            with section("dispatch.wait"):
                try:
                    self._dev.block_until_ready()
                except Exception:
                    pass         # deleted/donated output == retired
            led["compute_done"] = time.time()
            with section("dispatch.d2h", bytes=self._dev.nbytes):
                host = np.asarray(self._dev)
            led["d2h_done"] = time.time()
            out = host[:self._batch, :, :self._L]
            out = out.reshape(self._lead + out.shape[-2:])
            led["deliver"] = time.time()
            led["bytes"] = out.nbytes
            ids = self._mesh_device_ids
            if ids:
                n = len(ids)
                self.ledgers = [dict(led, device=d,
                                     bytes=led["bytes"] // n)
                                for d in ids]
            return out
        out = _fetch(self._dev, self._batch, self._L)
        return out.reshape(self._lead + out.shape[-2:])


class JaxBackend:
    """Backend for CodecCore executing on the default JAX platform.
    A host without a TPU (a monitor validating a profile, tier-1) runs
    the XLA:CPU kernels — chosen by platform, see gf8_kernel; on a TPU
    nothing here falls back to them."""

    name = "jax"

    def __init__(self, bucket_shapes: bool = True):
        self.bucket_shapes = bucket_shapes
        self._dev_matrices: dict = {}
        self._chain_lru = ChainLRU(256)
        self.staging = StagingPool()
        # multichip mesh (ISSUE 12): lazily resolved from the conf
        # knobs on first dispatch.  None on single-device hosts — the
        # single-chip path stays byte-identical with zero overhead.
        self._mesh_conf = (0, 0)      # (n_devices, sp); 0 = auto
        self._mesh = None
        self._mesh_checked = False
        self._mesh_err: Optional[Exception] = None
        self._mesh_sharding = None    # cached NamedSharding(dp, None, sp)
        self.mesh_events: list = []   # mesh_build records for the
                                      # flight recorder (batcher drains)
        # dispatches per kernel name (gf8_kernel / packet_kernel /
        # the XLA bit-plane applies): the evidence of WHICH kernel
        # served, for dump_device and chip_smoke.py
        self.kernel_calls: dict = {}
        self._kernel_lock = threading.Lock()
        # row sets bound to a program (BoundRows made: one per row set
        # the cache holds or held) and the executables those bindings
        # have needed: one per (family, row-set shape, input shape) on
        # the Pallas families, one per (row set, input shape) on a
        # static chain.  dump_device reports both
        self.row_sets_bound = 0
        self.row_programs_built = 0
        self._row_programs: set = set()

    def _note_kernel(self, name: str) -> None:
        with self._kernel_lock:
            self.kernel_calls[name] = self.kernel_calls.get(name, 0) + 1

    def _note_program(self, key: tuple) -> None:
        """A binding is about to run at ``key``: the first time, jit
        builds (or fetches from the persistent cache) an executable."""
        if key not in self._row_programs:
            with self._kernel_lock:
                if key not in self._row_programs:
                    self._row_programs.add(key)
                    self.row_programs_built += 1

    def _bound(self, key: tuple, make) -> BoundRows:
        """The cache's binding under ``key``; ``make() -> (program,
        device bits or None)`` runs for a row set not bound yet."""
        def bind():
            program, bits = make()
            with self._kernel_lock:
                self.row_sets_bound += 1
            return BoundRows(program, bits, self._note_program)
        return self._chain_lru.get_or_build(key, bind)

    # -- staging ring ------------------------------------------------
    def configure_staging(self, depth: int = 0) -> None:
        """Apply the ``ec_tpu_staging_depth`` knob to the live
        StagingPool (mirrors :meth:`configure_mesh`); 0 or negative
        leaves the pool as built."""
        if depth and depth > 0:
            self.staging.set_depth(depth)

    # -- multichip mesh ----------------------------------------------
    def configure_mesh(self, n_devices: int = 0, sp: int = 0) -> None:
        """Set the mesh conf knobs (``ec_tpu_mesh_devices`` /
        ``ec_tpu_mesh_sp``; 0 = auto).  Resets the lazy resolution so
        the next dispatch/prewarm re-probes."""
        conf = (int(n_devices), int(sp))
        if conf != self._mesh_conf:
            self._mesh_conf = conf
            self._mesh = None
            self._mesh_checked = False
            self._mesh_err = None
            self._mesh_sharding = None

    def _resolve_mesh(self, strict: bool = False):
        """The production mesh, or None (single device / probe failed).
        ``strict=True`` (prewarm) re-raises a bad explicit conf as a
        clear ValueError instead of silently falling back — a
        misconfigured mesh must fail at prewarm, not mid-dispatch."""
        if not self._mesh_checked:
            self._mesh_checked = True
            from ..parallel import mesh as pmesh
            try:
                self._mesh = pmesh.resolve_mesh(*self._mesh_conf)
            except Exception as e:
                self._mesh = None
                self._mesh_err = e
            if self._mesh is not None:
                from jax.sharding import NamedSharding, PartitionSpec
                self._mesh_sharding = NamedSharding(
                    self._mesh, PartitionSpec("dp", None, "sp"))
                info = pmesh.mesh_info(self._mesh) or {}
                self.mesh_events.append(
                    dict(info, event="mesh_build", ts=time.time()))
        if strict and self._mesh_err is not None:
            raise ValueError(
                f"mesh configuration invalid "
                f"(ec_tpu_mesh_devices={self._mesh_conf[0]}, "
                f"ec_tpu_mesh_sp={self._mesh_conf[1]}): "
                f"{self._mesh_err}")
        return self._mesh

    def mesh_info(self) -> Optional[dict]:
        """JSON-able dp/sp/device-id summary of the live mesh (admin
        socket ``dump_device`` + bench mesh block), or None."""
        from ..parallel import mesh as pmesh
        return pmesh.mesh_info(self._resolve_mesh())

    def _device_matrix(self, B: np.ndarray) -> jnp.ndarray:
        key = (B.shape, B.tobytes())  # copycheck: ok - cache key over a tiny coding matrix (k*m bytes), not payload
        hit = self._dev_matrices.get(key)
        if hit is None:
            # cast on the host: a transfer, no program of its own
            hit = jax.device_put(np.asarray(B, dtype=np.int8))
            self._dev_matrices[key] = hit
        return hit

    def _device_matrix_mesh(self, B: np.ndarray, mesh) -> jnp.ndarray:
        """Mesh-replicated bitmatrix (P(None, None)) so a sharded jit
        never sees mixed device placements."""
        key = ("mesh", tuple(int(v) for v in np.asarray(mesh.devices).shape),
               B.shape, B.tobytes())  # copycheck: ok - cache key over a tiny coding matrix (k*m bytes), not payload
        hit = self._dev_matrices.get(key)
        if hit is None:
            from jax.sharding import NamedSharding, PartitionSpec
            hit = jax.device_put(
                np.asarray(B, dtype=np.int8),
                NamedSharding(mesh, PartitionSpec(None, None)))
            self._dev_matrices[key] = hit
        return hit

    def _mesh_apply_fn(self, mesh, w: int):
        """Sharded generic-w bitmatrix apply, LRU-cached per (mesh
        shape, w) — the mesh twin of the module-level
        ``_apply_byte_domain`` jit."""
        dp = int(mesh.shape["dp"])
        sp = int(mesh.shape["sp"])
        from ..parallel import mesh as pmesh
        return self._chain_lru.get_or_build(
            ("bmmesh", dp, sp, w),
            lambda: pmesh.sharded_apply_fn(mesh, w))

    def memory_stats(self) -> dict:
        """Footprint snapshot for the memory-accounting gauges: host
        staging rings, device-resident coding matrices (per-geometry),
        and compiled-executable cache occupancy."""
        dev_matrix_bytes = 0
        for m in list(self._dev_matrices.values()):
            try:
                dev_matrix_bytes += int(m.nbytes)
            except Exception:
                pass
        st = self.staging.stats()
        return {
            "staging_host_bytes": st["host_bytes"],
            "staging_host_bytes_peak": st["host_bytes_peak"],
            "staging_slots": st["slots"],
            "dev_matrix_bytes": dev_matrix_bytes,
            "dev_matrix_entries": len(self._dev_matrices),
            "compile_cache_entries": len(self._chain_lru._d),
            "compile_cache_cap": self._chain_lru.cap,
        }

    def _padded(self, data: np.ndarray, quantum: int):
        """Pad [batch, k, L] to bucketed [batch', k, L'] (zeros are
        harmless: the code is GF-linear)."""
        batch, k, L = data.shape
        if not self.bucket_shapes:
            return data, batch, L
        bb = _bucket_batch(batch)
        Lb = _round_up(L, quantum)
        if bb == batch and Lb == L:
            return data, batch, L
        out = np.zeros((bb, k, Lb), dtype=np.uint8)
        out[:batch, :, :L] = data
        return out, batch, L

    def _staged_put(self, data: np.ndarray, quantum: int,
                    shard: bool = True):
        """Pad [batch, k, L] into a persistent staging slot and start
        its h2d.  Returns ``(dev, batch, L, done, sampled, ledger,
        mesh)``; the caller MUST invoke ``done(fence)`` with the device
        value computed from ``dev`` right after dispatch — the fence is
        what lets the slot's host bytes be overwritten by a later
        batch.  Every Nth staging is fenced and timed to keep the
        pool's warm h2d EWMA honest.  ``ledger`` carries the
        device-phase stamps accrued so far (stage_acquire/h2d_*);
        AsyncBatch finalizes it.  ``mesh`` is the live Mesh when the
        batch was placed with the sharded (dp, None, sp) layout — the
        caller must then dispatch the matching sharded kernel — or
        None for the single-chip layout (single-device host, a
        padded length the sp axis cannot shard cleanly, or a caller
        with no sharded kernel: ``shard=False``)."""
        batch, k, L = data.shape
        if not self.bucket_shapes:
            ledger = {"stage_acquire": time.time()}
            ledger["h2d_start"] = ledger["stage_acquire"]
            with section("dispatch.h2d", bytes=data.nbytes,
                         live_bytes=data.nbytes, batch=batch):
                dev = jax.device_put(data)
            ledger["h2d_done"] = time.time()
            return dev, batch, L, None, None, ledger, None
        mesh = self._resolve_mesh() if shard else None
        Lp = _round_up(L, quantum)
        bb = _bucket_batch(batch)
        if mesh is not None:
            # the sp axis shards the chunk-width dim: every shard must
            # be a whole number of w-bit words or the word repack
            # breaks.  Non-dividing geometry (auto sp) falls back to
            # the single-chip layout; an EXPLICIT bad sp was already
            # rejected at prewarm (strict resolve).
            wbytes = max(1, quantum // LENGTH_QUANTUM)
            if Lp % (int(mesh.shape["sp"]) * wbytes):
                mesh = None
            else:
                # dp shards the stripe-batch axis: round the bucket up
                # so every group shards cleanly (padding rows are
                # zero-stripes, stripped on deliver)
                bb = _round_up(bb, int(mesh.shape["dp"]))
        shape = (bb, k, Lp)
        # a full ring waits here for the oldest batch's fence
        with section("dispatch.stage_acquire", batch=bb):
            slot = self.staging.acquire(shape)
        # ledger origin: the slot is ours (ring fence retired).  The
        # interval ending at h2d_start is the host fill; h2d_done is
        # exact on fenced samples, dispatch-time otherwise.
        ledger = {"stage_acquire": time.time()}
        try:
            # the fill of the staging slot and the transfer of all of
            # it, padding included; live_bytes is the payload in it
            with section("dispatch.h2d", bytes=slot.host.nbytes,
                         live_bytes=data.nbytes, batch=bb):
                host = slot.host
                host[:batch, :, :L] = data  # copycheck: ok - staging fill into a REUSED persistent buffer (the one h2d copy)
                if slot.max_l > L:
                    # stale columns from a longer previous batch: packet-layout
                    # kernels mix columns within a super-word window, so the
                    # pad region must stay zero (GF-linear => zeros are inert)
                    host[:, :, L:slot.max_l] = 0
                slot.max_l = max(slot.max_l, L)
                if mesh is not None and slot.max_b > batch:
                    # mesh dp-padding contract: rows past the live batch
                    # are zero-stripes (stale stripes from a fuller
                    # previous batch would still be trimmed on deliver,
                    # but the sharded layout promises zero padding rows)
                    host[batch:slot.max_b, :, :] = 0  # copycheck: ok - zeroing dp-padding rows of the REUSED staging buffer, not a payload copy
                slot.max_b = max(slot.max_b, batch)
                sample = None
                ledger["h2d_start"] = time.time()
                sharding = self._mesh_sharding if mesh is not None else None
                if self.staging.should_sample():
                    t0 = time.monotonic()
                    dev = jax.device_put(host, sharding) \
                        if sharding is not None else jax.device_put(host)
                    try:
                        dev.block_until_ready()
                        dt = time.monotonic() - t0
                        self.staging.note_h2d(host.nbytes, dt)
                        sample = (host.nbytes, dt)
                    except Exception:
                        pass
                else:
                    dev = jax.device_put(host, sharding) \
                        if sharding is not None else jax.device_put(host)
            ledger["h2d_done"] = time.time()
        except BaseException:
            # staging/h2d failed before a fence existed: return the
            # slot with no fence, or the ring leaks a slot per failure
            # and two failures per shape wedge every later acquire()
            self.staging.release(shape, slot, None)
            raise

        def done(fence, _shape=shape, _slot=slot):
            self.staging.release(_shape, _slot, fence)
        return dev, batch, L, done, sample, ledger, mesh

    def prewarm_geometry(self, k: int, chunk_size: int,
                         batches=(1,), w: int = 8,
                         packetsize: int = 0) -> None:
        """Preallocate the staging rings a (k, chunk_size) geometry
        will dispatch, so the first client write after PG activation
        reuses warm buffers instead of paying fresh allocation.
        Idempotent and cheap (host-side only); executable compilation
        is driven by the codec layer, which calls this first.  A
        packet-layout code (``packetsize`` > 0) stages whole regions
        of w packets in the single-chip layout (apply_packet_async).

        This is also where mesh misconfiguration surfaces: a bad
        explicit ``ec_tpu_mesh_sp`` (doesn't divide the device count,
        or can't shard this geometry's padded chunk length) raises a
        clear ValueError HERE, not mid-dispatch."""
        if not self.bucket_shapes:
            return
        wbytes = max(1, w // 8)
        quantum = w * packetsize if packetsize else LENGTH_QUANTUM * wbytes
        Lp = _round_up(chunk_size, quantum)
        mesh = self._resolve_mesh(strict=True)
        dp = 1
        if mesh is not None and not packetsize:
            sp = int(mesh.shape["sp"])
            if Lp % (sp * wbytes):
                if self._mesh_conf[1]:
                    raise ValueError(
                        f"ec_tpu_mesh_sp={sp} cannot shard the padded "
                        f"chunk length {Lp} (w={w}: every sp shard "
                        f"must hold a whole number of {wbytes}-byte "
                        f"words) — pick an sp dividing "
                        f"{Lp // wbytes}")
                # auto sp that can't shard this geometry: single-chip
                # rings serve
            else:
                dp = int(mesh.shape["dp"])
        for nb in batches:
            self.staging.ensure(
                (_round_up(_bucket_batch(max(1, int(nb))), dp), k, Lp))

    def gf8_fast_path(self) -> bool:
        """The XOR-chain compiles once per coding matrix (static
        coeffs).  Worth it on TPU (per-pool constant, 14x runtime);
        NOT worth it on the CPU fallback, where test suites create
        hundreds of geometries and XLA-CPU compile time of the
        unrolled chain dominates — there the runtime-arg bit-plane
        path serves."""
        return jax.default_backend() == "tpu"

    def apply_gf8_matrix(self, M: np.ndarray, data: np.ndarray
                         ) -> np.ndarray:
        """Byte-domain w=8 fast path (encode hot path; the coding
        matrix is a per-pool constant so per-matrix compilation
        amortizes to zero)."""
        if not self.gf8_fast_path():
            from .matrix import matrix_to_bitmatrix
            return self.apply_bitmatrix_bytes(
                matrix_to_bitmatrix(M, 8), data, 8)
        squeeze = data.ndim == 2
        if squeeze:
            data = data[None]
        lead = data.shape[:-2]
        data = data.reshape((-1,) + data.shape[-2:])
        padded, batch, L = self._padded(data, LENGTH_QUANTUM)
        out = _run_sync(gf8_kernel(), self.gf8_fn(M), padded, batch, L)
        out = out.reshape(lead + out.shape[-2:])
        return out[0] if squeeze else out

    def apply_gf8_matrix_device(self, M: np.ndarray, dev_data):
        """Device-resident byte-domain apply (codec-kernel boundary)."""
        return self.gf8_fn(M)(dev_data)

    def gf8_fn(self, rows: np.ndarray, donate: bool = False,
               mesh=None):
        """The binding (BoundRows) that applies an arbitrary GF(2^8)
        row set to [.., C, L] byte chunks, LRU-cached per row set —
        per-pool coding matrices AND per-erasure-signature decode rows
        (the analog of ISA-L's decode-table LRU).  On a TPU the row
        set's bit-matrix (plane order, built here once) is an operand
        of the one gf8_mxu_pallas program (rows_program): a new
        signature costs a 2 KiB transfer, not a compile.  Off a TPU
        and on a mesh the rows are a constant of their own program
        (gf8_inner).  ``donate=True`` hands the staged device input to
        XLA for output aliasing — legal only when output bytes ==
        input bytes (square row set, m == k), so it is silently
        ignored otherwise.  ``mesh`` (from _staged_put) selects the
        sharded shard_map wrapper around the SAME kernel — one
        dispatch = one sharded GF matmul, bit-exact vs single-chip."""
        rows = np.asarray(rows, dtype=np.int64)
        donate = donate and rows.shape[0] == rows.shape[1]
        coeffs = tuple(tuple(int(v) for v in row) for row in rows)
        kernel = gf8_kernel()
        self._note_kernel(kernel)
        if mesh is not None:
            from ..parallel import mesh as pmesh
            dp = int(mesh.shape["dp"])
            sp = int(mesh.shape["sp"])
            return self._bound(
                ("gf8mesh", dp, sp, donate, coeffs),
                lambda: (pmesh.sharded_rows_fn(mesh, rows,
                                               donate=donate), None))

        def make():
            if kernel != "gf_mxu_pallas":
                return jax.jit(gf8_inner(rows), donate_argnums=(
                    (0,) if donate else ())), None
            from .matrix import matrix_to_bitmatrix
            return (rows_program(kernel, 8, donate=donate),
                    self._device_matrix(gf_plane_bits(
                        matrix_to_bitmatrix(rows, 8), rows.shape[1], 8)))
        return self._bound(("gf8don" if donate else "gf8", coeffs), make)

    def apply_gf8_rows(self, rows: np.ndarray, data: np.ndarray
                       ) -> np.ndarray:
        """Decode-side twin of apply_gf8_matrix: apply per-signature
        inverse rows via the signature-cached compiled kernel."""
        if not self.gf8_fast_path():
            from .matrix import matrix_to_bitmatrix
            return self.apply_bitmatrix_bytes(
                matrix_to_bitmatrix(np.asarray(rows, dtype=np.int64), 8),
                data, 8)
        squeeze = data.ndim == 2
        if squeeze:
            data = data[None]
        lead = data.shape[:-2]
        data = data.reshape((-1,) + data.shape[-2:])
        padded, batch, L = self._padded(data, LENGTH_QUANTUM)
        out = _run_sync(gf8_kernel(), self.gf8_fn(rows), padded, batch,
                        L)
        out = out.reshape(lead + out.shape[-2:])
        return out[0] if squeeze else out

    def packet_chain_fn(self, B: np.ndarray, w: int, packetsize: int):
        """The binding (BoundRows) that applies a packet-layout
        bitmatrix (cauchy/liberation families) to [batch, k, L] ->
        [batch, R/w, L], LRU-cached per matrix.  Where the fused
        kernel serves (packet_kernel) the matrix is an operand of the
        one packet_mxu_pallas program of its (w, packetsize).  The
        static XOR schedule that serves elsewhere (off a TPU, packets
        not lane-aligned) is unrolled from the matrix's ones, so it is
        a program per matrix by nature: there every erasure signature
        still compiles, at every batch bucket."""
        key = ("pkt", B.shape, B.tobytes(), w, packetsize)  # copycheck: ok - cache key over a tiny bitmatrix, not payload
        kernel = packet_kernel(packetsize)

        def make():
            if kernel == "packet_mxu_pallas":
                return (rows_program(kernel, w, packetsize),
                        self._device_matrix(B))
            return jax.jit(functools.partial(
                _packet_chain, schedule=build_xor_schedule(B), w=w,
                packetsize=packetsize)), None
        self._note_kernel(kernel)
        return self._bound(key, make)

    def apply_packet_xor(self, B: np.ndarray, data: np.ndarray, w: int,
                         packetsize: int) -> np.ndarray:
        """Static-schedule packet apply — used for both encode (coding
        bitmatrix, per-pool constant) and decode (inverted rows, cached
        per erasure signature) when the platform merits compilation."""
        squeeze = data.ndim == 2
        if squeeze:
            data = data[None]
        lead = data.shape[:-2]
        data = data.reshape((-1,) + data.shape[-2:])
        padded, batch, L = self._padded(data, w * packetsize)
        out = _run_sync(packet_kernel(packetsize),
                        self.packet_chain_fn(B, w, packetsize), padded,
                        batch, L)
        out = out.reshape(lead + out.shape[-2:])
        return out[0] if squeeze else out

    def _staged_call(self, data: np.ndarray, quantum: int, kernel: str,
                     lookup, shard: bool = True) -> "AsyncBatch":
        """The one staged, asynchronous dispatch every lane entry
        below goes through: fill a staging slot and start its h2d
        (_staged_put), look the program up (``lookup(mesh, donate)``
        -> the one-argument callable: a row set's binding, for the GF
        kernels), run it inside ``dispatch.call``, start the d2h copy,
        hand the slot its fence and return the handle with the
        seven-phase ledger and the fenced h2d sample."""
        squeeze = data.ndim == 2
        if squeeze:
            data = data[None]
        lead = data.shape[:-2] if not squeeze else ()
        data = data.reshape((-1,) + data.shape[-2:])
        dev, batch, L, done, sample, ledger, mesh = self._staged_put(
            data, quantum, shard)
        try:
            fn = lookup(mesh, done is not None)
            with _call_section(kernel, fn) as sec:
                out = fn(dev)
                sec.set_metadata(rows=out.shape[-2])
                ledger["compute_start"] = time.time()
                out.copy_to_host_async()
        except BaseException:
            # lookup or dispatch failed: no fence will ever retire, so
            # hand the slot back unfenced instead of leaking it
            if done is not None:
                done(None)
            raise
        if done is not None:
            done(out)
        ab = AsyncBatch(out, batch, L, lead, ledger)
        if sample is not None:
            ab.h2d_bytes, ab.h2d_seconds = sample
        return ab

    def apply_gf8_rows_async(self, rows: np.ndarray,
                             data: np.ndarray) -> "AsyncBatch":
        """Non-blocking apply_gf8_rows, for a pool's coding matrix
        (encode, delta) and per-erasure-signature inverse rows
        (decode) alike: both ride the same staging rings, the same
        program with their rows bound to it (gf8_fn), and the same
        device-phase ledger, so the OSD
        batcher can pipeline recovery decode groups exactly like
        encode groups (double buffering: submitting the next batch
        before waiting overlaps transfers with compute).  Donation
        is legal only for square row sets (gf8_fn enforces it), which
        decode hits whenever len(erased) == k."""
        if not self.gf8_fast_path():
            from .matrix import matrix_to_bitmatrix
            return self.apply_bitmatrix_bytes_async(
                matrix_to_bitmatrix(np.asarray(rows, dtype=np.int64),
                                    8), data, 8)
        return self._staged_call(
            data, LENGTH_QUANTUM, gf8_kernel(),
            lambda mesh, donate:
                self.gf8_fn(rows, donate=donate, mesh=mesh))

    def apply_packet_async(self, B: np.ndarray, data: np.ndarray, w: int,
                           packetsize: int) -> "AsyncBatch":
        """Non-blocking apply_packet_xor — the packet-layout twin of
        apply_gf8_rows_async, for encode (the pool's coding
        bit-matrix), decode (per-signature recovery rows) and delta
        alike.  The staging quantum is a whole region of w packets;
        the binding is looked up through packet_chain_fn on every
        call, under its one cache key.  There is no sharded packet
        apply: on a mesh the batch takes the single-chip layout."""
        return self._staged_call(
            data, w * packetsize, packet_kernel(packetsize),
            lambda mesh, donate: self.packet_chain_fn(B, w, packetsize),
            shard=False)

    def apply_bitmatrix_bytes(self, B: np.ndarray, data: np.ndarray,
                              w: int) -> np.ndarray:
        squeeze = data.ndim == 2
        if squeeze:
            data = data[None]
        lead = data.shape[:-2]
        data = data.reshape((-1,) + data.shape[-2:])
        wbytes = max(1, w // 8)
        if data.shape[-1] % wbytes:
            raise ValueError(
                f"chunk length must be a multiple of {wbytes} for w={w}")
        padded, batch, L = self._padded(data, LENGTH_QUANTUM * wbytes)
        self._note_kernel("bitplane_xla")
        Bdev = self._device_matrix(B)
        out = _run_sync("bitplane_xla",
                        lambda dev: _apply_byte_domain(Bdev, dev, w),
                        padded, batch, L)
        out = out.reshape(lead + out.shape[-2:])
        return out[0] if squeeze else out

    def apply_bitmatrix_bytes_async(self, B: np.ndarray, data: np.ndarray,
                                    w: int) -> AsyncBatch:
        """Non-blocking apply_bitmatrix_bytes: dispatches h2d staging, the
        MXU matmul, and the parity d2h copy, returning a handle.  Calling
        this for batch i+1 before AsyncBatch.wait() on batch i overlaps
        transfers with compute (double buffering)."""
        wbytes = max(1, w // 8)
        if data.shape[-1] % wbytes:
            raise ValueError(
                f"chunk length must be a multiple of {wbytes} for w={w}")
        self._note_kernel("bitplane_xla")

        def lookup(mesh, donate):
            if mesh is not None:
                return functools.partial(
                    self._mesh_apply_fn(mesh, w),
                    self._device_matrix_mesh(B, mesh))
            return functools.partial(_apply_byte_domain,
                                     self._device_matrix(B), w=w)
        return self._staged_call(data, LENGTH_QUANTUM * wbytes,
                                 "bitplane_xla", lookup)

    def apply_bitmatrix_bytes_device(self, B: np.ndarray, dev_data, w: int):
        """Device-resident apply: input is already a device array (padded
        to bucket shapes by the caller via stage()); output stays on
        device.  This is the codec-kernel boundary — the analog of the
        reference benchmark timing encode() over buffers in RAM
        (reference test/erasure-code/ceph_erasure_code_benchmark.cc:251)."""
        self._note_kernel("bitplane_xla")
        return _apply_byte_domain(self._device_matrix(B), dev_data, w)

    def stage(self, data: np.ndarray, w: int):
        """Pad + transfer a [batch, k, L] host array to the device."""
        wbytes = max(1, w // 8)
        padded, batch, L = self._padded(data, LENGTH_QUANTUM * wbytes)
        dev = jax.device_put(padded)
        dev.block_until_ready()
        return dev, batch, L

    def apply_bitmatrix_packets(self, B: np.ndarray, pk: np.ndarray
                                ) -> np.ndarray:
        raise NotImplementedError(
            "packet layout handled via apply_packet_chunks")

    def apply_packet_chunks(self, B: np.ndarray, data: np.ndarray, w: int,
                            packetsize: int) -> np.ndarray:
        squeeze = data.ndim == 2
        if squeeze:
            data = data[None]
        lead = data.shape[:-2]
        data = data.reshape((-1,) + data.shape[-2:])
        padded, batch, L = self._padded(data, w * packetsize)
        self._note_kernel("packet_bitplane_xla")
        Bdev = self._device_matrix(B)
        out = _run_sync(
            "packet_bitplane_xla",
            lambda dev: _apply_packet_domain(Bdev, dev, w, packetsize),
            padded, batch, L)
        out = out.reshape(lead + out.shape[-2:])
        return out[0] if squeeze else out
