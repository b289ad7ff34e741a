"""TPU plugin tests: bit-exactness vs the CPU jerasure plugin across all
techniques (the framework's analog of the reference's
ceph_erasure_code_non_regression corpus check), batched APIs, and shape
bucketing edge cases.  Runs on the JAX CPU backend (conftest forces an
8-device virtual CPU platform)."""
import itertools

import numpy as np
import pytest

from ceph_tpu.ec import registry as ecreg

TECHNIQUES = [
    ("reed_sol_van", {"k": "4", "m": "2"}),
    ("reed_sol_van", {"k": "8", "m": "4"}),
    ("reed_sol_van", {"k": "3", "m": "2", "w": "16"}),
    ("reed_sol_van", {"k": "3", "m": "2", "w": "32"}),
    ("reed_sol_r6_op", {"k": "4", "m": "2"}),
    ("cauchy_orig", {"k": "4", "m": "2", "packetsize": "32"}),
    ("cauchy_good", {"k": "5", "m": "3", "packetsize": "8"}),
    ("liberation", {"k": "4", "m": "2", "w": "7", "packetsize": "32"}),
    ("blaum_roth", {"k": "4", "m": "2", "w": "7", "packetsize": "32"}),
    ("liber8tion", {"k": "4", "m": "2", "w": "8", "packetsize": "32"}),
]


def pair(technique, profile):
    reg = ecreg.instance()
    p = dict(profile)
    p["technique"] = technique
    cpu = reg.factory("jerasure", dict(p))
    tpu = reg.factory("tpu", dict(p))
    return cpu, tpu


@pytest.mark.parametrize("technique,profile", TECHNIQUES)
def test_bit_exact_encode(technique, profile):
    cpu, tpu = pair(technique, profile)
    n = cpu.get_chunk_count()
    rng = np.random.default_rng(123)
    data = rng.integers(0, 256, 40000, dtype=np.uint8).tobytes()
    enc_cpu = cpu.encode(set(range(n)), data)
    enc_tpu = tpu.encode(set(range(n)), data)
    assert set(enc_cpu) == set(enc_tpu)
    for i in enc_cpu:
        assert enc_cpu[i] == enc_tpu[i], f"chunk {i} differs"


@pytest.mark.parametrize("technique,profile", TECHNIQUES[:6])
def test_bit_exact_decode(technique, profile):
    cpu, tpu = pair(technique, profile)
    n = cpu.get_chunk_count()
    m = cpu.get_coding_chunk_count()
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, 8192, dtype=np.uint8).tobytes()
    encoded = cpu.encode(set(range(n)), data)
    for nerasures in (1, m):
        for erased in itertools.combinations(range(n), nerasures):
            chunks = {i: c for i, c in encoded.items() if i not in erased}
            dec = tpu.decode(set(erased), chunks)
            for e in erased:
                assert dec[e] == encoded[e]


def test_encode_batch_matches_sequential():
    reg = ecreg.instance()
    tpu = reg.factory("tpu", {"k": "8", "m": "4"})
    cpu = reg.factory("jerasure", {"k": "8", "m": "4"})
    rng = np.random.default_rng(9)
    B, L = 17, 4096  # odd batch exercises bucketing/padding
    data = rng.integers(0, 256, (B, 8, L), dtype=np.uint8)
    parity = tpu.encode_batch(data)
    assert parity.shape == (B, 4, L)
    for b in range(0, B, 5):
        ref = cpu.core.encode(data[b])
        assert np.array_equal(parity[b], ref)


def test_decode_batch():
    reg = ecreg.instance()
    tpu = reg.factory("tpu", {"k": "4", "m": "2"})
    rng = np.random.default_rng(10)
    B, L = 6, 1024
    data = rng.integers(0, 256, (B, 4, L), dtype=np.uint8)
    parity = tpu.encode_batch(data)
    present = {i: data[:, i] for i in (0, 2, 3)}
    present[4] = parity[:, 0]
    present[5] = parity[:, 1]
    out = tpu.decode_batch(present, L)
    assert np.array_equal(out[1], data[:, 1])


@pytest.mark.parametrize("batch", [1, 2, 7, 8])
@pytest.mark.parametrize("length", [128, 129, 1000])
def test_bucketing_shapes(batch, length):
    reg = ecreg.instance()
    tpu = reg.factory("tpu", {"k": "2", "m": "1"})
    cpu = reg.factory("jerasure", {"k": "2", "m": "1"})
    rng = np.random.default_rng(batch * 1000 + length)
    data = rng.integers(0, 256, (batch, 2, length), dtype=np.uint8)
    parity = tpu.encode_batch(data)
    for b in range(batch):
        assert np.array_equal(parity[b], cpu.core.encode(data[b]))


def test_gf8_xor_chain_bit_exact():
    """The TPU encode fast path (fused XOR/xtime chain) must be
    bit-exact with the scalar GF reference — one small matrix keeps
    this a single cheap compile on the CPU backend."""
    import jax.numpy as jnp

    from ceph_tpu.ops.engine import NumpyBackend
    from ceph_tpu.ops.jax_engine import _apply_gf8_xor
    from ceph_tpu.ops.matrix import reed_sol_vandermonde_coding_matrix
    M = reed_sol_vandermonde_coding_matrix(3, 2, 8)
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (2, 3, 256), dtype=np.uint8)
    coeffs = tuple(tuple(int(v) for v in row) for row in M)
    out = np.asarray(_apply_gf8_xor(jnp.asarray(data), coeffs))
    ref = NumpyBackend().apply_matrix(M, data, 8)
    assert np.array_equal(out, ref)


def test_gf8_fast_path_forced_on_cpu(monkeypatch):
    """Force the w=8 XOR-chain fast path on the CPU backend and run
    the full plugin surface through it (encode, async encode, decode):
    the flagship kernel must be bit-exact with jerasure even off-TPU,
    so the suite — not just the bench — guards it."""
    from ceph_tpu.ec.plugins import tpu as tpumod
    be = tpumod.shared_backend()
    monkeypatch.setattr(type(be), "gf8_fast_path", lambda self: True)
    reg = ecreg.instance()
    k, m = 4, 2
    tpu = reg.factory("tpu", {"k": str(k), "m": str(m),
                              "technique": "reed_sol_van"})
    cpu = reg.factory("jerasure", {"k": str(k), "m": str(m),
                                   "technique": "reed_sol_van"})
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (3, k, 256), dtype=np.uint8)
    for b in range(3):
        assert np.array_equal(tpu.core.encode(data[b]),
                              cpu.core.encode(data[b]))
    # async entry point takes the same forced path
    parity = tpu.encode_batch(data)
    for b in range(3):
        assert np.array_equal(parity[b], cpu.core.encode(data[b]))
    # decode with erasures through the plugin API
    full = np.concatenate([data[0], cpu.core.encode(data[0])], axis=0)
    chunks = {i: full[i].tobytes() for i in range(k + m)
              if i not in (0, 3)}
    dec = tpu.decode({0, 3}, chunks)
    assert dec[0] == full[0].tobytes()
    assert dec[3] == full[3].tobytes()


def test_empty_object_roundtrip():
    """Zero-length objects must encode/decode without touching the
    device paths (regression: apply_gf8_matrix reshape crashed on
    L=0 chunks)."""
    reg = ecreg.instance()
    for plugin in ("tpu", "jerasure"):
        codec = reg.factory(plugin, {"k": "8", "m": "4"})
        ch = codec.encode(set(range(12)), b"")
        assert all(c == b"" for c in ch.values())
        assert codec.decode_concat({i: ch[i] for i in range(8)}) == b""
        dec = codec.decode({0, 9}, {i: ch[i] for i in range(12)
                                    if i not in (0, 9)})
        assert dec[0] == b"" and dec[9] == b""


def test_xor_schedule_reconstructs_bitmatrix():
    """build_xor_schedule's delta chains must reproduce the original
    bitmatrix rows exactly (XOR-simulated over GF(2) basis vectors)."""
    from ceph_tpu.ops.jax_engine import build_xor_schedule
    from ceph_tpu.ops.matrix import matrix_to_bitmatrix
    from ceph_tpu.ops.matrix import reed_sol_vandermonde_coding_matrix
    B = matrix_to_bitmatrix(
        reed_sol_vandermonde_coding_matrix(5, 3, 8), 8)
    sched = build_xor_schedule(B)
    assert len(sched) == B.shape[0]
    rows = []
    for prev, cols in sched:
        v = rows[prev].copy() if prev >= 0 else \
            np.zeros(B.shape[1], dtype=np.uint8)
        for c in cols:
            v[c] ^= 1
        rows.append(v)
    assert np.array_equal(np.stack(rows), B)


def test_packet_static_path_forced_on_cpu(monkeypatch):
    """Force the static XOR-schedule packet path on the CPU backend:
    cauchy encode + decode must stay bit-exact with the jerasure
    oracle when routed through compiled schedules."""
    from ceph_tpu.ec.plugins import tpu as tpumod
    be = tpumod.shared_backend()
    monkeypatch.setattr(type(be), "gf8_fast_path", lambda self: True)
    prof = {"k": "3", "m": "2", "technique": "cauchy_good",
            "packetsize": "8"}
    reg = ecreg.instance()
    tpu = reg.factory("tpu", dict(prof))
    cpu = reg.factory("jerasure", dict(prof))
    assert tpu.core.packet_static_fast()
    w = tpu.w
    L = 3 * w * 8  # a few super-words
    rng = np.random.default_rng(21)
    data = rng.integers(0, 256, (2, 3, L), dtype=np.uint8)
    parity = tpu.encode_batch(data)
    ref = cpu.core.encode(data)
    assert np.array_equal(parity, ref)
    # decode two erasures (one data, one parity) through the core
    present = {1: data[:, 1], 2: data[:, 2], 4: ref[:, 1]}
    out = tpu.core.decode_chunks(present, L)
    assert np.array_equal(out[0], data[:, 0])
    assert np.array_equal(out[3], ref[:, 0])


def test_packet_mxu_pallas_kernel_interpret():
    """The fused MXU packet kernel (the TPU fast path that replaced
    the XOR-schedule chain for cauchy-family encode AND per-signature
    decode — VERDICT r4 Next #4) must match the XLA schedule chain
    bit-for-bit, for both encode-shaped (R = m*w) and decode-shaped
    (arbitrary row-set) bitmatrices, across w values including the
    non-power-of-two widths the liberation family uses."""
    import jax.numpy as jnp

    from ceph_tpu.ops.jax_engine import (_packet_chain,
                                         _packet_mxu_pallas,
                                         build_xor_schedule)
    from ceph_tpu.ops.matrix import (cauchy_good_coding_matrix,
                                     matrix_to_bitmatrix)
    rng = np.random.default_rng(37)
    for k, m, w, ps in ((4, 2, 8, 128), (3, 2, 7, 256), (5, 3, 4, 128)):
        B = matrix_to_bitmatrix(cauchy_good_coding_matrix(k, m, w), w)
        data = rng.integers(0, 256, (2, k, 3 * w * ps), dtype=np.uint8)
        for rows in (B, B[: 2 * w]):     # encode shape + decode shape
            sched = build_xor_schedule(rows)
            ref = np.asarray(_packet_chain(jnp.asarray(data), sched,
                                           w, ps))
            out = np.asarray(_packet_mxu_pallas(
                jnp.asarray(rows, jnp.int8), jnp.asarray(data), w=w,
                packetsize=ps, interpret=True))
            assert np.array_equal(out, ref), (k, m, w, ps, rows.shape)


def test_gf_mxu_pallas_kernel_interpret():
    """The fused bit-plane MXU kernel (TPU w=8 fast path for encode and
    per-signature decode) must match the scalar oracle bit-for-bit,
    including chunk lengths that are NOT a multiple of 128 (the
    in-kernel padding branch the mesh data plane relies on)."""
    import jax.numpy as jnp

    from ceph_tpu.ops.engine import NumpyBackend
    from ceph_tpu.ops.jax_engine import _gf_mxu_pallas, gf_plane_bits
    from ceph_tpu.ops.matrix import (make_decoding_matrix,
                                     matrix_to_bitmatrix,
                                     reed_sol_vandermonde_coding_matrix)
    k, m, w = 4, 2, 8
    M = reed_sol_vandermonde_coding_matrix(k, m, w)
    rows = make_decoding_matrix(M, w, [1, 2, 4, 5])[[0, 3]]
    rng = np.random.default_rng(41)
    for mat, L in ((M, 256), (M, 192), (rows, 320)):
        B = matrix_to_bitmatrix(mat, w)
        data = rng.integers(0, 256, (2, k, L), dtype=np.uint8)
        out = np.asarray(_gf_mxu_pallas(
            jnp.asarray(gf_plane_bits(B, k, w)), jnp.asarray(data), w=w,
            interpret=True))
        ref = NumpyBackend().apply_matrix(mat, data, 8)
        assert np.array_equal(out, ref), (mat.shape, L)


def test_kernel_builder_failure_raises_no_chain_fallback(monkeypatch):
    """Kernel choice is by platform and geometry only.  Where the
    platform says Pallas, a builder that raises (on the chip: Mosaic
    refusing a block shape) reaches the caller — the codec does not
    quietly serve the geometry from an XLA chain."""
    from ceph_tpu.ops import jax_engine as je
    from ceph_tpu.ops.jax_engine import JaxBackend

    def refuse(*_a, **_kw):
        raise RuntimeError("mosaic refused")
    be = JaxBackend()
    monkeypatch.setattr(JaxBackend, "gf8_fast_path", lambda self: True)
    monkeypatch.setattr(je, "gf8_kernel", lambda: "gf_mxu_pallas")
    monkeypatch.setattr(je, "packet_kernel",
                        lambda ps: "packet_mxu_pallas")
    monkeypatch.setattr(je, "_gf_mxu_pallas", refuse)
    monkeypatch.setattr(je, "_packet_mxu_pallas", refuse)
    je.rows_program.cache_clear()    # programs traced before the patch
    reg = ecreg.instance()
    rs = reg.factory("tpu", {"k": "3", "m": "2"})
    rs.core.backend = be
    data = np.zeros((2, 3, 256), dtype=np.uint8)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        rs.encode_batch(data)
    with pytest.raises(RuntimeError, match="mosaic refused"):
        rs.encode_batch_async(data)
    cg = reg.factory("tpu", {"k": "3", "m": "2",
                             "technique": "cauchy_good",
                             "packetsize": "128"})
    cg.core.backend = be
    with pytest.raises(RuntimeError, match="mosaic refused"):
        cg.encode_batch(np.zeros((1, 3, cg.w * 128), dtype=np.uint8))
    assert be.kernel_calls == {"gf_mxu_pallas": 2,
                               "packet_mxu_pallas": 1}


def test_gf8_decode_rows_lru(monkeypatch):
    """Per-signature decode chains are served from the backend ChainLRU
    and evicted beyond the cap."""
    from ceph_tpu.ops.jax_engine import JaxBackend
    be = JaxBackend()
    monkeypatch.setattr(JaxBackend, "gf8_fast_path", lambda self: True)
    be._chain_lru.cap = 2
    from ceph_tpu.ops.matrix import reed_sol_vandermonde_coding_matrix
    M = reed_sol_vandermonde_coding_matrix(3, 2, 8)
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (2, 3, 128), dtype=np.uint8)
    from ceph_tpu.ops.engine import NumpyBackend
    ref_full = NumpyBackend().apply_matrix(M, data, 8)
    for rows in (M[:1], M[1:2], M[:2]):  # 3 signatures > cap 2
        out = be.apply_gf8_rows(rows, data)
        first = int(np.flatnonzero((M == rows[0]).all(axis=1))[0])
        assert np.array_equal(out[:, 0], ref_full[:, first])
    assert len(be._chain_lru._d) == 2


def test_jit_cache_reused_across_instances():
    """Two codec instances with the same geometry share one backend
    (so jit caches are shared: the w=8 XOR-chain keys on the static
    coeff tuple, the bit-plane path on the device-matrix cache)."""
    from ceph_tpu.ec.plugins import tpu as tpumod
    reg = ecreg.instance()
    a = reg.factory("tpu", {"k": "4", "m": "2"})
    b = reg.factory("tpu", {"k": "4", "m": "2"})
    assert a.core.backend is b.core.backend
    be = tpumod.shared_backend()
    pa = a.encode_batch(np.zeros((2, 4, 256), dtype=np.uint8))
    pb = b.encode_batch(np.zeros((2, 4, 256), dtype=np.uint8))
    assert np.array_equal(pa, pb)
    # the bit-plane device-matrix cache still serves non-w8 paths:
    # a w=16 codec populates it
    c = reg.factory("tpu", {"k": "3", "m": "2", "w": "16"})
    c.encode_batch(np.zeros((2, 3, 256), dtype=np.uint8))
    key = (c.core.bitmatrix.shape, c.core.bitmatrix.tobytes())
    assert key in be._dev_matrices


def test_staging_pool_reuses_host_arrays():
    """PR 5 persistent staging: consecutive async encodes of the same
    shape must serve their host staging from the preallocated ring
    (hits, not fresh allocs) and release slots on completion."""
    reg = ecreg.instance()
    codec = reg.factory("tpu", {"k": "4", "m": "2"})
    pool = codec.core.backend.staging
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (4, 4, 4096), dtype=np.uint8)
    ref = codec.encode_batch(data)
    a0, h0 = pool.allocs, pool.hits
    outs = [codec.encode_batch_async(data.copy()).wait()
            for _ in range(6)]
    for out in outs:
        assert np.array_equal(np.asarray(out), ref)
    # at most ring-depth fresh arrays for this shape; the rest reuse
    assert pool.allocs - a0 <= 2
    assert pool.hits - h0 >= 4, \
        "staging ring never reused a host array across encodes"
    # every slot came back: the ring is fully idle after the waits
    shape = next(s for s in pool._free if pool._free[s])
    assert len(pool._free[shape]) == pool._made[shape]


def test_staging_slot_released_on_failed_dispatch():
    """A raise between slot acquire and fence registration (the
    kernel call in apply_bitmatrix_bytes_async) must hand the slot
    back to the ring: with depth=2, two leaked slots would wedge
    every later acquire() for that shape on the batcher collector
    thread (regression: StagingPool slot leak on exception)."""
    from ceph_tpu.ops import jax_engine
    from ceph_tpu.ops.matrix import (
        reed_sol_vandermonde_coding_matrix, matrix_to_bitmatrix)
    reg = ecreg.instance()
    codec = reg.factory("tpu", {"k": "3", "m": "2"})
    be = codec.core.backend
    pool = be.staging
    B = matrix_to_bitmatrix(
        reed_sol_vandermonde_coding_matrix(3, 2, 8), 8)
    data = np.zeros((2, 3, 1024), dtype=np.uint8)
    ref = np.asarray(be.apply_bitmatrix_bytes_async(B, data, 8).wait())
    # the staged batch bucket is rounded up to a dp multiple when the
    # dispatch rides the device mesh
    info = be.mesh_info()
    dp = info["dp"] if info else 1
    shape = (jax_engine._round_up(jax_engine._bucket_batch(2), dp), 3,
             jax_engine._round_up(1024, jax_engine.LENGTH_QUANTUM))

    def boom(*a, **k):
        raise RuntimeError("injected kernel fault")

    # inject into both kernel seams so the fault fires whichever path
    # (sharded mesh or single-chip) the dispatch takes
    real = jax_engine._apply_byte_domain
    real_mesh = jax_engine.JaxBackend._mesh_apply_fn
    jax_engine._apply_byte_domain = boom
    jax_engine.JaxBackend._mesh_apply_fn = lambda self, mesh, w: boom
    try:
        for _ in range(2 * pool.depth):   # more failures than slots
            with pytest.raises(RuntimeError):
                be.apply_bitmatrix_bytes_async(B, data.copy(), 8)
    finally:
        jax_engine._apply_byte_domain = real
        jax_engine.JaxBackend._mesh_apply_fn = real_mesh
    # every slot came back unfenced: the ring is fully free and no
    # stall-recovery alloc was needed
    assert len(pool._free[shape]) == pool._made[shape]
    assert pool.stall_allocs == 0
    # and the path still serves, bit-exact
    out = np.asarray(be.apply_bitmatrix_bytes_async(B, data, 8).wait())
    assert np.array_equal(out, ref)


def test_staging_pool_acquire_stall_grows_ring():
    """Defense in depth: if a slot DOES leak (a crash path nobody
    releases), acquire() must not block forever on the batcher
    collector thread — past STALL_S it grows the ring by one and
    the write path keeps flowing."""
    from ceph_tpu.ops.jax_engine import StagingPool
    pool = StagingPool(depth=1)
    pool.STALL_S = 0.1                    # instance override: fast test
    shape = (1, 2, 64)
    held = pool.acquire(shape)            # the only slot, never released
    grown = pool.acquire(shape)           # must not wedge
    assert grown is not held
    assert pool.stall_allocs == 1
    assert pool._made[shape] == 2
    pool.release(shape, grown, None)
    pool.release(shape, held, None)
    assert len(pool._free[shape]) == 2


def test_prewarm_geometry_preallocates_and_compiles():
    """prewarm_geometry() must leave the staging ring allocated for
    the geometry's padded shape and the encode executable compiled,
    so the first real write pays neither."""
    reg = ecreg.instance()
    codec = reg.factory("tpu", {"k": "2", "m": "1"})
    pool = codec.core.backend.staging
    a0 = pool.allocs
    codec.prewarm_geometry(8192, batches=(4,))
    assert pool.allocs > a0, \
        "prewarm_geometry allocated no staging arrays"
    a1 = pool.allocs
    # a real write of the prewarmed shape allocates nothing new
    data = np.zeros((4, 2, 8192), dtype=np.uint8)
    out = codec.encode_batch_async(data).wait()
    assert np.asarray(out).shape[1] == 1
    assert pool.allocs == a1, \
        "prewarmed shape still paid a fresh staging alloc"
    # idempotent
    codec.prewarm_geometry(8192, batches=(4,))
    assert pool.allocs == a1
