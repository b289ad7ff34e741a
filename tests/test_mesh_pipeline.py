"""Multichip mesh pipeline tests (ISSUE 12).

The production batcher dispatch on a dp=4 x sp=2 device mesh: the
suite-wide conftest forces ``XLA_FLAGS
--xla_force_host_platform_device_count=8`` + ``JAX_PLATFORMS=cpu``
before JAX initializes (the documented CPU recipe — README
"Multichip mesh"), so every test here runs the REAL sharded path —
``JaxBackend._staged_put`` laying groups out with
``NamedSharding(mesh, P("dp", None, "sp"))`` and one sharded GF
matmul per dispatch — on a CPU-only box.  Covered: encode AND decode
bit-exactness vs the jerasure oracle across geometries and erasure
signatures, dp-padding (odd batches round up to a dp multiple with
zero stripes, stripped on deliver), per-device ledger lanes feeding
dump_device / the Perfetto deviceN bands with no schema change,
make_mesh single-device and non-factorable edges, and one subprocess
run that sets the XLA flag EXPLICITLY so the recipe is proven
independent of this conftest (and cannot perturb other tests'
device count).
"""
import itertools
import os
import subprocess
import sys
import threading

import numpy as np
import pytest

from ceph_tpu.ec import registry as ecreg
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.batcher import EncodeBatcher

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def make_codec(k, m):
    return ecreg.instance().factory(
        "tpu", {"k": str(k), "m": str(m),
                "technique": "reed_sol_van"})


def make_cpu(k, m):
    return ecreg.instance().factory(
        "jerasure", {"k": str(k), "m": str(m),
                     "technique": "reed_sol_van"})


def make_batcher(**over):
    conf = {"ec_tpu_batch_stripes": 1024,
            "ec_tpu_queue_window_us": 1000}
    conf.update(over)
    EncodeBatcher.reset_learning()
    return EncodeBatcher(conf)


@pytest.fixture
def backend():
    """The shared JaxBackend with the mesh reset to auto before AND
    after each test (tests here flip mesh shapes; the rest of the
    suite must always see the default-auto mesh)."""
    be = make_codec(2, 1).core.backend
    be.configure_mesh(0, 0)
    yield be
    be.configure_mesh(0, 0)


# ---------------------------------------------------------------------
# mesh resolution
# ---------------------------------------------------------------------
def test_mesh_active_by_default_on_8_devices(backend):
    """With 8 visible devices and no conf, the backend auto-builds a
    dp=4 x sp=2 mesh and records a mesh_build event for the flight
    recorder drain."""
    info = backend.mesh_info()
    assert info is not None
    assert info["dp"] == 4 and info["sp"] == 2
    assert info["n_devices"] == 8
    assert info["device_ids"] == list(range(8))
    assert any(ev.get("event") == "mesh_build"
               for ev in backend.mesh_events)


def test_single_device_mesh_is_no_mesh(backend):
    """n=1 resolves to NO mesh: mesh_info is None, dispatch takes the
    single-chip path, and the output is byte-identical to both the
    mesh path and the CPU oracle (zero-overhead fallback)."""
    from ceph_tpu.parallel import mesh as pmesh
    assert pmesh.resolve_mesh(1) is None
    codec = make_codec(4, 2)
    rng = np.random.default_rng(3)
    data = rng.integers(0, 256, (5, 4, 512), dtype=np.uint8)
    backend.configure_mesh(0, 0)
    mesh_out = codec.encode_batch(data)
    backend.configure_mesh(1, 0)
    assert backend.mesh_info() is None
    single_out = codec.encode_batch(data)
    assert np.array_equal(mesh_out, single_out)
    cpu = make_cpu(4, 2)
    ref = np.stack([cpu.core.encode(data[b]) for b in range(5)])
    assert np.array_equal(single_out, ref)


def test_forced_device_count_clamps_to_visible(backend):
    """ec_tpu_mesh_devices beyond the visible count clamps instead of
    failing the whole dispatch path."""
    backend.configure_mesh(64, 0)
    info = backend.mesh_info()
    assert info is not None and info["n_devices"] == 8


def test_bad_explicit_sp_raises_at_prewarm_not_dispatch(backend):
    """An explicit sp that cannot shard the geometry raises a clear
    ValueError at prewarm time; dispatch never sees it."""
    # sp=3 does not divide 8 devices: the mesh itself cannot build
    backend.configure_mesh(8, 3)
    with pytest.raises(ValueError, match="ec_tpu_mesh"):
        backend.prewarm_geometry(8, 4096, batches=(4,))
    # sp=5 divides a forced 5-device mesh but not the padded chunk
    # (multiples of 128): caught at prewarm with the conf knob named
    backend.configure_mesh(5, 5)
    with pytest.raises(ValueError, match="ec_tpu_mesh_sp"):
        backend.prewarm_geometry(8, 4096, batches=(4,))


# ---------------------------------------------------------------------
# batcher-routed bit-exactness through the mesh
# ---------------------------------------------------------------------
@pytest.mark.parametrize("k,m", [(8, 4), (4, 2), (2, 1)])
@pytest.mark.parametrize("stripes", [1, 3, 5, 16])
def test_batcher_encode_bit_exact_with_dp_padding(backend, k, m,
                                                  stripes):
    """Batcher-routed encode through the dp=4 x sp=2 mesh is
    bit-exact vs the jerasure oracle for every geometry and batch
    size — including batches that are NOT a dp multiple (1, 3, 5),
    where the bucket rounds up with zero stripes that must be
    stripped on deliver."""
    codec = make_codec(k, m)
    assert backend.mesh_info() is not None
    L = 512
    sinfo = ecutil.StripeInfo(k, k * L)
    rng = np.random.default_rng(100 + stripes)
    data = rng.integers(0, 256, (stripes, k, L),
                        dtype=np.uint8).tobytes()
    bat = make_batcher(ec_tpu_min_device_bytes=1)
    got, ev = {}, threading.Event()
    try:
        bat.submit(codec, sinfo, data,
                   lambda ch: (got.update(ch or {}), ev.set()))
        assert ev.wait(120)
    finally:
        bat.stop()
    ref = ecutil.encode(sinfo, make_cpu(k, m), data)
    assert set(got) == set(ref)
    for s in ref:
        assert bytes(got[s]) == bytes(ref[s]), \
            f"k={k} m={m} stripes={stripes} shard {s}"


@pytest.mark.parametrize("k,m", [(8, 4), (4, 2)])
def test_mesh_decode_bit_exact_every_signature(backend, k, m):
    """decode_batch_async rides the same sharded apply: every 1- and
    2-erasure signature reconstructs bit-exact through the mesh on a
    batch that exercises dp padding (5 stripes, dp=4)."""
    codec = make_codec(k, m)
    assert backend.mesh_info() is not None
    cs = 256
    rng = np.random.default_rng(7)
    data = rng.integers(0, 256, (5, k, cs), dtype=np.uint8)
    parity = codec.encode_batch(data)
    shards = {i: data[:, i] for i in range(k)}
    shards.update({k + e: parity[:, e] for e in range(m)})
    n = k + m
    sigs = [frozenset(c) for c in itertools.combinations(range(n), 1)]
    sigs += [frozenset(c) for c in itertools.combinations(range(n), 2)]
    for erased in sigs:
        present = {i: shards[i] for i in range(n) if i not in erased}
        rec = codec.decode_batch_async(present, cs).wait()
        for e in sorted(erased):
            assert np.array_equal(rec[e], shards[e]), \
                f"k={k} m={m} erased={sorted(erased)} shard {e}"


def test_mesh_vs_single_chip_decode_identical(backend):
    """The mesh recovery apply and the pinned single-chip apply
    produce byte-identical reconstructions (the decode twin of the
    encode fallback test)."""
    codec = make_codec(8, 4)
    cs = 512
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (6, 8, cs), dtype=np.uint8)
    parity = codec.encode_batch(data)
    shards = {i: data[:, i] for i in range(8)}
    shards.update({8 + e: parity[:, e] for e in range(4)})
    present = {i: shards[i] for i in range(12) if i not in (0, 9)}
    backend.configure_mesh(0, 0)
    rec_mesh = codec.decode_batch_async(present, cs).wait()
    backend.configure_mesh(1, 0)
    rec_one = codec.decode_batch_async(present, cs).wait()
    for e in (0, 9):
        assert np.array_equal(rec_mesh[e], rec_one[e])
        assert np.array_equal(rec_mesh[e], shards[e])


def test_pallas_kernel_traces_under_sharded_rows_fn(monkeypatch):
    """On TPU ``gf8_inner`` returns the fused Pallas kernel, and
    ``sharded_rows_fn`` wraps it in ``shard_map``.  Under the installed
    JAX that wrapper refused to trace (``check_vma=True`` wants a
    ``vma`` on the pallas ``out_shape``), which CPU routing never
    reaches — so push the kernel through in interpret mode on the
    virtual mesh and hold it to the jerasure oracle."""
    from ceph_tpu.ops import jax_engine as je
    from ceph_tpu.ops.matrix import matrix_to_bitmatrix
    from ceph_tpu.parallel import mesh as pmesh

    def pallas_inner(rows):
        import functools
        import jax.numpy as jnp
        rows = np.asarray(rows, dtype=np.int64)
        bits = je.gf_plane_bits(matrix_to_bitmatrix(rows, 8),
                                rows.shape[1], 8)
        return functools.partial(je._gf_mxu_pallas, jnp.asarray(bits),
                                 w=8, interpret=True)
    monkeypatch.setattr(je, "gf8_inner", pallas_inner)
    k, m, cs = 8, 4, 512
    cpu = make_cpu(k, m)
    mesh = pmesh.make_mesh(4)                    # 2 x 2, as on a v5e-4
    rng = np.random.default_rng(21)
    data = rng.integers(0, 256, (4, k, cs), dtype=np.uint8)
    fn = pmesh.sharded_rows_fn(mesh, cpu.core.coding_matrix)
    out = fn(pmesh.shard_batch(mesh, data))
    assert len(out.sharding.device_set) == 4
    ref = np.stack([cpu.core.encode(data[b]) for b in range(4)])
    assert np.array_equal(np.asarray(out), ref)


# ---------------------------------------------------------------------
# per-device observability (PR 10 machinery, no schema change)
# ---------------------------------------------------------------------
def test_per_device_ledger_lanes_and_dump(backend):
    """A mesh dispatch finalizes one ledger clone per chip: the
    batcher folds 8 lanes into the accumulator, device_dump carries
    the mesh block, and the Perfetto exporter emits one deviceN band
    per chip from the unchanged trace-block schema."""
    from ceph_tpu.utils.perf import PerfCountersCollection
    codec = make_codec(8, 4)
    assert backend.mesh_info() is not None
    L = 512
    sinfo = ecutil.StripeInfo(8, 8 * L)
    coll = PerfCountersCollection()
    EncodeBatcher.reset_learning()
    bat = EncodeBatcher({"ec_tpu_batch_stripes": 1024,
                         "ec_tpu_queue_window_us": 1000,
                         "ec_tpu_min_device_bytes": 1},
                        perf_coll=coll)
    rng = np.random.default_rng(13)
    data = rng.integers(0, 256, (8, 8, L), dtype=np.uint8).tobytes()
    got, ev = {}, threading.Event()
    try:
        bat.submit(codec, sinfo, data,
                   lambda ch: (got.update(ch or {}), ev.set()))
        assert ev.wait(120)
        recent = bat.ledger_accum.recent()
        lanes = sorted({int(led.get("device", -1)) for led in recent
                        if int(led.get("device", -1)) >= 0})
        assert lanes == list(range(8)), lanes
        dump = bat.device_dump()
        assert dump["mesh"] is not None
        assert dump["mesh"]["dp"] == 4 and dump["mesh"]["sp"] == 2
        assert sorted(dump["ledger"]["overlap"]["devices"]) == \
            list(range(8))
        # mesh gauges registered and set in the ec_device subsystem
        dp = bat.dperf
        assert dp.get("mesh_dp") == 4 and dp.get("mesh_sp") == 2
        assert dp.get("mesh_devices") == 8
        # Perfetto lanes: one deviceN band per chip, schema unchanged
        sys.path.insert(0, REPO)
        from tools.trace_export import export_bundles
        trace = export_bundles([{"daemon": "osd.0",
                                 "device": bat.device_trace_block()}])
        names = {e["args"]["name"]
                 for e in trace["traceEvents"]
                 if e.get("ph") == "M" and e["name"] == "thread_name"}
        for d in range(8):
            assert any(n.startswith(f"device{d} ") for n in names), \
                f"no device{d} lane in {sorted(names)}"
    finally:
        bat.stop()


def test_per_mesh_shape_learner_keying():
    """The h2d EWMA / crossover scalars swap with the mesh shape:
    state learned on the 4x2 mesh must not leak into single-chip
    routing, and flipping back restores it."""
    EncodeBatcher.reset_learning()
    EncodeBatcher._rekey_mesh((4, 2))
    EncodeBatcher._h2d_bps = 123.0
    EncodeBatcher._min_device_bytes = 456.0
    EncodeBatcher._rekey_mesh(None)          # to single-chip: fresh
    assert EncodeBatcher._mesh_key is None
    EncodeBatcher._h2d_bps = 7.0
    EncodeBatcher._rekey_mesh((4, 2))        # back: restored
    assert EncodeBatcher._h2d_bps == 123.0
    assert EncodeBatcher._min_device_bytes == 456.0
    EncodeBatcher._rekey_mesh(None)
    assert EncodeBatcher._h2d_bps == 7.0
    EncodeBatcher.reset_learning()
    assert EncodeBatcher._mesh_state == {}


# ---------------------------------------------------------------------
# the explicit-flag subprocess recipe
# ---------------------------------------------------------------------
def test_mesh_recipe_in_explicit_subprocess():
    """The README recipe stands alone: a fresh interpreter that sets
    XLA_FLAGS=--xla_force_host_platform_device_count=8 itself (no
    conftest) gets a dp=4 x sp=2 mesh and a bit-exact batcher-routed
    encode — proving the documented env, in a subprocess so this
    suite's device count is untouched."""
    code = (
        "import os\n"
        "os.environ['XLA_FLAGS'] = (os.environ.get('XLA_FLAGS', '')"
        " + ' --xla_force_host_platform_device_count=8').strip()\n"
        "import jax\n"
        "jax.config.update('jax_platforms', 'cpu')\n"
        f"import sys; sys.path.insert(0, {REPO!r})\n"
        "import numpy as np, threading\n"
        "from ceph_tpu.ec import registry as ecreg\n"
        "from ceph_tpu.osd import ecutil\n"
        "from ceph_tpu.osd.batcher import EncodeBatcher\n"
        "codec = ecreg.instance().factory('tpu', {'k': '8', 'm': '4'})\n"
        "info = codec.core.backend.mesh_info()\n"
        "assert info and info['dp'] == 4 and info['sp'] == 2, info\n"
        "data = np.random.default_rng(1).integers(\n"
        "    0, 256, (5, 8, 512), dtype=np.uint8).tobytes()\n"
        "sinfo = ecutil.StripeInfo(8, 8 * 512)\n"
        "bat = EncodeBatcher({'ec_tpu_min_device_bytes': 1})\n"
        "got, ev = {}, threading.Event()\n"
        "bat.submit(codec, sinfo, data,\n"
        "           lambda ch: (got.update(ch or {}), ev.set()))\n"
        "assert ev.wait(120); bat.stop()\n"
        "cpu = ecreg.instance().factory('jerasure',"
        " {'k': '8', 'm': '4'})\n"
        "ref = ecutil.encode(sinfo, cpu, data)\n"
        "assert all(bytes(got[s]) == bytes(ref[s]) for s in ref)\n"
        "print('MESH_RECIPE_OK', info['n_devices'])\n")
    env = dict(os.environ)
    env.pop("XLA_FLAGS", None)           # the child sets its own
    env["JAX_PLATFORMS"] = "cpu"
    proc = subprocess.run([sys.executable, "-c", code], env=env,
                          cwd=REPO, capture_output=True, text=True,
                          timeout=280)
    assert proc.returncode == 0, proc.stderr[-3000:]
    assert "MESH_RECIPE_OK 8" in proc.stdout
