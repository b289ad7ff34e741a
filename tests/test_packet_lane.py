"""Packet-layout codes through the served path (ISSUE 30).

The five packet-layout techniques of the ``tpu`` plugin (cauchy_orig,
cauchy_good, liberation, blaum_roth, liber8tion) go through the OSD
batcher's three lanes by ``JaxBackend.apply_packet_async``.  Before
this the encode lane applied a packet code's bit-matrix in the byte
domain and stored wrong parity in every row but the all-ones first;
decode took the completion-time path and a sub-stripe overwrite raised.
Every layer is held bit for bit to the CPU ``jerasure`` plugin: the
plugin's async entries, the batcher, and a live cluster's stored
shards, degraded reads, overwrites and recovery.
"""
import importlib.util
import itertools
import os
import threading
import time

import numpy as np
import pytest

from ceph_tpu.cluster import Cluster
from ceph_tpu.cluster import test_config as make_conf
from ceph_tpu.ec import registry as ecreg
from ceph_tpu.ec.plugins import tpu as tpu_plugin
from ceph_tpu.ops.jax_engine import _bucket_batch
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.batcher import EncodeBatcher

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: (technique, k, m, w, packetsize): a small and a wide geometry each;
#: the minimum-density codes take m=2 and k <= w
GEOMETRIES = [
    ("cauchy_orig", 4, 3, 8, 512), ("cauchy_orig", 10, 4, 8, 2048),
    ("cauchy_good", 4, 3, 8, 512), ("cauchy_good", 10, 4, 8, 2048),
    ("liberation", 4, 2, 7, 512), ("liberation", 7, 2, 7, 2048),
    ("blaum_roth", 4, 2, 6, 512), ("blaum_roth", 6, 2, 6, 2048),
    ("liber8tion", 4, 2, 8, 512), ("liber8tion", 8, 2, 8, 2048),
]
CAUCHY = [g for g in GEOMETRIES if g[0] == "cauchy_good"]


def gid(g) -> str:
    return f"{g[0]}-k{g[1]}m{g[2]}w{g[3]}ps{g[4]}"


def pair(technique, k, m, w, packetsize):
    """(the tpu plugin's codec, the CPU jerasure plugin's)."""
    profile = {"technique": technique, "k": str(k), "m": str(m),
               "w": str(w), "packetsize": str(packetsize)}
    reg = ecreg.instance()
    return reg.factory("tpu", profile), reg.factory("jerasure", profile)


def cauchy_reference():
    """The benchmark's plain reference, loaded by path: numpy alone,
    nothing of ceph_tpu."""
    path = os.path.join(ROOT, "benchmark", "references",
                        "cauchy_good_w8.py")
    spec = importlib.util.spec_from_file_location("ref_cauchy_good", path)
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    return mod


def shards(data: np.ndarray, parity: np.ndarray) -> dict:
    k = data.shape[1]
    out = {i: data[:, i] for i in range(k)}
    out.update({k + j: parity[:, j] for j in range(parity.shape[1])})
    return out


# -- (a) encode --------------------------------------------------------------
@pytest.mark.parametrize("regions", [1, 4])
@pytest.mark.parametrize("geometry", GEOMETRIES, ids=gid)
def test_async_encode_equals_the_synchronous_encode(geometry, regions):
    technique, k, m, w, ps = geometry
    tpu, cpu = pair(*geometry)
    assert tpu.core.layout == "packet"
    L = regions * w * ps
    rng = np.random.default_rng([k, m, w, ps, regions])
    for batch in (1, 3, 7, 16):          # 3 and 7 pad to 4 and 8
        d = rng.integers(0, 256, (batch, k, L), dtype=np.uint8)
        got = tpu.encode_batch_async(d).wait()
        assert got.shape == (batch, m, L)
        assert np.array_equal(got, tpu.core.encode_batch(d))
        assert np.array_equal(got, cpu.core.encode_batch(d)), \
            f"{gid(geometry)} batch {batch}: the async lane's parity " \
            f"is not the CPU plugin's"


@pytest.mark.parametrize("technique,profile", [
    ("reed_sol_van", {"k": "4", "m": "2"}),
    ("reed_sol_van", {"k": "4", "m": "2", "w": "16"}),
    ("reed_sol_r6_op", {"k": "4", "m": "2"}),
])
def test_byte_layout_codes_keep_their_entries(technique, profile):
    """Every technique the plugin registers: the byte-layout ones too
    (w=16 has no fast GF(2^8) matrix and keeps the bit-plane apply)."""
    assert set(tpu_plugin.TECHNIQUES) == \
        {g[0] for g in GEOMETRIES} | {"reed_sol_van", "reed_sol_r6_op"}
    codec = ecreg.instance().factory(
        "tpu", dict(profile, technique=technique))
    d = np.random.default_rng(3).integers(0, 256, (5, 4, 4096),
                                          dtype=np.uint8)
    assert np.array_equal(codec.encode_batch_async(d).wait(),
                          codec.core.encode_batch(d))


@pytest.mark.parametrize("geometry", CAUCHY, ids=gid)
def test_async_encode_equals_the_plain_reference(geometry):
    technique, k, m, w, ps = geometry
    tpu, _ = pair(*geometry)
    su = 4 * w * ps if k == 10 else w * ps      # 65536 and 4096
    obj = np.random.default_rng([k, 2147483659]).bytes(7 * k * su)
    want = cauchy_reference().shards_of(
        obj, {"technique": technique, "k": k, "m": m, "w": w,
              "packetsize": ps}, su)
    data = np.frombuffer(obj, np.uint8).reshape(7, k, su)
    parity = tpu.encode_batch_async(data).wait()
    got = [np.ascontiguousarray(a).tobytes()
           for _, a in sorted(shards(data, parity).items())]
    assert got == want


# -- (b) decode --------------------------------------------------------------
def decode_signatures(geometry, lost_sets, batch=3, regions=1):
    from ceph_tpu.utils.device_ledger import PHASE_ORDER
    technique, k, m, w, ps = geometry
    tpu, cpu = pair(*geometry)
    assert tpu.decode_async_supported()
    L = regions * w * ps
    d = np.random.default_rng([k, m, 11]).integers(
        0, 256, (batch, k, L), dtype=np.uint8)
    full = shards(d, cpu.core.encode_batch(d))
    for lost in lost_sets:
        present = {i: c for i, c in full.items() if i not in lost}
        h = tpu.decode_batch_async(present, L)
        rec = h.wait()
        assert set(rec) == set(lost)
        for e in lost:
            assert np.array_equal(rec[e], full[e]), \
                f"{gid(geometry)} lost {lost}: chunk {e}"
        assert not [p for p in PHASE_ORDER if h.ledger.get(p) is None]
        # and the synchronous decode, by the same combined rows
        sync = tpu.decode_batch(present, L)
        assert all(np.array_equal(sync[e], full[e]) for e in lost)


@pytest.mark.parametrize("n_lost", [1, 2, 3])
def test_async_decode_every_signature_at_k4m3(n_lost):
    decode_signatures(("cauchy_good", 4, 3, 8, 512),
                      list(itertools.combinations(range(7), n_lost)))


@pytest.mark.parametrize("n_lost", [1, 2, 3, 4])
def test_async_decode_a_seeded_sample_at_k10m4(n_lost):
    """Data and coding chunks, at the benchmark's chunk of 4 regions."""
    every = list(itertools.combinations(range(14), n_lost))
    rng = np.random.default_rng([10, 4, n_lost, 2147483659])
    picks = [every[i] for i in rng.choice(len(every), size=4,
                                          replace=False)]
    picks.append(tuple(range(14 - n_lost, 14)))     # coding chunks only
    decode_signatures(("cauchy_good", 10, 4, 8, 2048), picks, batch=2,
                      regions=4)


@pytest.mark.parametrize("geometry", [g for g in GEOMETRIES if g[1] == 4
                                      and g[0] != "cauchy_good"], ids=gid)
def test_async_decode_of_the_other_packet_codes(geometry):
    n = geometry[1] + geometry[2]
    decode_signatures(geometry, [(0,), (n - 1,), (1, n - 1), (0, 1)])


def test_recovery_rows_are_cached_per_signature():
    tpu, _ = pair("cauchy_good", 4, 3, 8, 512)
    core = tpu.core
    assert core.coding_matrix is None       # built from the bit-matrix
    chosen, erased = (0, 2, 3, 4), (1, 5, 6)
    rows_gf, rows_bits = core._recovery_rows(chosen, erased)
    assert rows_gf is None and rows_bits.shape == (3 * 8, 4 * 8)
    assert core._recovery_rows(chosen, erased)[1] is rows_bits


# -- (c) delta ---------------------------------------------------------------
@pytest.mark.parametrize("geometry", CAUCHY + [("liberation", 4, 2, 7, 512)],
                         ids=gid)
def test_async_delta_equals_encode_new_xor_encode_old(geometry):
    technique, k, m, w, ps = geometry
    tpu, cpu = pair(*geometry)
    assert tpu.delta_async_supported()
    L = 2 * w * ps
    rng = np.random.default_rng([k, m, 0xD417A])
    for n_dirty in (1, 2, k // 2, k):
        cols = tuple(sorted(rng.choice(k, size=n_dirty, replace=False)
                            .tolist()))
        old = rng.integers(0, 256, (5, k, L), dtype=np.uint8)
        new = old.copy()
        new[:, cols, :] = rng.integers(0, 256, (5, n_dirty, L),
                                       dtype=np.uint8)
        delta = (old ^ new)[:, cols, :]
        want = cpu.core.encode_batch(new) ^ cpu.core.encode_batch(old)
        assert np.array_equal(
            tpu.delta_encode_batch_async(delta, cols).wait(), want), cols
        # the synchronous twins: the plugin's, and CodecCore's on the
        # CPU plugin's core (what the batcher's twin calls)
        assert np.array_equal(tpu.delta_encode_batch(delta, cols), want)
        assert np.array_equal(cpu.core.delta_parity(delta, cols), want)


# -- the staged dispatch -----------------------------------------------------
def test_packet_dispatch_stages_whole_regions_in_the_single_chip_layout():
    """The quantum is a region of w packets, the ring prewarm made is
    the one a dispatch takes, and on a host with a mesh (tier-1 has 8
    virtual devices) the packet batch stays on one device."""
    tpu, cpu = pair("liberation", 4, 2, 7, 512)
    backend = tpu.core.backend
    pool = backend.staging
    L = 3 * 7 * 512                      # not a multiple of 128 * 7
    tpu.prewarm_geometry(L, batches=(3,))
    shape = (_bucket_batch(3), 4, L)
    assert shape in pool._made, sorted(pool._made)
    allocs = pool.allocs
    d = np.random.default_rng(5).integers(0, 256, (3, 4, L),
                                          dtype=np.uint8)
    h = tpu.encode_batch_async(d)
    assert np.array_equal(h.wait(), cpu.core.encode_batch(d))
    assert pool.allocs == allocs, "the prewarmed ring was not the one used"
    assert len(h.device_ids) == 1 and h.ledgers is None
    calls = dict(backend.kernel_calls)
    tpu.prewarm_decode(L, batches=(3,))
    tpu.prewarm_delta(L, batches=(3,))
    assert backend.kernel_calls["packet_xor_chain"] > \
        calls["packet_xor_chain"]
    assert {k for k in tpu_plugin._PREWARMED_SHAPES
            if k[0] in ("dec", "delta")
            and k[1:] == tpu._geometry(L)} \
        == {("dec",) + tpu._geometry(L), ("delta",) + tpu._geometry(L)}


def test_a_failed_packet_dispatch_hands_its_slot_back(monkeypatch):
    tpu, cpu = pair("cauchy_good", 4, 3, 8, 512)
    backend = tpu.core.backend
    d = np.random.default_rng(6).integers(0, 256, (2, 4, 4096),
                                          dtype=np.uint8)
    want = cpu.core.encode_batch(d)
    assert np.array_equal(tpu.encode_batch_async(d).wait(), want)

    def refuses(*a, **kw):
        raise RuntimeError("planted: the program cannot be built")
    with monkeypatch.context() as mp:
        mp.setattr(type(backend), "packet_chain_fn", refuses)
        for _ in range(2 * backend.staging.depth + 1):
            with pytest.raises(RuntimeError, match="planted"):
                tpu.encode_batch_async(d)
    t0 = time.monotonic()                # a leaked ring would stall 5 s
    assert np.array_equal(tpu.encode_batch_async(d).wait(), want)
    assert time.monotonic() - t0 < backend.staging.STALL_S


# -- (d) the batcher ---------------------------------------------------------
def make_batcher(**over):
    conf = {"ec_tpu_batch_stripes": 1024,
            "ec_tpu_queue_window_us": 1000,
            "ec_tpu_fallback_cpu": False}
    conf.update(over)
    EncodeBatcher.reset_learning()
    return EncodeBatcher(conf)


def through(submit, *args, timeout=60):
    got, ev = {}, threading.Event()

    def cb(res):
        got["res"] = res
        ev.set()
    submit(*args, cb)
    assert ev.wait(timeout), "the batcher never called back"
    return got["res"]


@pytest.mark.parametrize("geometry,regions", [(CAUCHY[0], 1),
                                              (CAUCHY[1], 4)],
                         ids=["k4m3", "k10m4"])
def test_batcher_lanes_on_a_packet_codec(geometry, regions):
    technique, k, m, w, ps = geometry
    tpu, _ = pair(*geometry)
    cs = regions * w * ps
    sinfo = ecutil.StripeInfo(k, k * cs)
    b = make_batcher()
    try:
        rng = np.random.default_rng([k, 17])
        obj = rng.bytes(7 * sinfo.stripe_width)
        # the CPU plugin's core, by ecutil's batched path (its own
        # per-stripe encode() would pad a stripe to the code's
        # alignment, which is the mon's business: ROADMAP.md)
        twin = b.cpu_twin(tpu, sinfo)
        assert type(twin.base).__name__ == "CauchyGood"
        want = ecutil.encode(sinfo, twin, obj)
        chunks = through(b.submit, tpu, sinfo, obj)
        assert {i: bytes(c) for i, c in chunks.items()} == want
        # decode: two data chunks and a coding chunk lost
        lost = {0, k - 1, k + 1}
        have = {i: c for i, c in want.items() if i not in lost}
        rec = through(b.submit_decode, tpu, sinfo, have, set(lost))
        assert {i: bytes(c) for i, c in rec.items()} == \
            {i: want[i] for i in lost}
        # delta: columns 1 and 2 of every stripe overwritten
        cols = (1, 2)
        data = np.frombuffer(obj, np.uint8).reshape(7, k, cs)
        new = data.copy()
        new[:, cols, :] ^= rng.integers(1, 256, (7, 2, cs), dtype=np.uint8)
        delta = np.ascontiguousarray((data ^ new)[:, cols, :])
        dpar = through(b.submit_delta, tpu, sinfo, delta, cols)
        new_want = ecutil.encode(sinfo, twin, new.tobytes())
        for j in range(m):
            assert bytes(np.frombuffer(want[k + j], np.uint8)
                         ^ np.frombuffer(bytes(dpar[k + j]), np.uint8)) \
                == new_want[k + j]
        assert (b.reqs_total, b.dec_reqs, b.delta_reqs) == (1, 1, 1)
        assert (b.cpu_reqs, b.dec_cpu_reqs, b.delta_cpu_reqs) == (0, 0, 0)
        assert b.device_errors == 0
    finally:
        b.stop()


# -- (e) a live cluster ------------------------------------------------------
K, M, PS, SU = 4, 3, 512, 4096
WIDTH = K * SU
SIZES = {"one_stripe": WIDTH, "several": 5 * WIDTH,
         "ragged": 3 * WIDTH + 1234}


class Pool:
    """A 7-OSD cluster with one cauchy_good k=4 m=3 packetsize=512 pool
    (4 KiB unit: a chunk is one region), kept for the tests below,
    which run in the file's order."""

    def __init__(self):
        self.cl = Cluster(n_osds=7, conf=make_conf(
            ec_tpu_fallback_cpu=False, mon_osd_down_out_interval=600.0))
        self.cl.start()
        try:
            for i in range(7):
                self.cl.wait_for_osd_up(i, 30)
            self.cl.create_ec_profile(
                "cauchy", plugin="tpu", technique="cauchy_good",
                k=str(K), m=str(M), packetsize=str(PS))
            self.cl.create_pool("cpool", "erasure",
                                erasure_code_profile="cauchy")
            ret, rs, _ = self.cl.mon_command({
                "prefix": "osd pool set", "pool": "cpool",
                "var": "allow_ec_overwrites", "val": "true"})
            assert ret == 0, rs
            self.rad = self.cl.rados()
            self.rad.wait_for_epoch(self.cl.mon.osdmap.epoch, 10)
            self.io = self.rad.open_ioctx("cpool")
            self.cl.wait_for_clean(60)
        except BaseException:
            self.cl.stop()
            raise
        self.cpu = ecreg.instance().factory("jerasure", {
            "technique": "cauchy_good", "k": str(K), "m": str(M),
            "packetsize": str(PS)})
        self.objects = {}
        self.down = []

    def want_shards(self, name: str) -> list:
        """What the CPU plugin stores of the object, zero-padded to its
        last stripe's end."""
        obj = self.objects[name]
        obj += bytes(-len(obj) % WIDTH)
        data = np.frombuffer(obj, np.uint8).reshape(-1, K, SU)
        full = shards(data, self.cpu.core.encode_batch(data))
        return [np.ascontiguousarray(full[s]).tobytes()
                for s in range(K + M)]

    def stored(self, name: str) -> dict:
        """shard -> [bytes held by a live store]."""
        out = {}
        for osd_id, store in sorted(self.cl.stores.items()):
            if self.cl.osds.get(osd_id) is None:
                continue
            for coll in store.list_collections():
                for obj in store.collection_list(coll):
                    if obj.oid == name and obj.shard >= 0:
                        out.setdefault(obj.shard, []).append(
                            bytes(store.read(coll, obj)))
        return out

    def wrong_shards(self, name: str, n_shards: int = K + M) -> list:
        want, got = self.want_shards(name), self.stored(name)
        bad = [s for s in range(K + M) for c in got.get(s, [])
               if c != want[s]]
        assert len(got) == n_shards, (name, sorted(got))
        return bad

    def lanes(self) -> dict:
        out = dict.fromkeys(("enc", "enc_twin", "dec", "dec_twin",
                             "delta", "delta_twin", "errors"), 0)
        for osd in self.cl.osds.values():
            if osd is None:
                continue
            b = osd.encode_batcher
            for key, v in (("enc", b.reqs_total), ("enc_twin", b.cpu_reqs),
                           ("dec", b.dec_reqs),
                           ("dec_twin", b.dec_cpu_reqs),
                           ("delta", b.delta_reqs),
                           ("delta_twin", b.delta_cpu_reqs),
                           ("errors", b.device_errors)):
                out[key] += v
        return out

    def overwrite(self, name: str, off: int, patch: bytes) -> None:
        from ceph_tpu.client.rados import RadosError
        deadline = time.monotonic() + 20
        while True:               # the pool flag reaches the OSDs late
            try:
                self.io.write(name, patch, off)
                break
            except RadosError as e:
                if e.errno != 95 or time.monotonic() > deadline:
                    raise
                time.sleep(0.2)
        obj = bytearray(self.objects[name])
        obj[off:off + len(patch)] = patch
        self.objects[name] = bytes(obj)


@pytest.fixture(scope="module")
def pool():
    p = Pool()
    yield p
    p.cl.stop()


@pytest.mark.parametrize("name", SIZES)
def test_cluster_stores_the_cpu_plugins_shards(pool, name):
    obj = np.random.default_rng([SIZES[name], 30]).bytes(SIZES[name])
    pool.io.write_full(name, obj)
    pool.objects[name] = obj
    assert pool.io.read(name, length=len(obj) + 1) == obj
    assert pool.wrong_shards(name) == []
    lanes = pool.lanes()
    assert lanes["enc"] > 0 and lanes["enc_twin"] == 0 \
        and lanes["errors"] == 0, lanes


def test_cluster_sub_stripe_overwrite_keeps_the_codes_parity(pool):
    before = pool.lanes()
    patch = np.random.default_rng(31).bytes(SU)
    pool.overwrite("several", WIDTH + SU, patch)       # one whole chunk
    pool.overwrite("several", 3 * WIDTH + 100, patch[:700])  # inside one
    assert pool.io.read("several", length=SIZES["several"]) == \
        pool.objects["several"]
    assert pool.wrong_shards("several") == []
    lanes = pool.lanes()
    assert lanes["delta"] > before["delta"], \
        "no overwrite took the parity-delta lane"
    assert lanes["delta_twin"] == 0 and lanes["errors"] == 0, lanes


@pytest.mark.parametrize("n_down", [1, 2])
def test_cluster_reads_back_with_osds_down(pool, n_down):
    """min_size is k+1 = 5 of 7: two is the most a served read can
    lose (three erasures are the codec tests' above)."""
    victim = n_down - 1                  # osd.0, then osd.1 as well
    pool.cl.kill_osd(victim)
    pool.cl.wait_for_osd_down(victim, 30)
    pool.down.append(victim)
    pool.rad.wait_for_epoch(pool.cl.mon.osdmap.epoch, 10)
    before = pool.lanes()
    for name, obj in pool.objects.items():
        assert pool.io.read(name, length=len(obj) + 1) == obj, name
    lanes = pool.lanes()
    assert lanes["dec_twin"] == before["dec_twin"] == 0 \
        and lanes["errors"] == 0, lanes


def decode_counts(pool) -> dict:
    """The decode lane's row and signature counters over the live
    OSDs (dump_device's ``lanes.decode``)."""
    out = dict.fromkeys(("reqs", "signatures", "rows_out", "rows_wanted"),
                        0)
    for osd in pool.cl.osds.values():
        if osd is not None:
            for key, v in osd.encode_batcher.device_dump()[
                    "lanes"]["decode"].items():
                if key in out:
                    out[key] += v
    return out


def test_cluster_decode_lane_counts_rows_and_signatures(pool):
    """Two OSDs down (ISSUE 34): every object reads back, and the
    decode lane's counters move as the reads say.  A read gathers k=4
    of the 7 shards, so a dispatch produces all 3 absent chunks a
    stripe where its riders lost 1 or 2 data chunks; the signatures
    were all dispatched by the reads before, so none is new."""
    assert pool.down == [0, 1]
    known = len(EncodeBatcher._dec_signatures)
    assert known >= 1
    before = decode_counts(pool)
    assert 1 <= before["signatures"] <= known
    for name, obj in pool.objects.items():
        assert pool.io.read(name, length=len(obj) + 1) == obj, name
    after = decode_counts(pool)
    d = {key: after[key] - before[key] for key in after}
    stripes = sum(-(-len(o) // WIDTH) for o in pool.objects.values())
    assert d["reqs"] >= 1 and d["signatures"] == 0, d
    assert len(EncodeBatcher._dec_signatures) == known
    assert d["rows_out"] % M == 0 and \
        1 <= d["rows_out"] // M <= stripes, (d, stripes)
    assert d["rows_out"] // M <= d["rows_wanted"] <= \
        2 * d["rows_out"] // M, d
    backend = tpu_plugin.shared_backend()
    assert backend.row_sets_bound >= known
    assert backend.row_programs_built >= 1


def test_cluster_overwrites_degraded_and_recovers_every_shard(pool):
    assert pool.down == [0, 1]
    patch = np.random.default_rng(32).bytes(2 * SU)
    pool.overwrite("several", 2 * WIDTH, patch)
    pool.overwrite("ragged", SU // 2, patch[:SU])
    obj = np.random.default_rng(33).bytes(2 * WIDTH)
    pool.io.write_full("written_degraded", obj)
    pool.objects["written_degraded"] = obj
    for name, want in pool.objects.items():
        assert pool.io.read(name, length=len(want) + 1) == want, name
    for osd_id in pool.down:
        pool.cl.revive_osd(osd_id)
        pool.cl.wait_for_osd_up(osd_id, 30)
    pool.down.clear()
    pool.cl.wait_for_clean(120)
    for name in pool.objects:
        assert pool.wrong_shards(name) == [], name
    lanes = pool.lanes()
    assert lanes["enc_twin"] == lanes["dec_twin"] == \
        lanes["delta_twin"] == lanes["errors"] == 0, lanes
