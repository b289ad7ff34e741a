"""Cross-op TPU stripe batcher tests.

Covers the SURVEY §3.1 batching-point claim end-to-end: the OSD-level
coalescer (ceph_tpu/osd/batcher.py) must gather encode work from
multiple concurrent write ops into ONE device call, produce chunk maps
bit-identical to the synchronous ecutil.encode path, consume the
``ec_tpu_batch_stripes`` / ``ec_tpu_queue_window_us`` knobs, and keep
the live-cluster write path green while doing so."""
import os
import threading
import time

import numpy as np
import pytest

from ceph_tpu.cluster import Cluster
from ceph_tpu.cluster import test_config as make_conf
from ceph_tpu.ec import registry as ecreg
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.batcher import _LANES, EncodeBatcher, _geometry_key


def make_batcher(**over):
    conf = {"ec_tpu_batch_stripes": 1024,
            "ec_tpu_queue_window_us": 30_000}
    conf.update(over)
    EncodeBatcher.reset_learning()   # crossover state is process-wide
    return EncodeBatcher(conf)


@pytest.fixture
def codec():
    return ecreg.instance().factory(
        "tpu", {"k": "2", "m": "1", "technique": "reed_sol_van"})


LANE_NAMES = sorted(_LANES)


class LaneDrive:
    """One small group on one of the batcher's three lanes, for
    the tests that hold the ONE ladder, learner and route note on each
    of them: how to submit it, the input bytes the router judges it
    by, the queue key it rides under and the answer it must give."""

    def __init__(self, name, codec, nstripes=2):
        self.lane = lane = _LANES[name]
        self.calls, _reqs, self.twin_reqs, _coalesced = lane.counters
        sinfo = self.sinfo = ecutil.StripeInfo(2, 8192)
        geom = _geometry_key(codec, sinfo)
        data = os.urandom(nstripes * 8192)
        enc = ecutil.encode(sinfo, codec, data)
        if name == "enc":
            self.key = ("enc",) + geom
            self.args, self.want = (data,), enc
            self.nbytes, self.out_bytes = len(data), len(data) // 2
        elif name == "dec":
            self.key = ("dec", geom, (0, 2), (1,))
            self.args = ({0: enc[0], 2: enc[2]}, {1})
            self.want = {1: enc[1]}
            self.nbytes = nstripes * 2 * 4096
            self.out_bytes = nstripes * 4096
        else:
            self.key = ("delta", geom, (0,))
            delta = np.frombuffer(os.urandom(nstripes * 4096),
                                  np.uint8).reshape(nstripes, 1, 4096)
            self.args = (delta, (0,))
            parity = codec.delta_encode_batch(delta, (0,))
            self.want = {2: parity[:, 0].tobytes()}
            self.nbytes, self.out_bytes = delta.nbytes, parity.nbytes
        self._submit = {"enc": "submit", "dec": "submit_decode",
                        "delta": "submit_delta"}[name]
        self._codec = codec

    def submit(self, b, cb):
        getattr(b, self._submit)(self._codec, self.sinfo, *self.args, cb)

    def request(self):
        from ceph_tpu.osd import batcher
        cls = {"enc": batcher._Req, "dec": batcher._DecReq,
               "delta": batcher._DeltaReq}[self.lane.name]
        return cls(self._codec, self.sinfo, *self.args, lambda c: None)

    def set_crossover(self, value):
        setattr(EncodeBatcher, self.lane.crossover, value)

    def crossover(self):
        return getattr(EncodeBatcher, self.lane.crossover)


def test_two_ops_share_one_device_call(codec):
    """Two concurrent submits inside the window coalesce into a single
    encode_batch_async call, and each op's chunks are bit-exact with
    the synchronous path."""
    b = make_batcher()
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        d1 = os.urandom(3 * 8192)        # 3 stripes
        d2 = os.urandom(5 * 8192)        # 5 stripes
        got = {}
        done = threading.Event()

        def cb(tag):
            def _cb(chunks):
                got[tag] = chunks
                if len(got) == 2:
                    done.set()
            return _cb

        b.submit(codec, sinfo, d1, cb("a"))
        b.submit(codec, sinfo, d2, cb("b"))
        assert done.wait(30)
        assert b.calls == 1, "expected ONE device call for both ops"
        assert b.reqs_coalesced == 2
        assert got["a"] == ecutil.encode(sinfo, codec, d1)
        assert got["b"] == ecutil.encode(sinfo, codec, d2)
    finally:
        b.stop()


def test_different_geometries_never_mix(codec):
    other = ecreg.instance().factory(
        "tpu", {"k": "3", "m": "2", "technique": "reed_sol_van"})
    b = make_batcher()
    try:
        s2 = ecutil.StripeInfo(2, 8192)
        s3 = ecutil.StripeInfo(3, 12288)
        d2 = os.urandom(2 * 8192)
        d3 = os.urandom(2 * 12288)
        got = {}
        done = threading.Event()

        def cb(tag):
            def _cb(chunks):
                got[tag] = chunks
                if len(got) == 2:
                    done.set()
            return _cb

        b.submit(codec, s2, d2, cb("k2"))
        b.submit(other, s3, d3, cb("k3"))
        assert done.wait(30)
        assert b.calls == 2              # one per geometry
        assert got["k2"] == ecutil.encode(s2, codec, d2)
        assert got["k3"] == ecutil.encode(s3, other, d3)
    finally:
        b.stop()


def test_stripe_budget_flushes_before_window(codec):
    """Hitting ec_tpu_batch_stripes releases the batch without waiting
    out the (deliberately huge) window."""
    b = make_batcher(ec_tpu_batch_stripes=4,
                     ec_tpu_queue_window_us=60_000_000)
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        data = os.urandom(4 * 8192)      # meets the budget alone
        done = threading.Event()
        b.submit(codec, sinfo, data, lambda chunks: done.set())
        assert done.wait(30), \
            "budget-full batch should flush immediately"
    finally:
        b.stop()


def test_non_batchable_codec_encodes_inline():
    jr = ecreg.instance().factory("jerasure", {"k": "2", "m": "1"})
    b = make_batcher()
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        data = os.urandom(8192)
        out = {}
        b.submit(jr, sinfo, data, out.update)
        # inline: the callback already ran on this thread
        assert out == ecutil.encode(sinfo, jr, data)
        assert b.calls == 0
    finally:
        b.stop()


def test_collector_survives_raising_continuation(codec, capsys):
    """A continuation that raises must not kill the collector thread
    (that would wedge every EC write on the OSD)."""
    b = make_batcher()
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        data = os.urandom(8192)

        def bad_cb(chunks):
            raise RuntimeError("continuation exploded")

        b.submit(codec, sinfo, data, bad_cb)
        # the next op must still encode fine on the same collector
        done = threading.Event()
        out = {}

        def good_cb(chunks):
            out.update(chunks)
            done.set()

        deadline = time.monotonic() + 30
        while not done.is_set() and time.monotonic() < deadline:
            b.submit(codec, sinfo, data, good_cb)
            done.wait(1)
        assert done.is_set(), "collector died after a bad continuation"
        assert out == ecutil.encode(sinfo, codec, data)
    finally:
        b.stop()


def test_adaptive_crossover_routes_small_batches_to_cpu(codec):
    """A device whose round trip loses to the CPU twin must push the
    learned crossover up, after which small batches encode on the CPU
    — bit-exactly — and the stats show it."""
    b = make_batcher(ec_tpu_queue_window_us=1000)
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        data = os.urandom(2 * 8192)

        real_async = type(codec).encode_batch_async

        class SlowBatch:
            def __init__(self, inner):
                self.inner = inner

            def wait(self):
                time.sleep(0.5)      # simulated terrible link
                return self.inner.wait()

        def slow_async(self_codec, arr):
            return SlowBatch(real_async(self_codec, arr))

        type(codec).encode_batch_async = slow_async
        try:
            done = threading.Event()
            b.submit(codec, sinfo, data, lambda c: done.set())
            assert done.wait(30)
            assert b._min_device_bytes > len(data), \
                "losing device call should raise the crossover"
            # subsequent small batches take the CPU path
            out = {}
            done2 = threading.Event()
            b.submit(codec, sinfo, data,
                     lambda c: (out.update(c), done2.set()))
            assert done2.wait(30)
            assert b.cpu_reqs >= 1
            assert out == ecutil.encode(sinfo, codec, data)
        finally:
            type(codec).encode_batch_async = real_async
    finally:
        b.stop()


def test_dispatch_rides_mesh_on_multidevice_host(codec):
    """ISSUE 12 tentpole: on a multi-device host (the conftest's
    8-device virtual CPU mesh) the batcher's production dispatch must
    shard over the mesh INSIDE the backend (jax_engine _staged_put
    lays the staging slot out with the (dp, None, sp) NamedSharding),
    bit-exact with the synchronous path — including batches that need
    dp padding."""
    import jax

    assert len(jax.devices()) > 1
    backend = codec.core.backend
    info = backend.mesh_info()
    assert info is not None, "multi-device host must resolve a mesh"
    assert info["dp"] * info["sp"] == info["n_devices"] == 8
    # the codec's async entry (the batcher's dispatch seam) returns a
    # handle whose device output spans every mesh chip — the
    # production path rides the sharded layout, one dispatch = one
    # sharded GF matmul — and wait() fans the phase ledger out into
    # one lane per chip
    probe = np.zeros((5, 2, 256), dtype=np.uint8)
    ab = codec.encode_batch_async(probe)
    devs = sorted(d.id for d in ab._dev.sharding.device_set)
    assert devs == info["device_ids"]
    ab.wait()
    assert ab.ledgers is not None and len(ab.ledgers) == 8
    assert sorted(led["device"] for led in ab.ledgers) == devs
    bat = make_batcher()
    sinfo = ecutil.StripeInfo(2, 2 * 256)
    rng = np.random.default_rng(3)
    # 5 stripes: not a multiple of dp=4 -> exercises zero-stripe padding
    data = rng.integers(0, 256, (5, 2, 256), dtype=np.uint8).tobytes()
    got, ev = {}, threading.Event()
    bat.submit(codec, sinfo, data, lambda ch: (got.update(ch), ev.set()))
    assert ev.wait(30)
    bat.stop()
    assert got == ecutil.encode(sinfo, codec, data)


def test_cluster_concurrent_writes_coalesce():
    """Live cluster: concurrent client writes across PGs land in
    shared device calls on the primaries (the README's 'gathers
    stripes from many in-flight ops into one device call' claim)."""
    # adaptive CPU routing off: this test asserts DEVICE coalescing
    conf = make_conf(ec_tpu_queue_window_us=100_000,
                     ec_tpu_fallback_cpu=False)
    with Cluster(n_osds=3, conf=conf) as c:
        for i in range(3):
            c.wait_for_osd_up(i, 20)
        c.create_ec_profile("eb", plugin="tpu", k="2", m="1")
        c.create_pool("ecb", "erasure", erasure_code_profile="eb")
        io = c.rados().open_ioctx("ecb")
        blob = os.urandom(24 << 10)
        comps = [io.aio_write_full(f"o{i}", blob) for i in range(16)]
        for comp in comps:
            assert comp.wait(30) == 0
        coalesced = sum(o.encode_batcher.reqs_coalesced
                        for o in c.osds.values() if o is not None)
        calls = sum(o.encode_batcher.calls
                    for o in c.osds.values() if o is not None)
        reqs = sum(o.encode_batcher.reqs_total
                   for o in c.osds.values() if o is not None)
        assert reqs == 16, "every write encodes through the batcher"
        assert coalesced >= 2, \
            f"no cross-op coalescing observed ({calls} calls/16 ops)"
        assert calls < reqs
        for i in range(16):
            assert io.read(f"o{i}") == blob


def test_oversized_group_tiles_at_max_stripes(codec):
    """A dispatch group larger than ec_tpu_batch_stripes is tiled into
    multiple device calls (bounded per-call memory + a bounded compile
    shape set), and the reassembled chunks stay bit-exact."""
    b = make_batcher(ec_tpu_batch_stripes=4,
                     ec_tpu_queue_window_us=30_000)
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        d1 = os.urandom(7 * 8192)        # 7 stripes > 4-stripe tile
        d2 = os.urandom(3 * 8192)
        got = {}
        done = threading.Event()

        def cb(tag):
            def _cb(chunks):
                got[tag] = chunks
                if len(got) == 2:
                    done.set()
            return _cb

        b.submit(codec, sinfo, d1, cb("a"))
        b.submit(codec, sinfo, d2, cb("b"))
        assert done.wait(30)
        assert got["a"] == ecutil.encode(sinfo, codec, d1)
        assert got["b"] == ecutil.encode(sinfo, codec, d2)
    finally:
        b.stop()


def test_prewarm_measures_cpu_rate_ahead_of_ops(codec):
    """prewarm() at EC-backend build fills the crossover router's CPU
    rate for the geometry BEFORE any client op, and is once-per-
    geometry process-wide (VERDICT r3 next #1a)."""
    from ceph_tpu.osd.batcher import _geometry_key
    b = make_batcher()
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        key = _geometry_key(codec, sinfo)
        assert key not in EncodeBatcher._cpu_bps
        b.prewarm(codec, sinfo)
        deadline = time.time() + 20
        while key not in EncodeBatcher._cpu_bps \
                and time.time() < deadline:
            time.sleep(0.05)
        assert EncodeBatcher._cpu_bps.get(key, 0) > 0, \
            "prewarm did not measure the CPU twin rate"
        assert key in EncodeBatcher._warmed
        # second prewarm is a no-op (already warmed)
        b.prewarm(codec, sinfo)
    finally:
        b.stop()


def test_stop_drains_inflight_work(codec):
    """stop() must not return while a device call + continuation are
    still in flight — OSD shutdown unmounts the store right after, and
    a late continuation would land in an unmounted store (the r3
    driver's teardown crash)."""
    b = make_batcher(ec_tpu_queue_window_us=1000)
    sinfo = ecutil.StripeInfo(2, 8192)
    done = threading.Event()
    orig = codec.encode_batch_async

    def slow(data):
        time.sleep(0.8)              # a cold compile / busy device
        return orig(data)
    codec.encode_batch_async = slow
    try:
        b.submit(codec, sinfo, os.urandom(8192), lambda _c: done.set())
        time.sleep(0.2)              # collector picks the group up
        b.stop()
        assert done.is_set(), \
            "stop() returned before the in-flight continuation ran"
    finally:
        del codec.encode_batch_async


def test_decode_requests_coalesce_per_signature(codec):
    """VERDICT r4 Next #3: concurrent reconstructions of the SAME
    erasure signature (what a rebuild produces for every object) share
    one batched decode call, bit-exact with the synchronous path."""
    b = make_batcher()
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        d1 = os.urandom(3 * 2 * 8192)    # 3 stripes
        d2 = os.urandom(5 * 2 * 8192)    # 5 stripes
        enc1 = ecutil.encode(sinfo, codec, d1)
        enc2 = ecutil.encode(sinfo, codec, d2)
        have1 = {0: enc1[0], 2: enc1[2]}     # shard 1 lost
        have2 = {0: enc2[0], 2: enc2[2]}
        got = {}
        done = threading.Event()

        def cb(tag):
            def _cb(dec):
                got[tag] = dec
                if len(got) == 2:
                    done.set()
            return _cb

        b.submit_decode(codec, sinfo, have1, {1}, cb("a"))
        b.submit_decode(codec, sinfo, have2, {1}, cb("b"))
        assert done.wait(30)
        assert b.dec_calls == 1, "same signature must share one call"
        assert b.dec_coalesced == 2
        assert got["a"] == {1: enc1[1]}
        assert got["b"] == {1: enc2[1]}
    finally:
        b.stop()


def test_decode_signatures_never_mix(codec):
    """Different erasure signatures (different shards lost) must not
    share a decode call — their row sets differ."""
    b = make_batcher()
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        d = os.urandom(2 * 2 * 8192)
        enc = ecutil.encode(sinfo, codec, d)
        got = {}
        done = threading.Event()

        def cb(tag):
            def _cb(dec):
                got[tag] = dec
                if len(got) == 2:
                    done.set()
            return _cb

        b.submit_decode(codec, sinfo, {0: enc[0], 2: enc[2]}, {1},
                        cb("s1"))
        b.submit_decode(codec, sinfo, {1: enc[1], 2: enc[2]}, {0},
                        cb("s0"))
        assert done.wait(30)
        assert b.dec_calls == 2
        assert b.dec_coalesced == 0
        assert got["s1"] == {1: enc[1]}
        assert got["s0"] == {0: enc[0]}
    finally:
        b.stop()


def test_cpu_routed_group_still_coalesces(codec):
    """When the learned crossover routes a group off the device, the
    group still encodes as ONE batched twin call (native C++ when
    available) — the coalescing win survives CPU routing (VERDICT r4
    Weak #2: '0 coalesced, 9 routed to cpu twin' must be impossible
    for a multi-op group)."""
    b = make_batcher()
    try:
        EncodeBatcher._min_device_bytes = 1 << 30   # force CPU route
        EncodeBatcher._probe_tick = 1               # avoid probe tick
        sinfo = ecutil.StripeInfo(2, 8192)
        d1 = os.urandom(3 * 8192)
        d2 = os.urandom(5 * 8192)
        got = {}
        done = threading.Event()

        def cb(tag):
            def _cb(chunks):
                got[tag] = chunks
                if len(got) == 2:
                    done.set()
            return _cb

        b.submit(codec, sinfo, d1, cb("a"))
        b.submit(codec, sinfo, d2, cb("b"))
        assert done.wait(30)
        assert b.calls == 0, "device must not be touched"
        assert b.cpu_calls == 1, "ONE batched twin call for the group"
        assert b.reqs_coalesced == 2
        assert b.cpu_reqs == 2
        assert got["a"] == ecutil.encode(sinfo, codec, d1)
        assert got["b"] == ecutil.encode(sinfo, codec, d2)
    finally:
        b.stop()
        EncodeBatcher.reset_learning()


def test_batch_twin_is_bit_exact_for_packet_codec():
    """The native-backed _BatchTwin must be bit-exact for packet-layout
    (cauchy) geometries too — the rebuild path's decode twin."""
    cauchy = ecreg.instance().factory(
        "tpu", {"k": "4", "m": "2", "technique": "cauchy_good",
                "packetsize": "128"})
    b = make_batcher()
    try:
        sinfo = ecutil.StripeInfo(4, 4 * 8 * 128)
        twin = b.cpu_twin(cauchy, sinfo)
        rng = np.random.default_rng(11)
        data = rng.integers(0, 256, (6, 4, 8 * 128), dtype=np.uint8)
        assert np.array_equal(twin.encode_batch(data),
                              cauchy.encode_batch(data))
        parity = cauchy.encode_batch(data)
        present = {0: data[:, 0], 2: data[:, 2], 3: data[:, 3],
                   4: parity[:, 0]}
        rec = twin.decode_batch(present, 8 * 128)
        assert np.array_equal(rec[1], data[:, 1])
    finally:
        b.stop()


def test_rebuild_decodes_ride_the_batcher():
    """Live cluster: a rebuild's recovery decodes go through the
    OSD batcher (dec_reqs > 0 on the recovering primaries) and the
    rebuilt data is intact."""
    conf = make_conf(ec_tpu_queue_window_us=5_000)
    with Cluster(n_osds=3, conf=conf) as c:
        for i in range(3):
            c.wait_for_osd_up(i, 20)
        c.create_ec_profile("er", plugin="tpu", k="2", m="1")
        c.create_pool("ecr", "erasure", erasure_code_profile="er")
        io = c.rados().open_ioctx("ecr")
        blob = os.urandom(64 << 10)
        for i in range(8):
            io.write_full(f"r{i}", blob)
        c.wait_for_clean(30)
        c.kill_osd(1, lose_data=True)
        c.wait_for_osd_down(1)
        c.revive_osd(1)
        c.wait_for_osd_up(1)
        c.wait_for_clean(60)
        dec_reqs = sum(o.encode_batcher.dec_reqs
                       for o in c.osds.values() if o is not None)
        assert dec_reqs > 0, \
            "recovery decodes did not ride the batcher"
        for i in range(8):
            assert io.read(f"r{i}") == blob


def test_stage_counters_and_tracked_events(codec):
    """The dedicated ec_batcher perf subsystem fills the per-stage
    histograms/counters for a device-routed group, the cumulative
    stage clocks advance, and a tracked op receives the batcher's
    dispatch stage event."""
    from ceph_tpu.utils.optracker import OpTracker
    from ceph_tpu.utils.perf import PerfCountersCollection
    EncodeBatcher.reset_learning()
    coll = PerfCountersCollection()
    b = EncodeBatcher({"ec_tpu_batch_stripes": 1024,
                       "ec_tpu_queue_window_us": 1000},
                      perf_coll=coll)
    try:
        top = OpTracker().create("osd_op(client.1.1 ...)")
        sinfo = ecutil.StripeInfo(2, 8192)
        data = os.urandom(4 * 8192)
        done = threading.Event()
        b.submit(codec, sinfo, data, lambda _c: done.set(),
                 tracked=top)
        assert done.wait(30)
        assert "ec:batch_dispatched" in [e for _, e in top.events]
        d = coll.perf_dump()["ec_batcher"]
        assert sum(d["queue_wait_us"]["buckets"]) == 1
        assert sum(d["batch_stripes"]["buckets"]) == 1
        assert sum(d["dispatch_ms"]["buckets"]) == 1
        assert d["device_reqs"] == 1 and d["cpu_reqs"] == 0
        assert d["h2d_bytes"] == len(data)
        assert d["d2h_bytes"] > 0            # parity came back
        assert b.stage_seconds["queue_wait"] > 0
        # the fenced window is fully attributed across the legs
        dev = (b.stage_seconds["h2d"] + b.stage_seconds["device"]
               + b.stage_seconds["d2h"])
        assert dev > 0
    finally:
        b.stop()


def test_admission_window_grows_under_pressure_and_cuts(codec):
    """The coalescing window is admission-aware: submits arriving at
    window expiry extend it (bounded), and a cycle that closes with no
    joiners shrinks it back toward the base."""
    b = make_batcher(ec_tpu_queue_window_us=80_000)
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        base = b.window_base_s
        got = []
        done = threading.Event()

        def cb(chunks):
            got.append(chunks)
            if len(got) >= 2:
                done.set()

        b.submit(codec, sinfo, os.urandom(2 * 8192), cb)
        time.sleep(0.04)                  # mid-window: a joiner lands
        b.submit(codec, sinfo, os.urandom(2 * 8192), cb)
        assert done.wait(30)
        assert b.window_grows >= 1, \
            "late joiner did not extend the admission window"
        assert b.dyn_window_s > base
        assert b.dyn_window_s <= b.window_max_s
        assert b.queue_depth_hwm >= 2

        # a lone op afterwards closes its window with no joiners: the
        # window must shrink back toward base
        lone = threading.Event()
        b.submit(codec, sinfo, os.urandom(2 * 8192),
                 lambda _c: lone.set())
        assert lone.wait(30)
        assert b.window_cuts >= 1, \
            "drained queue did not cut the admission window"
        assert b.dyn_window_s < 2 * base + 1e-9
    finally:
        b.stop()


def test_view_based_encode_bit_exact_with_bytes_path(codec):
    """memoryview / bytearray / ndarray submissions must produce
    chunks byte-identical to the synchronous bytes-input encode (the
    zero-copy rework may change buffer types, never content)."""
    sinfo = ecutil.StripeInfo(2, 8192)
    data = os.urandom(4 * 8192)
    ref = ecutil.encode(sinfo, codec, data)
    for variant in (memoryview(data), bytearray(data),
                    np.frombuffer(data, dtype=np.uint8)):
        b = make_batcher(ec_tpu_queue_window_us=1_000)
        try:
            out = {}
            ev = threading.Event()

            def cb(chunks):
                out["c"] = chunks
                ev.set()

            b.submit(codec, sinfo, variant, cb)
            assert ev.wait(30)
            got = out["c"]
            assert set(got) == set(ref)
            for s in ref:
                assert bytes(got[s]) == bytes(ref[s]), \
                    f"shard {s} diverged for {type(variant).__name__}"
        finally:
            b.stop()


def test_cluster_workload_device_routes_and_window_adapts():
    """Cluster-shaped workload: concurrent client writes must land in
    at least one DEVICE-routed encode group, and the admission window
    must both grow (overlapping waves) and cut (drained solo ops).
    The time window is the classic OSD's: crimson's reactor cuts the
    window at the end of every tick (tick_flush), so it never grows
    there."""
    conf = make_conf(osd_backend="classic",
                     ec_tpu_queue_window_us=150_000,
                     ec_tpu_fallback_cpu=False)
    with Cluster(n_osds=3, conf=conf) as c:
        for i in range(3):
            c.wait_for_osd_up(i, 20)
        c.create_ec_profile("aw", plugin="tpu", k="2", m="1")
        c.create_pool("awp", "erasure", erasure_code_profile="aw")
        io = c.rados().open_ioctx("awp")
        blob = os.urandom(48 << 10)
        # wave 1 opens the windows; wave 2 lands mid-window → grow
        w1 = [io.aio_write_full(f"a{i}", blob) for i in range(8)]
        time.sleep(0.07)
        w2 = [io.aio_write_full(f"b{i}", blob) for i in range(8)]
        for comp in w1 + w2:
            assert comp.wait(30) == 0
        batchers = [o.encode_batcher for o in c.osds.values()
                    if o is not None]
        assert sum(b.calls for b in batchers) >= 1, \
            "no device-routed encode group in a cluster workload"
        assert sum(b.cpu_reqs for b in batchers) == 0
        assert sum(b.window_grows for b in batchers) >= 1, \
            "overlapping write waves never grew a window"
        # sequential solo writes drain each primary's queue → cut
        for i in range(6):
            assert io.aio_write_full(f"s{i}", blob).wait(30) == 0
        assert sum(b.window_cuts for b in batchers) >= 1, \
            "drained queues never cut a grown window"
        assert sum(b.queue_depth_hwm for b in batchers) >= 2
        for i in range(8):
            assert io.read(f"a{i}") == blob
            assert io.read(f"b{i}") == blob


# -- PR 5: device-first routing regressions ---------------------------------


def test_8mib_k8m4_group_routes_to_device():
    """The BENCH_r05 misrouting regression: a healthy device with warm
    geometry must route an 8 MiB k8m4 encode group to the DEVICE
    (attribution: device calls > 0, batched-twin calls == 0) — with
    the crossover pinned where the fixed bench calibration pins it
    when the device wins pipelined (1 MiB)."""
    k8m4 = ecreg.instance().factory(
        "tpu", {"k": "8", "m": "4", "technique": "reed_sol_van"})
    b = make_batcher(ec_tpu_queue_window_us=1000,
                     ec_tpu_min_device_bytes=1 << 20)
    try:
        from ceph_tpu.osd.batcher import _geometry_key
        sinfo = ecutil.StripeInfo(8, 8 * 16384)      # 128 KiB stripes
        b.prewarm(k8m4, sinfo)
        key = _geometry_key(k8m4, sinfo)
        deadline = time.time() + 20
        while key not in EncodeBatcher._cpu_bps \
                and time.time() < deadline:
            time.sleep(0.05)
        assert key in EncodeBatcher._cpu_bps       # geometry is warm
        # force the staging pool to sample THIS put so the h2d EWMA
        # provably updates from a real batch transfer
        k8m4.core.backend.staging._puts = 0
        data = os.urandom(8 << 20)                   # 64 stripes
        out = {}
        done = threading.Event()
        b.submit(k8m4, sinfo, data,
                 lambda c: (out.update(c), done.set()))
        assert done.wait(60)
        assert b.calls >= 1, \
            "8 MiB group with a healthy warm device never reached it"
        assert b.cpu_calls == 0 and b.cpu_reqs == 0, \
            "8 MiB group misrouted to the batched CPU twin"
        assert out == ecutil.encode(sinfo, k8m4, data)
        assert EncodeBatcher._h2d_bps > 0, \
            "warm h2d EWMA never updated from a real batch transfer"
    finally:
        b.stop()


@pytest.mark.parametrize("lane", LANE_NAMES)
def test_idle_device_gets_reprobed_despite_cpu_bias(codec, lane):
    """A stale learned CPU bias with ZERO recent device traffic is the
    misrouting failure mode: once the device has been idle past
    ec_tpu_device_idle_reprobe_s, the next group must go to the
    device as a probe instead of waiting out the 1-in-N tick — on
    every lane, against that lane's own threshold."""
    b = make_batcher(ec_tpu_queue_window_us=1000)
    try:
        d = LaneDrive(lane, codec)
        # absurd learned bias (every batch "too small" for the device)
        d.set_crossover(1 << 30)
        # ...but the device has been idle for a long time
        past = time.monotonic() - 10 * b.idle_reprobe_s
        EncodeBatcher._last_device_ts = past
        EncodeBatcher._last_idle_probe_ts = past
        done = threading.Event()
        d.submit(b, lambda c: done.set())
        assert done.wait(30)
        assert getattr(b, d.calls) == 1 and \
            getattr(b, d.twin_reqs) == 0, \
            "idle device never re-probed; CPU bias locked in"
        # the probe is rate-limited: an immediate second small batch
        # (device no longer idle) goes back to the learned route
        done2 = threading.Event()
        d.set_crossover(1 << 30)
        EncodeBatcher._probe_tick = 1   # keep the 1-in-N tick silent
        d.submit(b, lambda c: done2.set())
        assert done2.wait(30)
        assert getattr(b, d.twin_reqs) == 1
    finally:
        b.stop()


def test_breaker_close_resets_learned_crossover(codec):
    """PR 5 satellite: while the breaker is open every group encodes
    on the twin, so the learner can only accumulate CPU bias — on
    close the crossover must snap back to the operator's pin and the
    per-geometry device EWMAs must be dropped."""
    b = make_batcher(ec_tpu_min_device_bytes=4096)
    try:
        assert EncodeBatcher._pinned_min_device_bytes == 4096
        # bias accumulated while the device was sick
        EncodeBatcher._min_device_bytes = 1 << 30
        EncodeBatcher._dev_bps = {("stale",): 1.0}
        for _ in range(b.device_error_threshold):
            b._device_failure("dispatch")
        assert EncodeBatcher._breaker_open
        b._device_success()          # re-admission probe completed
        assert not EncodeBatcher._breaker_open
        assert EncodeBatcher._min_device_bytes == 4096, \
            "breaker close must restore the operator's crossover pin"
        assert EncodeBatcher._dev_bps == {}, \
            "breaker close must drop stale device-rate EWMAs"
    finally:
        b.stop()


@pytest.mark.parametrize("lane", LANE_NAMES)
def test_learn_crossover_uses_pipelined_model_and_rejects_outliers(
        codec, lane):
    """Unit-level checks on the rebuilt learner: (a) a serial fenced
    time whose slowest LEG still beats the CPU must not raise the
    threshold (pipelined overlap credited); (b) a call 5x slower than
    the geometry's steady-state EWMA is a compile/outlier and teaches
    nothing.  Held on every lane: its own byte count, its own rate
    bucket and its own crossover."""
    b = make_batcher()
    try:
        d = LaneDrive(lane, codec, nstripes=64)   # 512 KiB encode group
        req = d.request()
        key = d.lane.bucket(d.key)
        total = float(d.nbytes)
        assert d.lane.group_bytes([req]) == d.nbytes
        # measured machine profile: CPU 1 GB/s, link 2 GB/s — the
        # transfer legs are a real fraction of the fenced window
        EncodeBatcher._cpu_bps[key] = 1e9
        EncodeBatcher._h2d_bps = 2e9
        cpu_pred = total / 1e9
        # (a) serial fence = 1.2x the CPU time, but split over
        # h2d (total/2e9) + d2h + compute, every leg is well under
        # cpu_pred: the pipelined router must NOT raise the threshold
        # (the old serial-sum judge did, and misrouted everything)
        b._learn_crossover(d.lane, d.key, [req], 1.2 * cpu_pred,
                           d.nbytes, d.out_bytes)
        assert d.crossover() == 0, \
            "serial-sum judging regressed: pipelined win raised the " \
            "crossover"
        steady = EncodeBatcher._dev_bps.get(key, 0.0)
        assert steady > 0
        # (b) a 100x-slower call (jit compile) must be rejected: no
        # threshold move, EWMA not poisoned
        b._learn_crossover(d.lane, d.key, [req], 100 * total / steady,
                           d.nbytes, d.out_bytes)
        assert d.crossover() == 0
        assert EncodeBatcher._dev_bps[key] == steady, \
            "compile outlier absorbed into the steady-state EWMA"
    finally:
        b.stop()


@pytest.mark.parametrize("lane", LANE_NAMES)
def test_route_verdicts_hit_recorder_and_ec_device_counters(codec, lane):
    """PR 6 tentpole: every routing verdict lands in the flight
    recorder with a reason code plus the crossover snapshot, and
    increments the matching ``ec_device`` ``<prefix>route_*`` counter
    (``route_``, ``dec_route_``, ``delta_route_``); the completed
    device group publishes staging/h2d telemetry."""
    from ceph_tpu.utils.flight_recorder import FlightRecorder
    from ceph_tpu.utils.perf import PerfCountersCollection

    coll = PerfCountersCollection()
    rec = FlightRecorder(capacity=64, name="osd.t")
    EncodeBatcher.reset_learning()
    b = EncodeBatcher({"ec_tpu_batch_stripes": 1024,
                       "ec_tpu_queue_window_us": 1000,
                       "ec_tpu_min_device_bytes": 1},
                      perf_coll=coll, recorder=rec)
    try:
        d = LaneDrive(lane, codec)
        prefix = d.lane.prefix
        done = threading.Event()
        d.submit(b, lambda c: done.set())
        assert done.wait(30)
        routes = [e for e in rec.dump()
                  if e["kind"] == prefix + "route"]
        assert routes, rec.dump()
        assert routes[0]["to"] == "device"
        assert routes[0]["reason"] == "device"
        assert routes[0]["bytes"] == d.nbytes
        assert routes[0]["crossover"] == 1
        dp = coll.perf_dump()["ec_device"]
        assert dp[prefix + "route_device"] >= 1
        assert dp[prefix + "route_pin"] == 0
        # the completed group published the staging-pool and link
        # telemetry into the same subsystem
        deadline = time.monotonic() + 10
        while coll.perf_dump()["ec_device"]["staging_slots"] == 0 \
                and time.monotonic() < deadline:
            time.sleep(0.05)
        dp = coll.perf_dump()["ec_device"]
        assert dp["staging_slots"] >= 1
        assert dp["staging_hits"] + dp["staging_allocs"] >= 1
    finally:
        b.stop()


@pytest.mark.parametrize("lane", LANE_NAMES)
def test_pin_routed_twin_group_is_reason_coded(codec, lane):
    """A crossover pinned above the group size routes to the twin
    with reason="pin" — the exact evidence trail the r05 misrouting
    post-mortem lacked — on every lane, bit-exact."""
    from ceph_tpu.utils.flight_recorder import FlightRecorder
    from ceph_tpu.utils.perf import PerfCountersCollection

    coll = PerfCountersCollection()
    rec = FlightRecorder(capacity=64, name="osd.t2")
    EncodeBatcher.reset_learning()
    b = EncodeBatcher({"ec_tpu_batch_stripes": 1024,
                       "ec_tpu_queue_window_us": 1000,
                       "ec_tpu_min_device_bytes": 256 << 20},
                      perf_coll=coll, recorder=rec)
    try:
        d = LaneDrive(lane, codec)
        prefix = d.lane.prefix
        out = {}
        done = threading.Event()
        d.submit(b, lambda c: (out.update(c), done.set()))
        assert done.wait(30)
        assert out == d.want
        assert getattr(b, d.twin_reqs) == 1
        routes = [e for e in rec.dump()
                  if e["kind"] == prefix + "route"]
        assert routes and routes[0]["to"] == "cpu"
        assert routes[0]["reason"] == "pin"
        assert coll.perf_dump()["ec_device"][prefix + "route_pin"] >= 1
    finally:
        b.stop()


def test_kernel_compile_failure_is_recorded_with_its_message(
        codec, monkeypatch):
    """A kernel that cannot be built (on the chip: a Mosaic refusal at
    some block shape) must not vanish into the twin: the write is still
    served, but the exception's text reaches the flight recorder, the
    error counter and ``dump_device`` — and prewarm records it before
    the first client op meets it."""
    from ceph_tpu.ops import jax_engine as je
    from ceph_tpu.utils.flight_recorder import FlightRecorder
    from ceph_tpu.utils.perf import PerfCountersCollection

    def refuse(*_a, **_kw):
        raise RuntimeError("mosaic refused block shape (1, 8, 524288)")
    be = codec.core.backend
    monkeypatch.setattr(type(be), "gf8_fast_path", lambda self: True)
    monkeypatch.setattr(je, "gf8_inner", refuse)
    monkeypatch.setattr(be, "_chain_lru", je.ChainLRU(8))
    coll = PerfCountersCollection()
    rec = FlightRecorder(capacity=64, name="osd.t4")
    EncodeBatcher.reset_learning()
    b = EncodeBatcher({"ec_tpu_queue_window_us": 1000,
                       "ec_tpu_fallback_cpu": False,
                       "ec_tpu_device_retry_ms": 0.0},
                      perf_coll=coll, recorder=rec)
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        data = os.urandom(2 * 8192)
        out = {}
        done = threading.Event()
        b.submit(codec, sinfo, data,
                 lambda c: (out.update(c or {}), done.set()))
        assert done.wait(30)
        twin = b.cpu_twin(codec, sinfo)
        assert out == ecutil.encode(sinfo, twin, data)
        assert coll.perf_dump()["ec_batcher"]["device_errors"] == 1
        errs = [e for e in rec.dump() if e["kind"] == "device_error"]
        assert errs and "mosaic refused" in errs[0]["exc"]
        dump = b.device_dump()
        assert "mosaic refused" in dump["last_device_error"]
        assert dump["device_errors"] == 1
        # the same failure met at prewarm is kept for dump_device
        with pytest.raises(RuntimeError, match="mosaic refused"):
            codec.prewarm_geometry(4096, batches=(2,))
        b.note_prewarm_error("activation.encode",
                             RuntimeError("mosaic refused"))
        assert any("mosaic refused" in e["error"]
                   for e in b.device_dump()["prewarm_errors"])
    finally:
        b.stop()


def test_breaker_transitions_are_recorded_and_auto_dumped(codec,
                                                          capsys):
    """Opening the breaker records the device_error run and the
    open transition, and auto-dumps the ring to stderr (rate
    limited); closing records the close with the restored
    crossover."""
    from ceph_tpu.utils.flight_recorder import FlightRecorder
    from ceph_tpu.utils.perf import PerfCountersCollection

    coll = PerfCountersCollection()
    rec = FlightRecorder(capacity=64, name="osd.t3")
    EncodeBatcher.reset_learning()
    b = EncodeBatcher({"ec_tpu_min_device_bytes": 4096},
                      perf_coll=coll, recorder=rec)
    try:
        for _ in range(b.device_error_threshold):
            b._device_failure("dispatch")
        assert EncodeBatcher._breaker_open
        dp = coll.perf_dump()["ec_device"]
        assert dp["breaker_opened"] == 1
        assert dp["breaker_open_now"] == 1
        kinds = [e["kind"] for e in rec.dump()]
        assert kinds.count("device_error") == b.device_error_threshold
        opens = [e for e in rec.dump() if e["kind"] == "breaker"
                 and e["state"] == "open"]
        assert opens and opens[0]["cause"] == "dispatch"
        err = capsys.readouterr().err
        assert "flight-recorder auto-dump [osd.t3] " \
               "reason=breaker-open" in err
        b._device_success()
        assert not EncodeBatcher._breaker_open
        dp = coll.perf_dump()["ec_device"]
        assert dp["breaker_closed"] == 1
        assert dp["breaker_open_now"] == 0
        closes = [e for e in rec.dump() if e["kind"] == "breaker"
                  and e["state"] == "closed"]
        assert closes and closes[0]["crossover"] == 4096
    finally:
        b.stop()
