"""Device decode pipeline tests (ISSUE 11).

Reconstruction as a first-class device path, symmetric to the encode
pipeline: batched Vandermonde-inverse decode keyed by erasure
signature (ceph_tpu/ops/engine.py `_recovery_rows` +
ec/plugins/tpu.py `decode_batch_async`), routed through the
EncodeBatcher's crossover / breaker / inflight machinery with full
seven-phase DeviceLedger stamps, consumed by recovery, degraded
client reads, and the windowed deep-scrub CRC path
(ops/crclinear.py).  Reference analog: ISA-L's per-erasure-signature
decode-table cache and ECBackend::handle_recovery_read_complete
decoding per recovery window (reference src/osd/ECBackend.cc:414)."""
import itertools
import os
import threading
import time

import numpy as np
import pytest

from ceph_tpu.cluster import Cluster
from ceph_tpu.cluster import test_config as make_conf
from ceph_tpu.ec import registry as ecreg
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.batcher import _DEC, EncodeBatcher


def make_codec(k, m):
    return ecreg.instance().factory(
        "tpu", {"k": str(k), "m": str(m),
                "technique": "reed_sol_van"})


def make_batcher(**over):
    conf = {"ec_tpu_batch_stripes": 1024,
            "ec_tpu_queue_window_us": 1000}
    conf.update(over)
    EncodeBatcher.reset_learning()
    return EncodeBatcher(conf)


# ---------------------------------------------------------------------
# codec boundary: batched Vandermonde-inverse recovery
# ---------------------------------------------------------------------
@pytest.mark.parametrize("k,m", [(8, 4), (4, 2)])
def test_device_decode_bit_exact_every_signature(k, m):
    """Every 1- and 2-erasure signature reconstructs bit-exact
    through decode_batch_async (combined data+parity recovery rows,
    ONE kernel apply per signature), and each handle carries a full
    seven-phase ledger."""
    from ceph_tpu.utils.device_ledger import PHASE_ORDER

    codec = make_codec(k, m)
    assert codec.decode_async_supported()
    cs = 256
    rng = np.random.default_rng(5)
    data = rng.integers(0, 256, (3, k, cs), dtype=np.uint8)
    parity = codec.encode_batch(data)
    shards = {i: data[:, i] for i in range(k)}
    shards.update({k + e: parity[:, e] for e in range(m)})
    n = k + m
    sigs = [frozenset(c) for c in itertools.combinations(range(n), 1)]
    sigs += [frozenset(c) for c in itertools.combinations(range(n), 2)]
    for erased in sigs:
        present = {i: shards[i] for i in range(n) if i not in erased}
        h = codec.decode_batch_async(present, cs)
        rec = h.wait()
        for e in sorted(erased):
            assert np.array_equal(rec[e], shards[e]), \
                f"k={k} m={m} erased={sorted(erased)} shard {e}"
        led = h.ledger
        assert led is not None
        missing = [p for p in PHASE_ORDER if led.get(p) is None]
        assert not missing, \
            f"signature {sorted(erased)} ledger lacks {missing}"


@pytest.mark.parametrize("k,m", [(8, 4), (4, 2)])
def test_prewarm_decode_caches_single_erasure_rows(k, m):
    """PG-activation decode prewarm: every single-erasure signature's
    recovery rows land in the signature cache ahead of traffic, and
    the warm is idempotent per (geometry, chunk) shape."""
    from ceph_tpu.ec.plugins import tpu as tpu_plugin

    codec = make_codec(k, m)
    core = codec.core
    codec.prewarm_decode(1024)
    n = k + m
    for e in range(n):
        chosen = tuple(i for i in range(n) if i != e)[:k]
        assert ("rec", chosen, (e,)) in core._decode_cache, \
            f"single-erasure signature {e} not prewarmed"
    marks = {key for key in tpu_plugin._PREWARMED_SHAPES
             if key and key[0] == "dec"}
    codec.prewarm_decode(1024)       # second call must be a no-op
    assert {key for key in tpu_plugin._PREWARMED_SHAPES
            if key and key[0] == "dec"} == marks


# ---------------------------------------------------------------------
# batcher: decode groups on the device pipeline
# ---------------------------------------------------------------------
def test_decode_group_rides_device_with_full_ledger():
    """A device-routed decode group dispatches async, completes
    bit-exact, and folds a SEVEN-phase ledger tagged group=="decode"
    into the accumulator (the pre-ISSUE-11 path folded a coarse
    two-stamp ledger); the dec_route_device verdict and the decode
    counters land in the ec_device subsystem."""
    from ceph_tpu.utils.device_ledger import PHASE_ORDER
    from ceph_tpu.utils.perf import PerfCountersCollection

    codec = make_codec(2, 1)
    coll = PerfCountersCollection()
    EncodeBatcher.reset_learning()
    b = EncodeBatcher({"ec_tpu_batch_stripes": 1024,
                       "ec_tpu_queue_window_us": 1000,
                       "ec_tpu_min_device_bytes": 1},
                      perf_coll=coll)
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        d1 = os.urandom(3 * 2 * 8192)
        d2 = os.urandom(2 * 2 * 8192)
        enc1 = ecutil.encode(sinfo, codec, d1)
        enc2 = ecutil.encode(sinfo, codec, d2)
        got = {}
        done = threading.Event()

        def cb(tag):
            def _cb(dec):
                got[tag] = dec
                if len(got) == 2:
                    done.set()
            return _cb

        b.submit_decode(codec, sinfo, {0: enc1[0], 2: enc1[2]}, {1},
                        cb("a"))
        b.submit_decode(codec, sinfo, {0: enc2[0], 2: enc2[2]}, {1},
                        cb("b"))
        assert done.wait(30)
        assert got["a"] == {1: enc1[1]}
        assert got["b"] == {1: enc2[1]}
        assert b.dec_calls == 1 and b.dec_coalesced == 2
        assert b.dec_cpu_reqs == 0, "group was device-routed"
        dec_leds = [led for led in b.ledger_accum.recent()
                    if led.get("group") == "decode"]
        assert dec_leds, "no decode-tagged ledger reached the accum"
        for led in dec_leds:
            missing = [p for p in PHASE_ORDER if led.get(p) is None]
            assert not missing, f"decode ledger lacks {missing}"
            assert led.get("device", -1) >= 0
        dp = coll.perf_dump()["ec_device"]
        assert dp["dec_route_device"] >= 1
        assert dp["dec_route_pin"] == 0
        # decode groups count into the shared inflight accounting
        assert dp["inflight_groups_hwm"] >= 1
    finally:
        b.stop()


def test_decode_pin_routes_to_twin_with_reason():
    """A crossover pinned above the group routes decode to the twin
    batch path with reason="pin" — same evidence trail as encode."""
    from ceph_tpu.utils.perf import PerfCountersCollection

    codec = make_codec(2, 1)
    coll = PerfCountersCollection()
    EncodeBatcher.reset_learning()
    b = EncodeBatcher({"ec_tpu_batch_stripes": 1024,
                       "ec_tpu_queue_window_us": 1000,
                       "ec_tpu_min_device_bytes": 256 << 20},
                      perf_coll=coll)
    try:
        EncodeBatcher._probe_tick = 1     # keep the tick probe silent
        EncodeBatcher._last_device_ts = time.monotonic()
        sinfo = ecutil.StripeInfo(2, 8192)
        d = os.urandom(2 * 2 * 8192)
        enc = ecutil.encode(sinfo, codec, d)
        out = {}
        done = threading.Event()
        b.submit_decode(codec, sinfo, {0: enc[0], 2: enc[2]}, {1},
                        lambda dec: (out.update(dec), done.set()))
        assert done.wait(30)
        assert out == {1: enc[1]}
        assert b.dec_cpu_reqs == 1
        dp = coll.perf_dump()["ec_device"]
        assert dp["dec_route_pin"] >= 1
        assert dp["dec_route_device"] == 0
    finally:
        b.stop()


def test_decode_crossover_seeds_from_encode_ewma():
    """Until decode groups teach their own threshold, routing judges
    against the ENCODE-learned crossover; a decode-learned value then
    takes over, and breaker close / reset_learning clear it back to
    the seed."""
    b = make_batcher()
    try:
        EncodeBatcher._min_device_bytes = 123456.0
        EncodeBatcher._dec_min_device_bytes = 0.0
        assert b._min_bytes(_DEC) == 123456.0, \
            "decode crossover must seed from the encode EWMA"
        EncodeBatcher._dec_min_device_bytes = 777.0
        assert b._min_bytes(_DEC) == 777.0
        # breaker close re-seeds decode from encode
        for _ in range(b.device_error_threshold):
            b._device_failure("dispatch")
        assert EncodeBatcher._breaker_open
        b._device_success()
        assert not EncodeBatcher._breaker_open
        assert EncodeBatcher._dec_min_device_bytes == 0.0, \
            "breaker close must drop the stale decode crossover"
        EncodeBatcher._dec_min_device_bytes = 42.0
        EncodeBatcher.reset_learning()
        assert EncodeBatcher._dec_min_device_bytes == 0.0
    finally:
        b.stop()
        EncodeBatcher.reset_learning()


def test_breaker_open_decode_falls_to_twin_without_errors():
    """Chaos: with the circuit breaker OPEN, device-eligible decode
    groups fall to the CPU twin — bit-exact results, zero
    client-visible errors, and the dec_route_breaker_open verdict on
    the books."""
    from ceph_tpu.utils.perf import PerfCountersCollection

    codec = make_codec(2, 1)
    coll = PerfCountersCollection()
    EncodeBatcher.reset_learning()
    b = EncodeBatcher({"ec_tpu_batch_stripes": 1024,
                       "ec_tpu_queue_window_us": 1000,
                       "ec_tpu_min_device_bytes": 1},
                      perf_coll=coll)
    try:
        for _ in range(b.device_error_threshold):
            b._device_failure("dispatch")
        assert EncodeBatcher._breaker_open
        EncodeBatcher._probe_tick = 1    # keep the 1-in-N probe silent
        sinfo = ecutil.StripeInfo(2, 8192)
        results = []
        done = threading.Event()
        enc = []
        for i in range(3):
            d = os.urandom(2 * 2 * 8192)
            enc.append(ecutil.encode(sinfo, codec, d))

        def cb(dec):
            results.append(dec)
            if len(results) == 3:
                done.set()

        for e in enc:
            b.submit_decode(codec, sinfo, {0: e[0], 2: e[2]}, {1}, cb)
        assert done.wait(30)
        assert all(r is not None for r in results), \
            "breaker-open decode leaked an error to the client"
        assert sorted(bytes(r[1]) for r in results) == \
            sorted(bytes(e[1]) for e in enc)
        assert b.dec_cpu_reqs == 3
        dp = coll.perf_dump()["ec_device"]
        assert dp["dec_route_breaker_open"] >= 1
    finally:
        b.stop()
        EncodeBatcher.reset_breaker()
        EncodeBatcher.reset_learning()


DEC_ROUTE_CEILING = 20e-6


def test_decode_route_note_overhead_within_budget():
    """ISSUE 11 perf guard: the decode router's per-group verdict
    publication (counter + recorder) stays under 20us/op — decode
    observability must not tax the recovery hot path."""
    from ceph_tpu.osd.batcher import _DecReq
    from ceph_tpu.utils.flight_recorder import FlightRecorder
    from ceph_tpu.utils.perf import PerfCountersCollection

    codec = make_codec(2, 1)
    coll = PerfCountersCollection()
    rec = FlightRecorder(capacity=64, name="osd.dectest")
    EncodeBatcher.reset_learning()
    b = EncodeBatcher({"ec_tpu_batch_stripes": 1024,
                       "ec_tpu_queue_window_us": 1000},
                      perf_coll=coll, recorder=rec)
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        d = os.urandom(2 * 2 * 8192)
        enc = ecutil.encode(sinfo, codec, d)
        req = _DecReq(codec, sinfo, {0: enc[0], 2: enc[2]}, {1},
                      lambda dec: None)
        key = ("dec", "geom", (0, 2), (1,))
        n = 20_000
        b._note_route(_DEC, key, [req], False)     # warm
        t0 = time.perf_counter()
        for _ in range(n):
            b._note_route(_DEC, key, [req], False)
        cost = (time.perf_counter() - t0) / n
        assert cost < DEC_ROUTE_CEILING, \
            f"decode route note costs {cost * 1e6:.2f}us/op " \
            f"(ceiling {DEC_ROUTE_CEILING * 1e6:.0f}us)"
    finally:
        b.stop()


# ---------------------------------------------------------------------
# degraded client reads through the batcher
# ---------------------------------------------------------------------
@pytest.mark.parametrize("backend", ["classic", "crimson"])
def test_degraded_read_reconstructs_through_batcher(backend):
    """One OSD down: client reads return reconstructed bytes
    bit-exact, the reconstruction rides the OSD batcher's decode
    pipeline (dec_reqs > 0) instead of the inline CPU loop, and the
    client's read ledger still carries the decode_dispatch /
    decode_complete hops — under BOTH OSD execution models."""
    with Cluster(n_osds=4,
                 conf=make_conf(osd_backend=backend,
                                ec_tpu_queue_window_us=2000)) as c:
        for i in range(4):
            c.wait_for_osd_up(i, 20)
        c.create_ec_profile("ddp", plugin="tpu", k="2", m="1")
        c.create_pool("ddpp", "erasure", erasure_code_profile="ddp")
        rad = c.rados(timeout=60)
        io = rad.open_ioctx("ddpp")
        blobs = {f"d{i}": os.urandom(32768) for i in range(8)}
        for oid, blob in blobs.items():
            io.write_full(oid, blob)
        c.wait_for_clean(30)
        c.kill_osd(3)
        c.wait_for_osd_down(3, 30)
        for oid, blob in blobs.items():
            assert io.read(oid) == blob, f"{oid} degraded read wrong"
        dec_reqs = sum(o.encode_batcher.dec_reqs
                       for o in c.osds.values() if o is not None)
        assert dec_reqs > 0, \
            "degraded reads bypassed the decode batcher"
        hops = rad.objecter.hops_read.dump()
        assert {"decode_dispatch", "decode_complete"} <= \
            set(hops["hop_counts"])


# ---------------------------------------------------------------------
# crclinear: CRC32C as a GF(2) linear map + syndrome bands
# ---------------------------------------------------------------------
def test_crclinear_bit_exact_vs_crc32c_kernel():
    from ceph_tpu.ops import crclinear
    from ceph_tpu.utils.crc import crc32c

    lin = crclinear.shared()
    rng = np.random.default_rng(7)
    chunks = [rng.integers(0, 256, n, dtype=np.uint8).tobytes()
              for n in (1, 7, 511, 512, 513, 1024, 4096, 10000)]
    got = lin.crc_batch(chunks)
    for c, g in zip(chunks, got):
        assert int(g) == crc32c(c)


def test_crclinear_backend_apply_matches_host():
    from ceph_tpu.ops import crclinear
    from ceph_tpu.utils.crc import crc32c

    codec = make_codec(2, 1)
    backend = codec.core.backend
    lin = crclinear.shared()
    rng = np.random.default_rng(9)
    chunks = [rng.integers(0, 256, 4096, dtype=np.uint8).tobytes()
              for _ in range(5)]
    got = lin.crc_batch(chunks, backend=backend)
    for c, g in zip(chunks, got):
        assert int(g) == crc32c(c)


def test_crclinear_syndrome_partials_cancel_on_codeword():
    """The distributed GF-syndrome identity: per-shard linear-CRC
    partials of C[e][s]-scaled chunks XOR to ZERO across a valid
    codeword (data + parity), and any single corrupted shard breaks
    the cancellation — the unlocalizable-staleness detector deep
    scrub runs per window."""
    from ceph_tpu.ops import crclinear

    k, m = 2, 1
    codec = make_codec(k, m)
    cm = codec.core.coding_matrix
    lin = crclinear.shared()
    cs = 2048
    rng = np.random.default_rng(11)
    data = rng.integers(0, 256, (1, k, cs), dtype=np.uint8)
    parity = codec.encode_batch(data)
    shards = [np.ascontiguousarray(data[0, s]) for s in range(k)]
    shards += [np.ascontiguousarray(parity[0, e]) for e in range(m)]

    def partials(shard_arrays):
        syn = [0] * m
        for s, arr in enumerate(shard_arrays):
            if s < k:
                scales = [int(cm[e][s]) for e in range(m)]
            else:
                scales = [1 if e == s - k else 0 for e in range(m)]
            nz = sorted({x for x in scales if x})
            if not nz:
                continue
            parts = lin._apply_window(arr.reshape(1, cs), tuple(nz))
            for e, sc in enumerate(scales):
                if sc:
                    syn[e] ^= int(parts[nz.index(sc)][0])
        return syn

    assert partials(shards) == [0] * m, \
        "syndrome partials must cancel on a consistent codeword"
    bad = [a.copy() for a in shards]
    bad[0][100] ^= 0x5A
    assert any(partials(bad)), \
        "corrupted shard must break the syndrome cancellation"


def test_scrub_syndrome_clean_pool_and_counters():
    """Live cluster with osd_deep_scrub_syndrome on: a clean pool
    deep-scrubs with ZERO errors and ZERO syndrome errors, the
    backends checksum through the windowed batched path, and the
    scrubber dump exports the syndrome counter."""
    with Cluster(n_osds=3,
                 conf=make_conf(osd_deep_scrub_syndrome=True)) as c:
        for i in range(3):
            c.wait_for_osd_up(i, 20)
        c.create_ec_profile("syn", plugin="tpu", k="2", m="1")
        c.create_pool("synp", "erasure", erasure_code_profile="syn")
        io = c.rados().open_ioctx("synp")
        for i in range(4):
            io.write_full(f"y{i}", os.urandom(16384))
        c.wait_for_clean(30)
        ret, _, out = c.mon_command({"prefix": "pg dump"})
        assert ret == 0
        pgids = sorted(out["pg_stats"])
        for pgid in pgids:
            ret, rs, _ = c.mon_command({"prefix": "pg deep-scrub",
                                        "pgid": pgid})
            assert ret == 0, rs
        deadline = time.monotonic() + 60
        while time.monotonic() < deadline:
            ret, _, out = c.mon_command({"prefix": "pg dump"})
            stats = out["pg_stats"]
            if all(stats.get(p, {}).get("last_deep_scrub", 0) > 0
                   for p in pgids):
                break
            time.sleep(0.2)
        else:
            raise TimeoutError("deep scrub never completed")
        for p in pgids:
            assert stats[p].get("num_scrub_errors", 0) == 0
        windows = syndrome = 0
        for osd in c.osds.values():
            for pg in osd.pgs.values():
                windows += getattr(pg.backend, "scrub_windows", 0)
                sc = getattr(pg, "scrubber", None)
                syndrome += getattr(sc, "syndrome_errors", 0)
                if sc is not None:
                    assert "syndrome_errors" in sc.dump()
        assert windows > 0, "deep scrub never used the windowed path"
        assert syndrome == 0, \
            "clean pool must not raise syndrome errors"


def test_scrub_syndrome_flags_unlocalizable_inconsistency():
    """The syndrome compare itself: per-shard CRCs all clean but the
    cross-shard partials XOR nonzero -> ONE unlocalizable syndrome
    error, no shard blamed, no auto-repair queued."""
    from ceph_tpu.osd.scrub import Scrubber

    sc = Scrubber.__new__(Scrubber)
    base = {"size": 100, "hinfo_ok": True}
    sc.maps = {
        0: {"o": dict(base, syndrome_partials=[3])},
        1: {"o": dict(base, syndrome_partials=[5])},
        2: {"o": dict(base, syndrome_partials=[9])},
    }
    sc.syndrome_errors = 0
    out = {}
    sc._compare_ec(out)
    assert out == {}, \
        "syndrome inconsistency must not blame a shard"
    assert sc.syndrome_errors == 1
    # consistent partials (XOR zero) raise nothing
    sc.maps[2]["o"]["syndrome_partials"] = [3 ^ 5]
    sc.syndrome_errors = 0
    sc._compare_ec({})
    assert sc.syndrome_errors == 0


# ---------------------------------------------------------------------
# perf_trend: rebuild floor + decode routing collapse gates
# ---------------------------------------------------------------------
def _hist_round(records):
    return {"n": 1, "path": "r1", "records": records}


def test_perf_trend_rebuild_floor_and_collapse():
    from tools import perf_trend

    hist = [_hist_round([
        {"metric": "OSD rebuild MB/s (k=8 m=4 pool, kill osd)",
         "value": 100.0, "unit": "MB/s", "vs_baseline": 4.0}])]
    ok = {"vs_baseline": 3.9, "expect_device": True,
          "device_decode_fraction": 0.9, "dec_routes": {"device": 9}}
    assert perf_trend.check(None, hist, fresh_rebuild=ok) == []
    # floor: 0.8 x best history
    slow = dict(ok, vs_baseline=1.0)
    findings = perf_trend.check(None, hist, fresh_rebuild=slow)
    assert any(f["check"] == "rebuild-throughput-regression"
               for f in findings)
    # decode routing collapse, gated on expect_device
    collapsed = dict(ok, device_decode_fraction=0.1,
                     dec_routes={"pin": 9})
    findings = perf_trend.check(None, hist, fresh_rebuild=collapsed)
    assert any(f["check"] == "dec-routing-collapse"
               for f in findings)
    cpu_box = dict(collapsed, expect_device=False)
    assert perf_trend.check(None, hist, fresh_rebuild=cpu_box) == []
    # no rebuild record at all: every rebuild gate self-skips
    assert perf_trend.check(None, hist, fresh_rebuild=None) == []
