"""Distributed tracing tests.

Reference analog: blkin/ZTracer spans threaded through the EC write
path (osd/ECBackend.cc:2063-2068) with child spans per shard
sub-write; LTTng process-local tracepoints."""
import time

import pytest

from ceph_tpu.cluster import Cluster
from ceph_tpu.cluster import test_config as make_conf
from ceph_tpu.client.rados import Rados
from ceph_tpu.utils.tracer import Tracer


def test_tracer_spans_and_sampling():
    t = Tracer("svc", enabled=True, sample_every=2)
    spans = [t.maybe_start("op") for _ in range(8)]
    started = [s for s in spans if s is not None]
    assert len(started) == 4             # every 2nd sampled
    for s in started:
        s.tag("k", "v").finish()
    dump = t.dump()
    assert len(dump) == 4
    assert dump[0]["tags"] == {"k": "v"}
    assert dump[0]["service"] == "svc"
    # child continuation inherits the trace id
    child = t.start("sub", started[0].trace_id,
                    started[0].span_id)
    child.finish()
    same = t.dump(trace_id=started[0].trace_id)
    assert {d["name"] for d in same} == {"op", "sub"}
    # disabled tracer costs one branch — including for propagated
    # contexts (an operator who turned tracing off records nothing)
    off = Tracer("svc2", enabled=False)
    assert off.maybe_start("x") is None
    assert off.start("x", 0) is None
    assert off.start("x", 12345) is None


def test_spans_cross_daemons_ec_write():
    """One traced client write to an EC pool must produce spans with
    the SAME trace id on the client, the primary, and shard OSDs."""
    conf = make_conf(osd_tracing=True, rados_tracing=True)
    with Cluster(n_osds=3, conf=conf) as c:
        for i in range(3):
            c.wait_for_osd_up(i, 20)
        c.create_ec_profile("trp", plugin="jerasure", k="2", m="1")
        c.create_pool("trpool", "erasure",
                      erasure_code_profile="trp")
        client = Rados(c.mon_addr, conf=conf).connect()
        try:
            io = client.open_ioctx("trpool")
            io.write_full("traced", b"x" * 8192)
            assert io.read("traced") == b"x" * 8192
            # the client recorded root spans
            client_spans = client.tracer.dump()
            assert client_spans
            tid = client_spans[0]["trace_id"]
            # the same trace id shows up inside the daemons
            deadline = time.monotonic() + 10
            osd_spans = []
            while time.monotonic() < deadline:
                osd_spans = [s for osd in c.osds.values()
                             if osd is not None
                             for s in osd.tracer.dump()]
                if any(s["trace_id"] == tid for s in osd_spans):
                    break
                time.sleep(0.2)
            names = {s["name"] for s in osd_spans
                     if s["trace_id"] == tid}
            assert "osd_op" in names, osd_spans
            # the EC write fanned out: shard sub-write spans exist
            all_names = {s["name"] for s in osd_spans}
            assert "ec_sub_write" in all_names, all_names
            # sub-write spans share trace ids with osd_op spans
            sub_tids = {s["trace_id"] for s in osd_spans
                        if s["name"] == "ec_sub_write"}
            op_tids = {s["trace_id"] for s in osd_spans
                       if s["name"] == "osd_op"}
            assert sub_tids & op_tids
        finally:
            client.shutdown()


def test_ec_write_span_tree_and_stage_timeline(tmp_path):
    """One traced client EC write yields a LINKED span tree — client
    rados_op -> primary osd_op (parent = client span) -> one
    ec_sub_write child per shard (parent = osd_op span, including the
    primary's own shard) — the primary's dump_historic_ops timeline
    shows the write-pipeline stage events in order, and the OSD's
    admin socket serves the observability surface."""
    conf = make_conf(osd_tracing=True, rados_tracing=True,
                     admin_socket=str(tmp_path) + "/$name.asok")
    with Cluster(n_osds=3, conf=conf) as c:
        for i in range(3):
            c.wait_for_osd_up(i, 20)
        c.create_ec_profile("trs", plugin="jerasure", k="2", m="1")
        c.create_pool("trsp", "erasure",
                      erasure_code_profile="trs")
        client = Rados(c.mon_addr, conf=conf).connect()
        try:
            io = client.open_ioctx("trsp")
            io.write_full("tree", b"y" * 8192)
            root = next(s for s in client.tracer.dump()
                        if s["tags"].get("oid") == "tree")
            tid = root["trace_id"]
            deadline = time.monotonic() + 15
            op_spans, subs = [], []
            while time.monotonic() < deadline:
                spans = [s for osd in c.osds.values()
                         if osd is not None
                         for s in osd.tracer.dump()
                         if s["trace_id"] == tid]
                op_spans = [s for s in spans
                            if s["name"] == "osd_op"]
                subs = [s for s in spans
                        if s["name"] == "ec_sub_write"]
                if op_spans and len(subs) >= 3:
                    break
                time.sleep(0.2)
            # the primary's osd_op span is the client span's child
            assert len(op_spans) == 1, op_spans
            assert op_spans[0]["parent_id"] == root["span_id"]
            # one sub-write child per shard (k=2 m=1 -> 3 shards),
            # every one parented on the primary's osd_op span
            assert len(subs) == 3, subs
            assert all(s["parent_id"] == op_spans[0]["span_id"]
                       for s in subs), subs
            # ... and they landed on every shard OSD
            for osd in c.osds.values():
                if osd is None:
                    continue
                assert any(s["trace_id"] == tid
                           and s["name"] == "ec_sub_write"
                           for s in osd.tracer.dump()), \
                    f"osd.{osd.whoami} recorded no sub-write span"

            # stage timeline: the primary's historic-op dump carries
            # the write pipeline's stage events in pipeline order
            hist = None
            primary = None
            deadline = time.monotonic() + 15
            while hist is None and time.monotonic() < deadline:
                for osd in c.osds.values():
                    if osd is None:
                        continue
                    for opd in osd.op_tracker.dump_historic_ops():
                        if "tree" in opd["description"]:
                            hist = opd
                            primary = osd
                if hist is None:
                    time.sleep(0.2)
            assert hist is not None
            names = [e["event"] for e in hist["events"]]
            want = ["initiated", "queued_for_pg", "reached_pg",
                    "started_write", "ec:encode_queued",
                    "ec:encoded", "ec:sub_write_sent",
                    "ec:all_shards_committed", "op_commit", "done"]
            assert set(want) <= set(names), names
            idx = [names.index(w) for w in want]
            assert idx == sorted(idx), names

            # admin socket surface: perf dump carries the ec_batcher
            # subsystem; the op dumps answer over the same socket
            from ceph_tpu.utils.admin_socket import admin_command
            sock = str(tmp_path) + "/osd.0.asok"
            pd = admin_command(sock, "perf dump")
            assert "osd" in pd and "ec_batcher" in pd
            assert "queue_wait_us" in pd["ec_batcher"]
            for prefix in ("dump_historic_slow_ops",
                           "dump_blocked_ops"):
                out = admin_command(sock, prefix)
                assert isinstance(out["ops"], list), (prefix, out)
            tr = admin_command(sock, "dump_traces")
            assert isinstance(tr["spans"], list)
            # flight recorder round-trip: an event noted on the
            # OSD's in-process ring comes back through the admin
            # socket, ordered by sequence
            c.osds[0].flight_recorder.note(
                "route", reason="pin", to="cpu", bytes=8192)
            fr = admin_command(sock, "dump_flight_recorder")
            assert fr["name"] == "osd.0" and fr["capacity"] >= 16
            routes = [e for e in fr["events"]
                      if e["kind"] == "route"]
            assert routes and routes[-1]["reason"] == "pin"
            assert routes[-1]["to"] == "cpu"
            seqs = [e["seq"] for e in fr["events"]]
            assert seqs == sorted(seqs)
            # critical-path round-trip on the PRIMARY (the client
            # op retired there): stage seconds sum to the op total
            # and the dominant stage is recorded
            psock = str(tmp_path) + f"/osd.{primary.whoami}.asok"
            cp = admin_command(psock, "dump_critical_path")
            assert cp["ops"] >= 1
            assert cp["bounding_ops"]
            assert cp["slowest_op"] is not None
            so = cp["slowest_op"]
            assert abs(sum(so["stages"].values())
                       - so["total"]) < 1e-6
            assert so["bounding_stage"] in so["stages"]
            # ... and the same totals ride the perf dump as the
            # critpath subsystem
            ppd = admin_command(psock, "perf dump")
            assert ppd["critpath"]["ops"] >= 1
            assert ppd["critpath"]["stage_commit_wait"]["avgcount"] \
                >= 0
        finally:
            client.shutdown()


def test_dump_traces_tell_command():
    conf = make_conf(osd_tracing=True, rados_tracing=True)
    with Cluster(n_osds=2, conf=conf) as c:
        for i in range(2):
            c.wait_for_osd_up(i, 20)
        c.create_pool("trp2", "replicated", size=2)
        client = Rados(c.mon_addr, conf=conf).connect()
        try:
            io = client.open_ioctx("trp2")
            io.write_full("t1", b"data")
            from ceph_tpu.tools import ceph_cli
            host, port = c.mon_addr
            import json
            import io as _io
            import contextlib
            buf = _io.StringIO()
            with contextlib.redirect_stdout(buf):
                ret = ceph_cli.main(["-m", f"{host}:{port}",
                                     "--format", "json", "tell",
                                     "osd.0", "dump_traces"])
            assert ret == 0
            spans = json.loads(buf.getvalue())["spans"]
            assert isinstance(spans, list)
        finally:
            client.shutdown()


# -- sections: the program's threads on the profiler's clock ------------------
#
# One CPU jax.profiler session over a small EC write, an overwrite, a
# read and a degraded read on a 4-OSD bluestore cluster, then the
# instruments that have no cluster path of their own.  The trace is
# read with the benchmark's own reader (benchmark/harness/spans.py).

import os
import sys
import threading

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))

#: section -> (a section some instance of it must nest under, or None
#: for "on top of its thread"; whether it carries the client's reqid)
SECTIONS = {
    "reactor.io": (None, False),
    "reactor.mailbox": (None, False),
    "reactor.timer": (None, False),
    "reactor.cb": (None, False),
    "reactor.tick_hook": (None, False),
    "msgr.encode": ("reactor.cb", False),
    "msgr.send": ("reactor.cb", False),
    "msgr.recv": ("reactor.io", False),
    "msgr.decode": ("reactor.io", False),
    "msgr.dispatch": ("reactor.io", False),
    "msgr.reconnect": (None, False),
    "objecter.submit": (None, True),
    "objecter.reply": ("msgr.dispatch", True),
    "pg.do_op": ("reactor.", True),
    "ec.prepare": ("pg.do_op", True),
    "ec.rmw_read": ("ec.prepare", True),
    "ec.fanout": ("reactor.", True),
    "ec.sub_write": ("ec.fanout", True),
    "ec.sub_read": ("msgr.dispatch", False),
    "ec.commit": ("reactor.", True),
    "ec.reconstruct": ("reactor.", True),
    "batcher.submit": ("reactor.tick_hook", False),
    "batcher.form": (None, False),
    "batcher.dispatch": ("batcher.form", False),
    "batcher.complete": (None, False),
    "batcher.deliver": ("batcher.complete", False),
    "dispatch.stage_acquire": ("batcher.dispatch", False),
    "dispatch.h2d": ("batcher.dispatch", False),
    "dispatch.call": ("batcher.dispatch", False),
    "dispatch.wait": ("batcher.complete", False),
    "dispatch.d2h": ("batcher.complete", False),
    "store.txn": ("ec.sub_write", False),
    "store.wal": ("store.txn", False),
    "store.data_write": (None, False),
    "store.kv_commit": (None, False),
    "store.read": ("ec.sub_read", False),
    "crc.host": ("ec.", False),
    "lock.wait": (None, False),
    "timer.cb": (None, False),
    "finisher.cb": (None, False),
    "sampler.pass": (None, False),
    "mon.dispatch": ("msgr.dispatch", False),
    "mon.tick": (None, False),
}
# crc.device opens only in deep scrub's device windows, where
# jax.default_backend() is not "cpu" (osd/ecbackend.py): no CPU case.

def own_keywords(meta: dict) -> dict:
    """A section's keywords less the one a probed section carries."""
    return {k: v for k, v in meta.items() if k != "cpu_ns"}


def _drive_cluster():
    conf = make_conf(osd_objectstore="bluestore")
    with Cluster(n_osds=4, conf=conf, store_kind="bluestore") as cl:
        for i in range(4):
            cl.wait_for_osd_up(i, 20)
        cl.create_ec_profile("secp", plugin="tpu", k="2", m="1")
        cl.create_pool("secpool", "erasure", erasure_code_profile="secp")
        ret, rs, _ = cl.mon_command({
            "prefix": "osd pool set", "pool": "secpool",
            "var": "allow_ec_overwrites", "val": "true"})
        assert ret == 0, rs
        r = cl.rados()
        r.wait_for_epoch(cl.mon.osdmap.epoch, 10)
        io = r.open_ioctx("secpool")
        size = 256 << 10
        names = [f"sec{i}" for i in range(4)]
        for n in names:
            io.write_full(n, bytes([len(n)]) * size)
        cl.wait_for_clean(20)
        deadline = time.monotonic() + 10
        from ceph_tpu.client.rados import RadosError
        while True:                   # flag propagation to the OSDs
            try:
                io.write(names[0], b"Z" * 4096, 8192)
                break
            except RadosError as e:
                if e.errno != 95 or time.monotonic() > deadline:
                    raise
                time.sleep(0.2)
        for n in names:
            io.write(n, b"Y" * 4096, 4096)      # delta RMW
            io.write(n, b"X" * 100, 70000)      # sub-chunk: reads back
        for n in names:
            assert len(io.read(n, length=size)) == size
        # a remote sub-read is dispatched where it arrives only when its
        # connection's reactor owns its PG (else it hops through that
        # reactor's mailbox): reads over many PGs make one of each sure
        for i in range(24):
            io.write_full(f"spread{i}", bytes([i]) * 8192)
        for i in range(24):
            assert io.read(f"spread{i}", length=8192) == bytes([i]) * 8192
        cl.kill_osd(0, lose_data=True)
        cl.wait_for_osd_down(0)
        for n in names:                          # degraded: reconstructs
            got = io.read(n, length=size)
            assert got[4096:8192] == b"Y" * 4096
        return {}


def _drive_instruments():
    """What no small cluster reaches by itself."""
    from ceph_tpu.crimson.reactor import Reactor
    from ceph_tpu.utils.config import Config
    from ceph_tpu.utils.locks import ContentionStats, TimedLock
    from ceph_tpu.utils.sampler import StackSampler
    from ceph_tpu.utils.timer_wheel import TimerWheel
    out = {}
    # a contended and an uncontended TimedLock; an option read while a
    # writer holds the shared Config's lock
    hot = TimedLock("pg_lock", stats=ContentionStats())
    quiet = TimedLock("quiet_lock")
    conf = Config()
    held = threading.Event()
    release = threading.Event()

    def holder():
        with hot, conf._lock:
            held.set()
            release.wait(5)
    t = threading.Thread(target=holder, name="the-holder")
    t.start()
    assert held.wait(5)
    threading.Timer(0.05, release.set).start()
    with hot:
        pass
    t.join(5)
    assert not t.is_alive()
    held.clear()
    release.clear()
    t = threading.Thread(target=holder, name="the-holder")
    t.start()
    assert held.wait(5)
    assert conf.get("osd_tick_interval") > 0
    out["config_read_while_locked"] = not conf._lock.acquire(False)
    release.set()
    t.join(5)
    assert not t.is_alive()
    for _ in range(100):
        with quiet:
            pass
    # a reactor callback that raises: counted, and named on its section
    r = Reactor("lone-reactor")
    r.start()
    done = threading.Event()

    def boom():
        raise ValueError("swallowed by the loop")
    r.call_soon(boom)
    r.call_soon(done.set)
    assert done.wait(5)
    r.stop()
    out["reactor"] = r
    # a timer-wheel callback and one sampler pass
    fired = threading.Event()
    wheel = TimerWheel()
    wheel.call_later(0.01, fired.set)
    assert fired.wait(5)
    wheel.stop()
    StackSampler().sample_once()
    # one encode straight through the plugin, marked by a section of
    # the test's own around it
    import numpy as np
    from ceph_tpu.ec import registry as ecreg
    from ceph_tpu.utils.tracer import section
    codec = ecreg.instance().factory("tpu", {"k": "8", "m": "4"})
    data = np.arange(16 * 8 * 4096, dtype=np.uint32).astype(np.uint8) \
        .reshape(16, 8, 4096)
    with section("batcher.dispatch", lane="linktest"):
        parity = codec.encode_batch_async(data).wait()
    assert parity.shape == (16, 4, 4096)
    out["link_payload"] = data.nbytes
    # and one of a packet-layout code: 7 stripes, staged as 8
    cauchy = ecreg.instance().factory("tpu", {
        "technique": "cauchy_good", "k": "4", "m": "3",
        "packetsize": "512"})
    with section("batcher.dispatch", lane="packettest"):
        parity = cauchy.encode_batch_async(data[:7, :4]).wait()
    assert parity.shape == (7, 3, 4096)
    # a bluestore in RAM mode with no applier: this thread folds its
    # own writes (7 blocks, then 1) under a section of the test's own
    from ceph_tpu.store import BlueStore, GHObject, Transaction
    store = BlueStore("", start_applier=False)
    store.mount()
    try:
        store.queue_transactions(
            [Transaction().create_collection("9.0s0")])
        with section("store.txn", op="foldtest:1"):
            for name, nblocks in (("seven", 7), ("one", 1)):
                store.queue_transactions([Transaction().write(
                    "9.0s0", GHObject(name, 0), 0,
                    bytes(range(256)) * 16 * nblocks)])
                store.flush()
        out["folds"] = store.csum_batches
    finally:
        store.umount()
    return out


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    import jax
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from harness import spans
    from ceph_tpu.utils import tracer
    from ceph_tpu.utils.tracer import section
    log_dir = str(tmp_path_factory.mktemp("trace"))
    with section("msgr.send", bytes=1):
        pass                        # no session yet: leaves nothing
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    opts.host_tracer_level = 2
    with pytest.MonkeyPatch.context() as mp:
        mp.setattr(tracer, "PROBE_SHARE", 1.0)     # every nest probed
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            extras = _drive_instruments()
            extras.update(_drive_cluster())
        finally:
            jax.profiler.stop_trace()
    plain = spans.load(log_dir)
    # every section with the names of the sections it nests under
    seen = {}
    for evs in spans.clipped(plain, *spans.window_of(plain)):
        stack = []
        for s, e, name, meta in evs:
            while stack and stack[-1][0] <= s:
                stack.pop()
            # (names it nests under, its keywords, theirs)
            seen.setdefault(name, []).append(
                ([n for _, n, _ in stack], meta,
                 [m for _, _, m in stack]))
            stack.append((e, name, meta))
    return {"plain": plain, "seen": seen, "spans": spans,
            "log_dir": log_dir, **extras}


@pytest.mark.parametrize("name", sorted(SECTIONS))
def test_section_is_there_nests_and_names_its_op(traced, name):
    parent, has_op = SECTIONS[name]
    rows = traced["seen"].get(name)
    assert rows, f"no {name} section in the trace; has " \
        f"{sorted(traced['seen'])}"
    if parent is not None:
        assert any(any(a.startswith(parent) for a in anc)
                   for anc, _, _ in rows), \
            f"no {name} nests under {parent}: " \
            f"{sorted({tuple(a) for a, _, _ in rows})[:5]}"
    else:
        assert any(not anc or anc[0].startswith("reactor.")
                   for anc, _, _ in rows) or name == "lock.wait"
    if has_op:
        ops = {m.get("op") for _, m, _ in rows if m.get("op")}
        assert ops and all(":" in op for op in ops), rows[:3]


def test_sections_of_one_request_share_its_reqid_across_daemons(traced):
    seen = traced["seen"]
    submitted = {m["op"] for _, m, _ in seen["objecter.submit"]}
    for name in ("pg.do_op", "ec.prepare", "ec.fanout", "ec.sub_write",
                 "ec.commit", "objecter.reply"):
        ops = {m.get("op") for _, m, _ in seen[name] if m.get("op")}
        assert ops & submitted, name
    # the outermost section of a thread loop says whose thread it is
    assert {m.get("d") for _, m, _ in seen["reactor.io"]} >= \
        {"crimson-osd1-r0"}
    assert any(str(m.get("d", "")).startswith("osd.")
               for _, m, _ in seen["batcher.form"])


def test_store_read_names_its_blocks_and_its_objects_blocks(traced):
    """``blocks`` is what the read gathered and verified, ``obj_blocks``
    what the object has: a shard of a 256 KiB object at k=2 is 32
    blocks, the sub-chunk overwrite's read-back wants one of them, a
    client read of the whole object all 32."""
    reads = [m for _, m, _ in traced["seen"]["store.read"]]
    assert all({"bytes", "blocks", "obj_blocks"} <= set(m)
               for m in reads), reads[:3]
    assert all(0 <= m["blocks"] <= m["obj_blocks"] for m in reads)
    assert all(m["bytes"] <= m["blocks"] * 4096 for m in reads)
    shard = [m for m in reads if m["obj_blocks"] == 32]
    assert any(m["blocks"] == 1 and m["bytes"] == 4096 for m in shard)
    assert any(m["blocks"] == 32 for m in shard)


def test_a_bluestore_fold_is_one_host_crc_section_and_no_dispatch(traced):
    """Two writes, two folds, two ``crc.host`` sections on the store's
    thread with the batch each one call covered; nothing of the write
    opens ``crc.device`` or stages a byte for the device."""
    def under_the_store(name):
        return [own_keywords(meta)
                for _, meta, theirs in traced["seen"].get(name, [])
                if any(m.get("op") == "foldtest:1" for m in theirs)]
    assert traced["folds"] == 2
    assert under_the_store("crc.host") == [
        {"blocks": 7, "bytes": 7 * 4096}, {"blocks": 1, "bytes": 4096}]
    assert len(under_the_store("store.data_write")) == 2
    for name in ("crc.device", "dispatch.h2d", "dispatch.call",
                 "dispatch.d2h"):
        assert under_the_store(name) == [], name
    assert "crc.device" not in traced["seen"]


def test_lock_wait_only_under_contention_with_site_and_holder(traced):
    waits = [m for _, m, _ in traced["seen"]["lock.wait"]]
    sites = {m["site"] for m in waits}
    assert "pg_lock" in sites
    assert "quiet_lock" not in sites
    # an option read takes no lock: the get under a held ``conf._lock``
    # returned before the holder let go, and waited for nothing
    assert "config" not in sites
    assert traced["config_read_while_locked"]
    assert {m["holder"] for m in waits
            if m["site"] == "pg_lock"} >= {"the-holder"}


def test_reactor_counts_and_names_the_exceptions_it_swallows(traced):
    r = traced["reactor"]
    assert r.callbacks_failed == 1
    errs = [m for _, m, _ in traced["seen"]["reactor.cb"]
            if m.get("error")]
    assert any(m["error"] == "ValueError" and m["d"] == "lone-reactor"
               and m["fn"].endswith("boom") for m in errs)
    r.util_samples.append((0.0, 0.5, 0.0, r.callbacks_failed))
    assert r.util_dump()[-1]["callbacks_failed"] == 1


def test_link_bytes_per_user_byte_is_k_plus_m_over_k_for_encode(traced):
    """One encode of 16 stripes at k=8 m=4 through the plugin's async
    path: 8 chunks in, 4 out, no padding at a batch of 16 and no CRC on
    a CPU backend, so what crossed is exactly 1.5 times the payload."""
    crossed = {"dispatch.h2d": 0, "dispatch.d2h": 0}
    for name in crossed:
        for _, meta, theirs in traced["seen"][name]:
            if any(m.get("lane") == "linktest" for m in theirs):
                crossed[name] += meta["bytes"]
    assert crossed == {"dispatch.h2d": traced["link_payload"],
                       "dispatch.d2h": traced["link_payload"] // 2}
    assert sum(crossed.values()) / traced["link_payload"] == 1.5


def test_a_packet_dispatch_opens_the_byte_dispatchs_sections(traced):
    """The same sections with the same keywords, the kernel's name
    apart: 7 stripes of [4, 4096] staged in a slot of 8."""
    under = {}
    for name in ("dispatch.stage_acquire", "dispatch.h2d", "dispatch.call",
                 "dispatch.wait", "dispatch.d2h"):
        under[name] = [own_keywords(meta)
                       for _, meta, theirs in traced["seen"][name]
                       if any(m.get("lane") == "packettest"
                              for m in theirs)]
        assert len(under[name]) == 1, name
    assert under["dispatch.call"][0]["kernel"] == "packet_xor_chain"
    assert under["dispatch.stage_acquire"][0]["batch"] == 8
    assert under["dispatch.h2d"][0] == {
        "bytes": 8 * 4 * 4096, "live_bytes": 7 * 4 * 4096, "batch": 8}
    assert under["dispatch.d2h"][0]["bytes"] == 8 * 3 * 4096


def test_every_h2d_section_says_how_much_of_it_is_payload(traced):
    h2d = [m for _, m, _ in traced["seen"]["dispatch.h2d"]]
    assert len(h2d) > 2
    assert all(0 < m["live_bytes"] <= m["bytes"] for m in h2d), h2d[:3]


def test_section_records_nothing_and_costs_little_without_a_session():
    import statistics
    from jax.profiler import TraceAnnotation
    from ceph_tpu.utils.tracer import section
    assert not TraceAnnotation.is_enabled()
    costs = []
    for _ in range(200):
        t0 = time.perf_counter()
        for _ in range(100):
            with section("msgr.send", bytes=4096, peer="osd.1"):
                pass
        costs.append((time.perf_counter() - t0) / 100)
    assert statistics.median(costs) < 5e-6


def test_no_section_of_before_the_session_is_in_the_trace(traced):
    sends = [m for _, m, _ in traced["seen"]["msgr.send"]]
    assert all(m.get("bytes") != 1 for m in sends)


@pytest.mark.parametrize("name", sorted(SECTIONS))
def test_section_carries_its_threads_cpu(traced, name):
    for _, meta, _ in traced["seen"][name]:
        assert isinstance(meta.get("cpu_ns"), int) and meta["cpu_ns"] >= 0, \
            (name, meta)


def test_the_cpu_printer_reads_the_session_and_its_largest_ack_gap(
        traced, capsys):
    from harness import cpu
    assert cpu.main(["cpu.py", traced["log_dir"]]) == 0
    out = capsys.readouterr().out
    assert out.startswith("window ") and "cores" in out
    rows = {}                       # the window's table comes first
    for ln in out.splitlines():
        if ln.startswith("   ") and ln.split()[0] in SECTIONS:
            rows.setdefault(ln.split()[0], ln.split()[1:])
    assert int(rows["msgr.recv"][0]) == len(traced["seen"]["msgr.recv"])
    assert "-- largest ack gap" in out


def test_section_without_a_session_is_the_shared_null_section():
    from ceph_tpu.utils.tracer import _NO_SECTION, section
    assert section("msgr.send", bytes=4096, peer="osd.1") is _NO_SECTION
    with section("pg.do_op", op="c:1") as s:
        assert s is _NO_SECTION
        s.set_metadata(error="none recorded")


@pytest.fixture(scope="module")
def nests(tmp_path_factory):
    """One session, 400 nests of three sections at each probe share:
    ``pg.do_op`` around ``crc.host`` around ``store.read``."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from harness import spans
    from ceph_tpu.utils import tracer
    from ceph_tpu.utils.tracer import section
    log_dir = str(tmp_path_factory.mktemp("nests"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    try:
        with pytest.MonkeyPatch.context() as mp:
            for share in SHARES:
                mp.setattr(tracer, "PROBE_SHARE", share)
                for i in range(400):
                    with section("pg.do_op", op=f"{share}:{i}"):
                        with section("crc.host"):
                            with section("store.read") as s:
                                s.set_metadata(blocks=1)
    finally:
        jax.profiler.stop_trace()
    out = {}
    for evs in spans.clipped(spans.load(log_dir), 0, float("inf")):
        stack = []
        for s, e, name, meta in evs:
            while stack and stack[-1][0] <= s:
                stack.pop()
            if name == "pg.do_op":
                stack.append((e, out.setdefault(meta["op"], [])))
            if stack:
                stack[-1][1].append((name, meta))
    return out


SHARES = (0.0, 0.5, 1.0)


@pytest.mark.parametrize("share", SHARES)
def test_a_nest_is_probed_whole_or_not_at_all(nests, share):
    """The outermost section draws for its nest: all three sections
    carry ``cpu_ns`` or none does; about ``share`` of the nests do, and
    every section is in the trace either way."""
    probed = 0
    for i in range(400):
        nest = nests[f"{share}:{i}"]
        assert [n for n, _ in nest] == ["pg.do_op", "crc.host", "store.read"]
        has = {"cpu_ns" in m for _, m in nest}
        assert len(has) == 1, nest
        probed += has.pop()
        assert nest[-1][1]["blocks"] == 1
    assert {0.0: probed == 0, 0.5: 120 < probed < 280,
            1.0: probed == 400}[share], probed


def _spin(stop: threading.Event) -> None:
    while not stop.is_set():
        pass


@pytest.fixture(scope="module")
def probed(tmp_path_factory):
    """One session of sections around known work, each the only section
    of its name: a busy loop, twenty interpreter releases against a
    thread that spins, keywords set at both ends, and two hundred empty
    sections against the spinner."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from harness import spans
    from ceph_tpu.utils.tracer import section
    from ceph_tpu.utils import tracer
    log_dir = str(tmp_path_factory.mktemp("probes"))
    opts = jax.profiler.ProfileOptions()
    opts.python_tracer_level = 0
    mp = pytest.MonkeyPatch()
    mp.setattr(tracer, "PROBE_SHARE", 1.0)         # every nest probed
    jax.profiler.start_trace(log_dir, profiler_options=opts)
    stop = threading.Event()
    spinner = threading.Thread(target=_spin, args=(stop,))
    try:
        with section("pg.do_op", op="probe:1"):
            t0 = time.thread_time()
            while time.thread_time() - t0 < 0.05:
                pass
        with section("crc.host", bytes=3) as s:
            s.set_metadata(blocks=1, error="probe")
        spinner.start()
        time.sleep(0.01)
        with section("msgr.recv"):
            for _ in range(20):
                time.sleep(0)           # gives the interpreter up
        for _ in range(200):
            with section("store.read"):
                pass
    finally:
        stop.set()
        if spinner.is_alive():
            spinner.join(5)
        jax.profiler.stop_trace()
        mp.undo()
    assert not spinner.is_alive()
    out = {}
    for evs in spans.load(log_dir)["lines"]:
        for name, _, dur, meta in evs:
            out.setdefault(name, []).append((dur, meta))
    return out


def test_section_around_a_busy_loop_is_mostly_cpu(probed):
    [(wall_ns, meta)] = probed["pg.do_op"]
    assert meta["op"] == "probe:1"
    assert meta["cpu_ns"] >= 0.05e9 and meta["cpu_ns"] >= wall_ns / 2


def test_section_around_interpreter_releases_is_mostly_off_the_cpu(probed):
    """Each release against a spinning thread waits out its switch
    interval off the CPU: little CPU, much wall."""
    [(wall_ns, meta)] = probed["msgr.recv"]
    assert meta["cpu_ns"] < wall_ns / 10


def test_set_metadata_keywords_land_beside_the_sections_own(probed):
    [(_, meta)] = probed["crc.host"]
    assert own_keywords(meta) == {"bytes": 3, "blocks": 1, "error": "probe"}
    assert "cpu_ns" in meta


def test_the_probes_do_not_give_the_interpreter_up(probed):
    """An empty probed section is short even with a thread waiting for
    the interpreter: had a probe released it, the spinner would take it
    and every one would wait out a switch interval.  (A forced switch
    every interval can still land inside one of them.)"""
    import statistics
    reads = probed["store.read"]
    assert len(reads) == 200 and all("cpu_ns" in m for _, m in reads)
    assert statistics.median(d for d, _ in reads) < \
        sys.getswitchinterval() * 1e9 / 4


def test_section_without_jax_is_a_null_context():
    import subprocess
    code = ("import sys; from ceph_tpu.utils.tracer import section\n"
            "from ceph_tpu.client import rados\n"
            "with section('objecter.submit', op='c:1') as s:\n"
            "    s.set_metadata(bytes=1)\n"
            "assert 'jax' not in sys.modules, 'jax was imported'\n")
    subprocess.run([sys.executable, "-c", code], cwd=ROOT, check=True,
                   timeout=60)
