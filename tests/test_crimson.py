"""crimson reactor OSD: unit + cluster smoke + fault tolerance.

The contract under test (ISSUE 2): the reactor runs the whole client
data path on one thread with futures instead of shard queues; the
crimson messenger keeps every session rule of the threaded one; the
EC batcher's window is cut at tick boundaries; and a crimson OSD is
operationally indistinguishable from a classic one — boot, heartbeat
failure reporting, kill/revive recovery, and mixed clusters all
behave identically.  The long RadosModel thrash soak is marked
``slow``; everything else is tier-1.
"""
import os
import threading
import time

import numpy as np
import pytest

from ceph_tpu.cluster import Cluster
from ceph_tpu.cluster import test_config as make_conf
from ceph_tpu.crimson import CrimsonOSD, Reactor
from ceph_tpu.crimson.net import CrimsonMessenger
from ceph_tpu.osd.osd import OSD
from ceph_tpu.utils.machine import scaled


# --------------------------------------------------------------- reactor
def test_call_soon_runs_on_reactor_thread():
    r = Reactor(name="t-reactor")
    r.start()
    try:
        seen = []
        done = threading.Event()

        def job(tag):
            seen.append((tag, threading.current_thread().name))
            if len(seen) == 3:
                done.set()

        for i in range(3):
            r.call_soon(job, i)
        assert done.wait(5)
        assert [s[0] for s in seen] == [0, 1, 2], "FIFO order"
        assert all(name == "t-reactor" for _, name in seen)
    finally:
        r.stop()


def test_call_later_ordering_and_cancel():
    r = Reactor()
    r.start()
    try:
        fired = []
        done = threading.Event()
        r.call_later(0.15, lambda: (fired.append("late"), done.set()))
        r.call_later(0.01, lambda: fired.append("early"))
        victim = r.call_later(0.05, lambda: fired.append("never"))
        victim.cancel()
        assert done.wait(5)
        assert fired == ["early", "late"]
    finally:
        r.stop()


def test_future_chain_and_exception_propagation():
    r = Reactor()
    r.start()
    try:
        out = []
        done = threading.Event()
        f = r.future()
        # mapper returning a Future splices in; exception propagates
        # down the chain past intermediate stages
        chained = f.then(lambda v: v + 1).then(
            lambda v: r.resolved(v * 10))

        def tail(v):
            out.append(v)
            raise RuntimeError("boom")

        err = chained.then(tail)
        err.add_done_callback(lambda fut: (
            out.append(type(fut.exception()).__name__), done.set()))
        f.set_result(1)
        assert done.wait(5)
        assert out == [20, "RuntimeError"]
    finally:
        r.stop()


def test_set_result_defers_callbacks():
    # asyncio semantics: resolving a future never runs continuations
    # synchronously, even from the reactor thread — a chain resolved
    # under a lock must not reenter
    r = Reactor()
    r.start()
    try:
        order = []
        done = threading.Event()

        def driver():
            f = r.future()
            f.then(lambda _: (order.append("cb"), done.set()))
            f.set_result(None)
            order.append("after-set")

        r.call_soon(driver)
        assert done.wait(5)
        assert order == ["after-set", "cb"]
    finally:
        r.stop()


def test_tick_hooks_run_every_tick():
    r = Reactor()
    hits = []
    r.add_tick_hook(lambda: hits.append(1))
    r.start()
    try:
        deadline = time.monotonic() + 5
        while len(hits) < 3 and time.monotonic() < deadline:
            r.call_soon(lambda: None)
            time.sleep(0.01)
        assert len(hits) >= 3
    finally:
        r.stop()


# ----------------------------------------------------- batcher tick flush
def test_tick_flush_cuts_the_batch_window():
    """With a multi-second window, tick_flush() must dispatch the
    queued stripes immediately — this is what makes reactor-tick
    batching latency-free vs the classic timed window."""
    from ceph_tpu.ec import registry as ecreg
    from ceph_tpu.osd import ecutil
    from ceph_tpu.osd.batcher import EncodeBatcher

    codec = ecreg.instance().factory(
        "tpu", {"k": "2", "m": "1", "technique": "reed_sol_van"})
    # pay the jit compile before timing anything
    codec.encode_batch_async(
        np.zeros((4, 2, 4096), dtype=np.uint8)).wait()
    EncodeBatcher.reset_learning()
    b = EncodeBatcher({"ec_tpu_batch_stripes": 1024,
                       "ec_tpu_queue_window_us": 8_000_000})
    try:
        sinfo = ecutil.StripeInfo(2, 8192)
        data = os.urandom(4 * 8192)
        got = {}
        done = threading.Event()
        b.submit(codec, sinfo, data,
                 lambda chunks: (got.update(chunks), done.set()))
        assert not done.wait(0.3), "dispatched before the window cut?"
        t0 = time.monotonic()
        b.tick_flush()
        assert done.wait(10)
        assert time.monotonic() - t0 < 5.0, \
            "tick_flush did not cut the 8s window"
        assert got == ecutil.encode(sinfo, codec, data)
        assert b.calls + b.cpu_calls == 1
    finally:
        b.stop()


# ----------------------------------------------------- crimson messenger
class _Capture:
    """Dispatcher recording (msg, dispatching-thread-name)."""

    def __init__(self):
        self.got = []
        self.cond = threading.Condition()

    def ms_dispatch(self, conn, msg):
        with self.cond:
            self.got.append((msg, threading.current_thread().name))
            self.cond.notify_all()
        return True

    def ms_handle_connect(self, conn):
        pass

    def ms_handle_reset(self, conn):
        pass

    def wait_n(self, n, timeout=10.0):
        deadline = time.monotonic() + scaled(timeout)
        with self.cond:
            while len(self.got) < n:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self.cond.wait(left)
        return True


def test_crimson_messengers_exchange_and_reply_on_reactor():
    from ceph_tpu.msg.messages import MOSDPing

    conf = make_conf()
    ra, rb = Reactor(name="msgr-ra"), Reactor(name="msgr-rb")
    ra.start()
    rb.start()
    ma = CrimsonMessenger("osd.0", conf=conf, reactor=ra)
    mb = CrimsonMessenger("osd.1", conf=conf, reactor=rb)
    ca, cb = _Capture(), _Capture()
    ma.add_dispatcher(ca)
    mb.add_dispatcher(cb)
    try:
        ma.bind()
        mb.bind()
        ma.start()
        mb.start()
        conn = ma.connect_to(mb.my_addr, peer_name="osd.1")
        n = 40
        for i in range(n):
            conn.send_message(MOSDPing(op=MOSDPing.PING, from_osd=0,
                                       epoch=i))
        assert cb.wait_n(n), f"B got {len(cb.got)}/{n}"
        # receiver dispatched inline on ITS reactor thread
        assert {t for _, t in cb.got} == {"msgr-rb"}
        assert [m.epoch for m, _ in cb.got] == list(range(n))
        # reply over the accepted (also crimson) connection
        back = cb.got[0][0].connection
        for i in range(n):
            back.send_message(MOSDPing(op=MOSDPing.PING_REPLY,
                                       from_osd=1, epoch=i))
        assert ca.wait_n(n), f"A got {len(ca.got)}/{n}"
        assert {t for _, t in ca.got} == {"msgr-ra"}
    finally:
        ma.shutdown()
        mb.shutdown()
        ra.stop()
        rb.stop()


def _numbered(i, big):
    """Message ``i`` of a stream: a ping, or (``big``) a sub-write
    whose 384 KiB transaction is received into a buffer of its own."""
    from ceph_tpu.msg.messages import MOSDECSubOpWrite, MOSDPing
    if not big:
        return MOSDPing(op=MOSDPing.PING, from_osd=0, epoch=i)
    return MOSDECSubOpWrite(pgid="1.0", shard=1, from_osd=0, tid=i,
                            epoch=i, txn=bytes([i]) * ((384 << 10) + i))


def _intact(msgs, big):
    return not big or all(
        isinstance(m.txn, memoryview)
        and m.txn == bytes([m.epoch]) * ((384 << 10) + m.epoch)
        for m in msgs if hasattr(m, "txn"))


@pytest.mark.parametrize("big", (False, True), ids=("small", "large"))
def test_crimson_lossless_survives_socket_death(big):
    """Kill the TCP socket under a lossless session: the base-class
    reconnect machinery must redial and the unacked queue must resend,
    with the non-blocking pumps re-registered on the new socket.
    ``large``: the stream is 384 KiB frames, so the socket dies with
    one half received; its buffer is dropped with the generation and
    the resend delivers it once."""
    from ceph_tpu.msg.messages import MOSDPing

    conf = make_conf()
    ra, rb = Reactor(), Reactor()
    ra.start()
    rb.start()
    ma = CrimsonMessenger("osd.0", conf=conf, reactor=ra)
    mb = CrimsonMessenger("osd.1", conf=conf, reactor=rb)
    cb = _Capture()
    mb.add_dispatcher(cb)
    ma.add_dispatcher(_Capture())
    try:
        ma.bind()
        mb.bind()
        ma.start()
        mb.start()
        conn = ma.connect_to(mb.my_addr, peer_name="osd.1")
        conn.send_message(MOSDPing(op=MOSDPing.PING, from_osd=0,
                                   epoch=0))
        assert cb.wait_n(1)
        # yank the transport out from under the session
        with conn.lock:
            sock, gen = conn.sock, conn.gen
        if big:
            # frames already on their way when the socket goes
            for i in range(1, 6):
                conn.send_message(_numbered(i, big))
        sock.close()
        for i in range(6 if big else 1, 21):
            conn.send_message(_numbered(i, big))
        assert cb.wait_n(21, 20), \
            f"only {len(cb.got)}/21 after reconnect"
        # at-most-once delivery held across the reconnect
        epochs = [m.epoch for m, _ in cb.got]
        assert epochs == sorted(set(epochs)) == list(range(21))
        assert _intact([m for m, _ in cb.got], big)
    finally:
        ma.shutdown()
        mb.shutdown()
        ra.stop()
        rb.stop()


@pytest.mark.parametrize("big", (False, True), ids=("small", "large"))
def test_socket_failure_injection_parity_with_classic(big):
    """``ms_inject_socket_failures`` must behave identically on the
    crimson messenger and the classic one: both consult the SAME
    fault-registry site (msg.send) before every frame write, both
    count their trips there, and both survive the injected socket
    deaths with exactly-once in-order delivery.  ``large``: with
    384 KiB frames, so sockets die with a frame's own buffer half
    filled."""
    from ceph_tpu.msg.messenger import Messenger
    from ceph_tpu.utils import faults as faultlib

    def run(flavor):
        faultlib.registry().reset()
        faultlib.registry().seed_all(13)
        conf = make_conf(ms_inject_socket_failures=10,
                         ms_connection_retry_interval=0.02)
        reactors = []
        if flavor == "crimson":
            reactors = [Reactor(), Reactor()]
            for r in reactors:
                r.start()
            ma = CrimsonMessenger("osd.0", conf=conf,
                                  reactor=reactors[0])
            mb = CrimsonMessenger("osd.1", conf=conf,
                                  reactor=reactors[1])
        else:
            ma = Messenger("osd.0", conf=conf)
            mb = Messenger("osd.1", conf=conf)
        sink = _Capture()
        mb.add_dispatcher(sink)
        ma.add_dispatcher(_Capture())
        try:
            ma.bind()
            addr = mb.bind()
            ma.start()
            mb.start()
            conn = ma.connect_to(addr, peer_name="osd.1")
            n = 60
            for i in range(n):
                conn.send_message(_numbered(i, big))
            assert sink.wait_n(n, 60), \
                f"{flavor}: {len(sink.got)}/{n} after injection"
            epochs = [m.epoch for m, _ in sink.got]
            assert epochs == list(range(n)), \
                f"{flavor}: delivery not exactly-once in-order"
            assert _intact([m for m, _ in sink.got], big)
            c = faultlib.registry().counters()[faultlib.MSG_SEND]
        finally:
            ma.shutdown()
            mb.shutdown()
            for r in reactors:
                r.stop()
            faultlib.registry().reset()
        return c

    classic = run("classic")
    crimson = run("crimson")
    # both flavors absorbed the legacy conf into the shared site
    for flavor, c in (("classic", classic), ("crimson", crimson)):
        assert c["trips"] >= 1, f"{flavor} never tripped msg.send"
        assert c["hits"] >= 60, f"{flavor} skipped the injection gate"


def test_crimson_messenger_rejects_secure_mode():
    r = Reactor()
    with pytest.raises(ValueError, match="secure"):
        CrimsonMessenger("osd.9", conf=make_conf(
            ms_secure_mode=True, auth_cluster_required="cephx",
            auth_key="c2VjcmV0"), reactor=r)


# ------------------------------------------------------- cluster smoke
def test_crimson_cluster_replicated_and_ec_io():
    conf = make_conf(osd_backend="crimson")
    with Cluster(n_osds=3, conf=conf) as c:
        for i in range(3):
            c.wait_for_osd_up(i, 30)
        assert all(type(o) is CrimsonOSD for o in c.osds.values())
        c.create_pool("rp", "replicated")
        io = c.rados().open_ioctx("rp")
        io.write_full("obj", b"crimson" * 512)
        assert io.read("obj") == b"crimson" * 512
        c.create_ec_profile("p21", plugin="tpu", k="2", m="1")
        c.create_pool("ecp", "erasure", erasure_code_profile="p21")
        io2 = c.rados().open_ioctx("ecp")
        blob = os.urandom(256 << 10)
        io2.write_full("eobj", blob)
        assert io2.read("eobj") == blob
        # the op tracker kept the PR-1 stage names, so attribution
        # JSON compares across backends
        events = set()
        for osd in c.osds.values():
            for opd in osd.op_tracker.dump_historic_ops():
                events.update(e["event"] for e in opd["events"])
        assert "queued_for_pg" in events
        assert "reached_pg" in events
        # reactors actually ticked and ran the continuations
        assert all(o.reactor.callbacks_run > 0
                   for o in c.osds.values())


def test_mixed_cluster_classic_and_crimson_side_by_side():
    # ISSUE 8 flipped the default to crimson, so the mixed-cluster
    # case is now classic-by-override: pin the conf back to classic
    # and promote one OSD
    conf = make_conf(osd_backend="classic")
    c = Cluster(n_osds=3, conf=conf)
    c.backend_overrides[1] = "crimson"
    with c:
        for i in range(3):
            c.wait_for_osd_up(i, 30)
        assert type(c.osds[0]) is OSD
        assert type(c.osds[1]) is CrimsonOSD
        assert type(c.osds[2]) is OSD
        c.create_ec_profile("pm", plugin="tpu", k="2", m="1")
        c.create_pool("mixed", "erasure", erasure_code_profile="pm")
        io = c.rados().open_ioctx("mixed")
        for i in range(8):
            io.write_full(f"o{i}", bytes([i]) * 8192)
        for i in range(8):
            assert io.read(f"o{i}") == bytes([i]) * 8192


def test_crimson_is_the_default_backend():
    """ISSUE 8: a cluster built with NO backend override boots
    crimson OSDs — and boot/heartbeat/IO behave like they always did
    (the parity bar for flipping the vstart default)."""
    with Cluster(n_osds=3, conf=make_conf()) as c:
        for i in range(3):
            c.wait_for_osd_up(i, 30)
        assert all(type(o) is CrimsonOSD for o in c.osds.values())
        c.create_pool("dp", "replicated")
        io = c.rados().open_ioctx("dp")
        io.write_full("obj", b"default" * 64)
        assert io.read("obj") == b"default" * 64


def test_crimson_default_kill_revive_recovery_parity():
    """Crimson-default recovery parity: kill an OSD under the default
    conf, confirm peers report it down, revive, and rebuild to clean
    (the classic-thread maintenance path, now on reactor timers, must
    drive the same outcome)."""
    with Cluster(n_osds=3, conf=make_conf()) as c:
        for i in range(3):
            c.wait_for_osd_up(i, 30)
        c.create_pool("rp", "replicated", size=2)
        io = c.rados().open_ioctx("rp")
        for i in range(8):
            io.write_full(f"o{i}", bytes([i]) * 4096)
        c.wait_for_clean(30)
        c.kill_osd(2)
        c.wait_for_osd_down(2, 30)
        c.revive_osd(2)
        assert type(c.osds[2]) is CrimsonOSD
        c.wait_for_osd_up(2, 15)
        c.wait_for_clean(60)
        for i in range(8):
            assert io.read(f"o{i}") == bytes([i]) * 4096


def test_crimson_osd_down_detection_and_rebuild():
    """Thrash acceptance: heartbeat reporting marks a killed crimson
    OSD down; a revive (fresh store = disk loss) rebuilds to clean
    with every object intact."""
    conf = make_conf(osd_backend="crimson")
    with Cluster(n_osds=4, conf=conf) as c:
        for i in range(4):
            c.wait_for_osd_up(i, 30)
        c.create_ec_profile("p21", plugin="tpu", k="2", m="1")
        c.create_pool("ecp", "erasure", erasure_code_profile="p21")
        io = c.rados().open_ioctx("ecp")
        for i in range(12):
            io.write_full(f"o{i}", bytes([i]) * 8192)
        c.wait_for_clean(30)
        c.kill_osd(3, lose_data=True)
        c.wait_for_osd_down(3, 30)       # peers reported it silent
        assert io.read("o5") == bytes([5]) * 8192, "degraded read"
        c.revive_osd(3)
        assert type(c.osds[3]) is CrimsonOSD, "backend sticky"
        c.wait_for_osd_up(3, 15)
        c.wait_for_clean(120)
        for i in range(12):
            assert io.read(f"o{i}") == bytes([i]) * 8192


@pytest.mark.slow
def test_crimson_thrash_radosmodel_soak():
    """Full thrash soak under crimson: random kills/revives during a
    random RadosModel workload, byte-exact verification after settle
    (same bar as test_thrash.py, backend flipped)."""
    from ceph_tpu.tools.thrash import RadosModel, Thrasher

    conf = make_conf(osd_backend="crimson")
    with Cluster(n_osds=4, conf=conf) as c:
        for i in range(4):
            c.wait_for_osd_up(i, 30)
        c.create_pool("soak", "replicated", size=3)
        client = c.rados(timeout=30)
        client.op_timeout = 120.0
        io = client.open_ioctx("soak")
        model = RadosModel(io, seed=7, snaps=True)
        model.run(50)
        thrasher = Thrasher(c, seed=7, min_alive=3,
                            interval=4.0).start()
        deadline = time.monotonic() + 12.0
        while time.monotonic() < deadline:
            model.step()
        thrasher.stop_and_settle(timeout=120)
        assert model.verify_all() == [], thrasher.actions
        assert all(type(o) is CrimsonOSD
                   for o in c.osds.values() if o is not None)
