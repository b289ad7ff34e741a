"""The receive path, frame by frame.

A raw TCP peer does the banner handshake by hand and then writes
encoded frames at the receiver in pieces of its own choosing: whole,
byte by byte, split inside the header, inside the trailer, and back to
back with small frames between.  Both receivers are driven, the
reactor's ``CrimsonConnection`` and the threaded ``Connection``: every
message decodes equal to what was sent, the wire CRC rejects a flipped
byte, the ``rx_*`` account counts what it says, and a large data field
is a read-only view of the frame's own buffer while every small field
is ``bytes`` (ISSUE 35).
"""
import os
import socket
import threading
import time

import pytest

from ceph_tpu.cluster import test_config as make_conf
from ceph_tpu.crimson import Reactor
from ceph_tpu.crimson.net import _DIRECT_MIN, CrimsonMessenger
from ceph_tpu.msg import messages as M
from ceph_tpu.msg.message import CRC_LEN, HEADER_LEN, encode_frame
from ceph_tpu.msg.messenger import (Messenger, _recv_banner,
                                    _send_banner)
from ceph_tpu.utils.encoding import ZC_MIN

FLAVORS = ("crimson", "threaded")
SIZES = (0, 1, ZC_MIN - 1, ZC_MIN, 64 << 10, (512 << 10) + 7, 4 << 20)
LARGE = tuple(n for n in SIZES if n >= 64 << 10)


class _Sink:
    def __init__(self):
        self.msgs = []
        self.cond = threading.Condition()

    def ms_dispatch(self, conn, msg):
        with self.cond:
            self.msgs.append(msg)
            self.cond.notify_all()
        return True

    def ms_handle_connect(self, conn):
        pass

    def ms_handle_reset(self, conn):
        pass

    def wait_n(self, n, timeout=20.0):
        deadline = time.monotonic() + timeout
        with self.cond:
            while len(self.msgs) < n:
                left = deadline - time.monotonic()
                if left <= 0:
                    return False
                self.cond.wait(left)
        return True


class _Receiver:
    """One bound messenger of a flavor, and raw peers dialled at it."""

    def __init__(self, flavor, **conf):
        self.flavor = flavor
        self.reactor = None
        conf = make_conf(**conf)
        if flavor == "crimson":
            self.reactor = Reactor(name="rx-test")
            self.reactor.start()
            self.msgr = CrimsonMessenger("osd.0", conf=conf,
                                         reactor=self.reactor)
        else:
            self.msgr = Messenger("osd.0", conf=conf)
        self.sink = _Sink()
        self.msgr.add_dispatcher(self.sink)
        self.addr = self.msgr.bind()
        self.msgr.start()
        self.socks = []

    def dial(self, name="client.9", nonce=77, lossless=False):
        """-> (raw socket, the receiver's in_seq for this session)."""
        s = socket.create_connection(self.addr)
        s.setsockopt(socket.IPPROTO_TCP, socket.TCP_NODELAY, 1)
        _send_banner(s, name, nonce, 0, lossless)
        _, _, in_seq, _ = _recv_banner(s)
        self.socks.append(s)
        return s, in_seq

    def conn(self, name="client.9"):
        """The receiver's side of the session, once attached."""
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            with self.msgr.lock:
                for c in self.msgr.conns:
                    if c.peer_name == name and c.state == "open":
                        return c
            time.sleep(0.005)
        raise AssertionError("receiver never attached the session")

    def close(self):
        for s in self.socks:
            try:
                s.close()
            except OSError:
                pass
        self.msgr.shutdown()
        if self.reactor is not None:
            assert self.reactor.callbacks_failed == 0, \
                "the reactor swallowed an exception of the read pump"
            self.reactor.stop()


@pytest.fixture(params=FLAVORS)
def rx(request):
    r = _Receiver(request.param)
    yield r
    r.close()


def _peer_hung_up(sock) -> bool:
    """The receiver tore the session down (a reset counts: it closes
    with our unread bytes still queued)."""
    sock.settimeout(10)
    try:
        return sock.recv(1) == b""
    except ConnectionResetError:
        return True


def _sub_write(tid, size, seq):
    """A sub-write whose transaction buffer is ``size`` seeded bytes."""
    m = M.MOSDECSubOpWrite(pgid="1.2", shard=3, from_osd=0, tid=tid,
                           epoch=4, txn=os.urandom(size),
                           log_entries=[{"v": tid}], at_version=(4, tid))
    m.seq = seq
    return m


def _ping(epoch, seq):
    m = M.MOSDPing(op=M.MOSDPing.PING, from_osd=0, epoch=epoch)
    m.seq = seq
    return m


def _is_direct(flavor, frame_len):
    """Whether a frame of ``frame_len`` bytes on the wire is decoded
    where it landed: the reactor's pump gives a frame its own buffer
    from ``_DIRECT_MIN`` up (smaller ones are cut out of the reusable
    buffer), the threaded reader hands a payload of ``ZC_MIN`` bytes
    or more to the decoder as a view."""
    if flavor == "crimson":
        return frame_len >= _DIRECT_MIN
    return frame_len - HEADER_LEN - CRC_LEN >= ZC_MIN


def _pieces(frame: bytes, mode: str):
    n = len(frame)
    if mode == "whole":
        return [frame]
    if mode == "split_header":
        return [frame[:7], frame[7:]]
    if mode == "split_trailer":
        return [frame[:n - 2], frame[n - 2:]]
    assert mode == "bytewise"
    if n <= 4096:
        return [frame[i:i + 1] for i in range(n)]
    # the header and the first payload bytes one at a time, the middle
    # in three ragged pieces, the trailer and a little before it one
    # at a time
    a, b = 64, n - 6
    third = (b - a) // 3
    return ([frame[i:i + 1] for i in range(a)]
            + [frame[a:a + third], frame[a + third:a + 2 * third + 1],
               frame[a + 2 * third + 1:b]]
            + [frame[i:i + 1] for i in range(b, n)])


@pytest.mark.parametrize("mode", ("whole", "bytewise", "split_header",
                                  "split_trailer"))
@pytest.mark.parametrize("size", SIZES)
def test_frame_arrives_in_pieces(rx, size, mode):
    sock, _ = rx.dial()
    sent = _sub_write(5, size, seq=1)
    frame = encode_frame(sent)
    for piece in _pieces(frame, mode):
        sock.sendall(piece)
    assert rx.sink.wait_n(1), f"{rx.flavor}: frame of {size} never came"
    got = rx.sink.msgs[0]
    assert bytes(got.txn) == sent.txn
    assert (got.pgid, got.shard, got.tid, got.log_entries,
            got.at_version) == ("1.2", 3, 5, [{"v": 5}], (4, 5))
    conn = rx.conn()
    direct = _is_direct(rx.flavor, len(frame))
    assert (conn.rx_frames_direct, conn.rx_frames_bulk) == \
        ((1, 0) if direct else (0, 1))
    assert conn.rx_bytes == len(frame) - HEADER_LEN - CRC_LEN
    assert conn.rx_calls >= 1
    # a data field is a view from ZC_MIN up, whatever buffer it lies in
    assert isinstance(got.txn, bytes if size < ZC_MIN else memoryview)


@pytest.mark.parametrize("size", SIZES)
def test_back_to_back_with_small_frames_between(rx, size):
    """ping, frame, ping, frame, ping in one write: order holds, every
    frame is counted once, and nothing of one frame leaks into the
    next."""
    sock, _ = rx.dial()
    big = [_sub_write(1, size, seq=2), _sub_write(2, size, seq=4)]
    frames = [encode_frame(_ping(0, 1)), encode_frame(big[0]),
              encode_frame(_ping(1, 3)), encode_frame(big[1]),
              encode_frame(_ping(2, 5))]
    sock.sendall(b"".join(frames))
    assert rx.sink.wait_n(5)
    kinds = [type(m).__name__ for m in rx.sink.msgs]
    assert kinds == ["MOSDPing", "MOSDECSubOpWrite"] * 2 + ["MOSDPing"]
    assert [m.epoch for m in rx.sink.msgs[0::2]] == [0, 1, 2]
    for got, sent in zip(rx.sink.msgs[1::2], big):
        assert bytes(got.txn) == sent.txn and got.tid == sent.tid
    conn = rx.conn()
    n_direct = sum(_is_direct(rx.flavor, len(f)) for f in frames)
    assert conn.rx_frames_direct == n_direct
    assert conn.rx_frames_bulk == 5 - n_direct
    assert conn.rx_bytes == sum(len(f) - HEADER_LEN - CRC_LEN
                                for f in frames)


def _wait_frame_in_flight(rx, conn):
    """Until the receiver has the header (and so, on the reactor, the
    frame's own buffer)."""
    if rx.flavor != "crimson":
        time.sleep(0.05)        # the reader blocks in its payload read
        return
    deadline = time.monotonic() + 10
    while conn._fview is None:
        assert time.monotonic() < deadline, "header never parsed"
        time.sleep(0.002)


@pytest.mark.parametrize("size", LARGE)
def test_large_frame_after_its_header_copies_nothing(rx, size):
    """The header first, alone; then the rest.  No payload byte moves
    in user space: the kernel fills the frame's own buffer, and the
    transaction is a read-only view of that buffer."""
    sock, _ = rx.dial()
    sock.sendall(encode_frame(_ping(0, 1)))
    assert rx.sink.wait_n(1)
    conn = rx.conn()
    copied0 = conn.rx_bytes_copied
    sent = _sub_write(9, size, seq=2)
    frame = encode_frame(sent)
    sock.sendall(frame[:HEADER_LEN])
    _wait_frame_in_flight(rx, conn)
    sock.sendall(frame[HEADER_LEN:])
    assert rx.sink.wait_n(2)
    got = rx.sink.msgs[1]
    assert conn.rx_bytes_copied == copied0
    assert (conn.rx_frames_direct, conn.rx_frames_bulk) == (1, 1)
    txn = got.txn
    assert isinstance(txn, memoryview) and txn.readonly
    assert txn == sent.txn
    buf = txn.obj
    assert isinstance(buf, bytearray)
    plen = len(frame) - HEADER_LEN - CRC_LEN
    # the reactor's buffer holds the whole frame, the threaded
    # reader's the payload and the trailer
    assert len(buf) == (len(frame) if rx.flavor == "crimson"
                        else plen + CRC_LEN)
    with pytest.raises(TypeError):
        txn[0] = 0
    # nothing small pins the frame
    assert type(got.pgid) is str and type(got.log_entries) is list
    assert all(type(v) is int for v in (got.shard, got.tid, got.epoch))


def _data_messages(size):
    blob = os.urandom(size)
    return blob, [
        (M.MOSDOp(client="client.9", tid=1, oid="o",
                  ops=[M.OSDOp("writefull", 0, size, blob),
                       M.OSDOp("setxattr", data=blob, name="big")]),
         lambda m: m.ops[0].data),
        (M.MOSDOpReply(tid=1, out_data=[blob], extra={"a": 1}),
         lambda m: m.out_data[0]),
        (M.MOSDECSubOpWrite(pgid="1.0", shard=1, tid=1, txn=blob),
         lambda m: m.txn),
        (M.MOSDECSubOpReadReply(pgid="1.0", shard=1, tid=1,
                                buffers=[("o", 0, blob)],
                                attrs=[("o", {"k": b"v" * 3000})]),
         lambda m: m.buffers[0][2]),
    ]


@pytest.mark.parametrize("which", range(4), ids=(
    "MOSDOp.data", "MOSDOpReply.out_data", "MOSDECSubOpWrite.txn",
    "MOSDECSubOpReadReply.buffers"))
def test_each_data_field_is_a_view_of_the_frame(rx, which):
    blob, msgs = _data_messages(96 << 10)
    sent, field = msgs[which]
    sent.seq = 1
    sock, _ = rx.dial()
    sock.sendall(encode_frame(sent))
    assert rx.sink.wait_n(1)
    got = rx.sink.msgs[0]
    data = field(got)
    assert isinstance(data, memoryview) and data.readonly
    assert isinstance(data.obj, bytearray) and data == blob
    # what is not object data decodes as bytes, however large
    if which == 0:
        assert type(got.ops[1].data) is bytes and got.ops[1].data == blob
        assert type(got.oid) is str and type(got.client) is str
    if which == 1:
        assert got.extra == {"a": 1}
    if which == 3:
        assert type(got.attrs[0][1]["k"]) is bytes
        assert type(got.buffers[0][0]) is str


@pytest.mark.parametrize("size", (1, ZC_MIN, 64 << 10, 4 << 20))
def test_crc_rejects_a_flipped_byte(rx, size):
    """A payload byte flipped on the wire: the frame is never
    dispatched and the (lossy) session is torn down."""
    sock, _ = rx.dial()
    good = encode_frame(_ping(0, 1))
    sock.sendall(good)
    assert rx.sink.wait_n(1)
    frame = bytearray(encode_frame(_sub_write(3, size, seq=2)))
    frame[HEADER_LEN + (len(frame) - HEADER_LEN - CRC_LEN) // 2] ^= 0x40
    sock.sendall(bytes(frame) + encode_frame(_ping(1, 3)))
    assert _peer_hung_up(sock), "the receiver kept a corrupt stream open"
    assert len(rx.sink.msgs) == 1


@pytest.mark.parametrize("flavor", FLAVORS)
def test_bad_header_after_good_frames(flavor):
    """Frames ahead of a bad header are delivered, the header kills
    the stream, nothing after it is read."""
    rx = _Receiver(flavor)
    try:
        sock, _ = rx.dial()
        sock.sendall(encode_frame(_ping(0, 1)) + encode_frame(_ping(1, 2))
                     + b"\xde\xad\xbe\xef" * 8
                     + encode_frame(_ping(2, 3)))
        assert rx.sink.wait_n(2)
        assert _peer_hung_up(sock)
        assert [m.epoch for m in rx.sink.msgs] == [0, 1]
    finally:
        rx.close()


@pytest.mark.parametrize("flavor", FLAVORS)
def test_die_on_bad_msg_with_a_large_frame(flavor):
    """``ms_die_on_bad_msg``: a corrupt large frame raises out of the
    pump instead of resetting quietly, and is not dispatched."""
    rx = _Receiver(flavor, ms_die_on_bad_msg=True)
    raised = []
    hook = threading.excepthook
    threading.excepthook = lambda args: raised.append(args.exc_type)
    try:
        sock, _ = rx.dial()
        sock.sendall(encode_frame(_ping(0, 1)))
        assert rx.sink.wait_n(1)
        frame = bytearray(encode_frame(_sub_write(3, 256 << 10, seq=2)))
        frame[HEADER_LEN + 1000] ^= 1
        sock.sendall(bytes(frame))
        deadline = time.monotonic() + 10
        while time.monotonic() < deadline:
            if flavor == "crimson" and rx.reactor.callbacks_failed:
                break
            if flavor == "threaded" and raised:
                break
            time.sleep(0.01)
        if flavor == "crimson":
            assert rx.reactor.callbacks_failed == 1
            rx.reactor.callbacks_failed = 0     # accounted for
        else:
            from ceph_tpu.utils.encoding import DecodeError
            assert raised == [DecodeError]
        assert len(rx.sink.msgs) == 1
    finally:
        threading.excepthook = hook
        rx.close()


@pytest.mark.parametrize("flavor", FLAVORS)
def test_half_received_frame_dies_with_its_socket(flavor):
    """A lossless peer dies with a large frame half sent.  The
    half-filled buffer goes with the socket generation; the peer
    redials, resends from the receiver's ``in_seq`` (one duplicate
    ahead of it), and the frame is delivered once, whole."""
    rx = _Receiver(flavor)
    try:
        sock, in_seq = rx.dial(name="osd.7", nonce=5, lossless=True)
        assert in_seq == 0
        sock.sendall(encode_frame(_ping(0, 1)))
        assert rx.sink.wait_n(1)
        conn = rx.conn("osd.7")
        sent = _sub_write(11, (1 << 20) + 5, seq=2)
        frame = encode_frame(sent)
        sock.sendall(frame[:len(frame) // 2])
        _wait_frame_in_flight(rx, conn)
        if flavor == "crimson":
            deadline = time.monotonic() + 10
            while conn._fgot < len(frame) // 2:
                assert time.monotonic() < deadline
                time.sleep(0.002)
        sock.close()
        # the acceptor keeps the session and waits for the redial
        deadline = time.monotonic() + 10
        while conn.state == "open":
            assert time.monotonic() < deadline, "socket death unnoticed"
            time.sleep(0.005)
        if flavor == "crimson":
            assert conn._fview is None and conn._fgot == 0
        sock2, in_seq = rx.dial(name="osd.7", nonce=5, lossless=True)
        assert in_seq == 1, "the half frame must not have counted"
        sock2.sendall(encode_frame(_ping(0, 1)) + frame
                      + encode_frame(_ping(1, 3)))
        assert rx.sink.wait_n(3)
        time.sleep(0.05)
        kinds = [type(m).__name__ for m in rx.sink.msgs]
        assert kinds == ["MOSDPing", "MOSDECSubOpWrite", "MOSDPing"]
        assert bytes(rx.sink.msgs[1].txn) == sent.txn
        assert rx.conn("osd.7") is conn
        assert conn.in_seq == 3
    finally:
        rx.close()


@pytest.mark.parametrize("flavor", FLAVORS)
def test_small_traffic_stays_in_the_reusable_buffer(flavor):
    """Pings, acks, sub-write replies: none gets a buffer of its own,
    and a burst of them costs the reactor few receive calls."""
    rx = _Receiver(flavor)
    try:
        sock, _ = rx.dial()
        n = 200
        sock.sendall(b"".join(encode_frame(_ping(i, i + 1))
                              for i in range(n)))
        assert rx.sink.wait_n(n)
        assert [m.epoch for m in rx.sink.msgs] == list(range(n))
        conn = rx.conn()
        assert (conn.rx_frames_direct, conn.rx_frames_bulk) == (0, n)
        assert conn.rx_bytes_copied >= conn.rx_bytes
        if flavor == "crimson":
            assert conn.rx_calls < n // 4
    finally:
        rx.close()
