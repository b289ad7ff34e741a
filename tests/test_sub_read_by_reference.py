"""A shard's sub-read is copied once, by the store's gather (ISSUE 38).

``ObjectStore.read_buffer`` hands out the verified gather buffer as a
view where ``read`` returns ``bytes``; ``ECBackend._chunk_read`` checks
that view against ``HashInfo`` in place and passes it on read-only;
``MOSDECSubOpReadReply`` sends its buffers by reference.  Held here:
the same bytes and errors as before on every store, the same wire
bytes as the parent's encoder, and ``copied`` 0 on the sections of a
degraded read under a profiler session.
"""
import errno
import hashlib
import os
import random
import sys
import types

import pytest

from ceph_tpu.msg import messages as M
from ceph_tpu.msg.message import (decode_frame_body, decode_frame_header,
                                  encode_frame, encode_frame_parts,
                                  HEADER_LEN, CRC_LEN)
from ceph_tpu.osd import ecutil
from ceph_tpu.osd.ecbackend import ECBackend
from ceph_tpu.store.blockstore import BLOCK, BlockStore
from ceph_tpu.store.bluestore import BlueStore
from ceph_tpu.store.memstore import MemStore
from ceph_tpu.store.objectstore import GHObject, Transaction

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
C = "1.0s0"
MiB = 1 << 20


def obj(name):
    return GHObject(name, 0)


# -- (a) the store's two entries ------------------------------------------

#: object -> how it is written: (offset, bytes) pieces
OBJECTS = {
    # seven blocks and a ragged tail: aligned and unaligned ranges
    "plain": [(0, random.Random(1).randbytes(7 * BLOCK + 100))],
    # written at its fourth block only: three holes before it
    "holes": [(3 * BLOCK, random.Random(2).randbytes(BLOCK + 17))],
    # compressible: stored as a compressed segment under zlib
    "squeezed": [(0, b"compress me! " * 5000)],
}
#: (offset, length): length None reads to EOF
RANGES = [(0, None), (0, 5 * BLOCK), (1, 5000), (BLOCK, BLOCK),
          (100, 0), (2 * BLOCK - 10, 30), (5 * BLOCK, 10 * BLOCK),
          (100 * BLOCK, 10)]


def _mount(kind, tmp_path):
    if kind == "blockstore":
        s = BlockStore(str(tmp_path / "bs"), compression="zlib")
    elif kind == "bluestore":
        s = BlueStore("", compression="zlib")      # RAM mode
    else:
        s = MemStore()
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    for name, pieces in OBJECTS.items():
        t = Transaction()
        for off, data in pieces:
            t.write(C, obj(name), off, data)
        s.queue_transactions([t])
    s.flush()
    return s


@pytest.fixture(scope="module", params=["blockstore", "bluestore",
                                        "memstore"])
def store(request, tmp_path_factory):
    s = _mount(request.param, tmp_path_factory.mktemp(request.param))
    yield request.param, s
    s.umount()


@pytest.mark.parametrize("name", sorted(OBJECTS))
@pytest.mark.parametrize("rng", RANGES, ids=lambda r: f"{r[0]}+{r[1]}")
def test_read_buffer_is_read(store, name, rng):
    kind, s = store
    got = s.read_buffer(C, obj(name), *rng)
    want = s.read(C, obj(name), *rng)
    assert type(want) is bytes
    assert bytes(got) == want
    if kind == "memstore":
        # the base entry: what read returns, as read returns it
        assert type(got) is bytes
    else:
        # a view of the gather's own buffer: writable, nobody else's
        assert isinstance(got, memoryview) and not got.readonly


def test_the_compressed_object_is_a_segment(store):
    kind, s = store
    if kind != "memstore":          # MemStore keeps one bytearray
        ext = s._load_extents(C, obj("squeezed"))
        assert any(p <= -2 for p in ext.blocks)
    assert bytes(s.read_buffer(C, obj("squeezed"))) == \
        OBJECTS["squeezed"][0][1]


@pytest.mark.parametrize("entry", ["read", "read_buffer"])
@pytest.mark.parametrize("kind", ["blockstore", "bluestore"])
def test_a_flipped_block_is_eio_on_both_entries(kind, entry, tmp_path):
    s = _mount(kind, tmp_path)
    try:
        phys = s._load_extents(C, obj("plain")).blocks[2]
        assert phys >= 0
        dev = s._dev
        dev.seek(phys * BLOCK + 5)
        b = dev.read(1)
        dev.seek(phys * BLOCK + 5)
        dev.write(bytes([b[0] ^ 0x10]))
        dev.flush()
        with pytest.raises(OSError) as e:
            getattr(s, entry)(C, obj("plain"), 0, 4 * BLOCK)
        assert e.value.errno == errno.EIO
        # the blocks before it still verify
        assert bytes(getattr(s, entry)(C, obj("plain"), 0, 2 * BLOCK)) \
            == OBJECTS["plain"][0][1][:2 * BLOCK]
    finally:
        s.umount()


# -- (b) the shard's sub-read ----------------------------------------------

def _backend(shard_bytes, hinfo_bytes):
    """An ECBackend over one bluestore (RAM mode) that holds shard 0 of
    ``o`` with a HashInfo taken over ``hinfo_bytes``."""
    from ceph_tpu.ec import registry as ecreg
    s = BlueStore("")
    s.mkfs()
    s.mount()
    hinfo = ecutil.HashInfo(6)
    hinfo.append(0, {0: hinfo_bytes})
    t = Transaction().create_collection(C)
    t.write(C, obj("o"), 0, shard_bytes)
    t.setattr(C, obj("o"), ecutil.HINFO_KEY, hinfo.encode())
    s.queue_transactions([t])
    s.flush()
    host = types.SimpleNamespace(store=s, pgid_str="1.0", whoami=0,
                                 coll_of=lambda shard: f"1.0s{shard}")
    codec = ecreg.instance().factory("jerasure", {
        "technique": "reed_sol_van", "k": "4", "m": "2"})
    return ECBackend(host, codec, stripe_width=4 * BLOCK), s


SHARD = random.Random(38).randbytes(64 * BLOCK)


def test_a_whole_shard_read_is_read_only_and_exact():
    be, s = _backend(SHARD, SHARD)
    try:
        data, err = be._chunk_read("o", 0, 0, len(SHARD))
        assert err == 0 and bytes(data) == SHARD
        assert isinstance(data, memoryview) and data.readonly
        with pytest.raises(TypeError):
            data[0] = 1
        # a ranged read (no HashInfo check) is read-only too
        data, err = be._chunk_read("o", 0, BLOCK, BLOCK)
        assert err == 0 and bytes(data) == SHARD[BLOCK:2 * BLOCK]
        with pytest.raises(TypeError):
            data[0] = 1
    finally:
        s.umount()


def test_a_shard_that_no_longer_matches_hashinfo_is_eio():
    rotten = bytearray(SHARD)
    rotten[12345] ^= 0x01            # the store's own CRCs are fresh
    be, s = _backend(bytes(rotten), SHARD)
    try:
        assert be._chunk_read("o", 0, 0, len(SHARD)) == (b"", -5)
    finally:
        s.umount()


def test_a_short_shard_is_eio():
    be, s = _backend(SHARD, SHARD)
    try:
        assert be._chunk_read("o", 0, 0, len(SHARD) + BLOCK) == (b"", -5)
        assert be._chunk_read("gone", 0, 0, BLOCK) == (b"", -2)
    finally:
        s.umount()


# -- (c) the read reply on the wire ----------------------------------------

def _reply(kind):
    """A read reply with buffers, attrs, errors and hops; ``kind`` says
    what the large buffer is."""
    big = random.Random(381).randbytes(MiB)
    if kind == "view":                  # as _chunk_read hands it out
        big = memoryview(bytearray(big)).toreadonly()
    m = M.MOSDECSubOpReadReply(pgid="3.1f", shard=2, from_osd=5,
                               tid=77, epoch=9)
    m.buffers = [("obj", 0, big), ("obj", MiB, b"t" * 100)]
    if kind == "small":
        m.buffers = [("obj", 0, b"s" * (BLOCK - 1))]
    m.attrs = [("obj", {"hinfo_key": b"\x01" * 40, "_": b"oi"})]
    m.errors = [("gone", -2), ("rot", -5)]
    m.hops = {"client_send": 1.5, "shard_read": 2.25}
    m.seq = 12
    return m


#: sha256 of encode_frame(_reply(kind)) on the parent b1287f0, whose
#: reply had no parts of its own and joined its payload
PARENT_FRAME = {
    "bytes": "21ebdecea46e41f0b1f4a3cfe392e1b059dc59faff7fab780301456ddd94b313",
    "view": "21ebdecea46e41f0b1f4a3cfe392e1b059dc59faff7fab780301456ddd94b313",
    "small": "f5828a5f764231f8e576177a94727f44de615496c7ae22ceab0423bd3ee75d05",
}


@pytest.mark.parametrize("kind", sorted(PARENT_FRAME))
def test_the_reply_frame_is_the_parents_byte_for_byte(kind):
    m = _reply(kind)
    frame = encode_frame(m)
    assert b"".join(encode_frame_parts(m)) == frame
    assert b"".join(m.encode_payload_parts()) == m.encode_payload()
    assert hashlib.sha256(frame).hexdigest() == PARENT_FRAME[kind]
    mtype, seq, plen = decode_frame_header(frame[:HEADER_LEN])
    back = decode_frame_body(mtype, seq, frame[:HEADER_LEN],
                             frame[HEADER_LEN:HEADER_LEN + plen],
                             frame[HEADER_LEN + plen:])
    assert [(o, off, bytes(d)) for o, off, d in back.buffers] == \
        [(o, off, bytes(d)) for o, off, d in m.buffers]
    assert back.attrs == m.attrs and back.errors == m.errors
    assert back.hops == m.hops and len(frame) == HEADER_LEN + plen + CRC_LEN


@pytest.mark.parametrize("kind", ["bytes", "view"])
def test_the_reply_sends_its_buffer_by_reference(kind):
    from ceph_tpu.utils.encoding import copied_bytes
    m = _reply(kind)
    big = m.buffers[0][2]
    c0 = copied_bytes()
    parts = encode_frame_parts(m)
    assert copied_bytes() == c0
    (part,) = [p for p in parts if len(p) == MiB]
    if kind == "bytes":
        assert part is big
    else:                               # a view of the same buffer
        assert part.obj is big.obj


def test_a_message_with_no_parts_counts_its_payload_copied():
    from ceph_tpu.utils.encoding import copied_bytes
    m = M.MOSDPing(from_osd=1, epoch=2)
    c0 = copied_bytes()
    parts = encode_frame_parts(m)
    assert copied_bytes() - c0 == len(m.encode_payload()) \
        == sum(map(len, parts[1:-1]))


# -- (d) a degraded read, traced -------------------------------------------

@pytest.fixture(scope="module")
def degraded(tmp_path_factory):
    """7 OSDs, a k4m2 pool on bluestore (RAM mode), one 4 MiB object,
    the OSD of its shard 1 down: the object as written, and every
    section of one read of it under a profiler session."""
    import jax
    sys.path.insert(0, os.path.join(ROOT, "benchmark"))
    from harness import spans
    from ceph_tpu.cluster import Cluster, test_config
    conf = test_config(osd_objectstore="bluestore",
                       osd_pool_erasure_code_stripe_unit=BLOCK,
                       mon_osd_down_out_interval=600.0)
    payload = random.Random(3800).randbytes(4 * MiB)
    with Cluster(n_osds=7, conf=conf) as cl:
        for i in range(7):
            cl.wait_for_osd_up(i, 30)
        cl.create_ec_profile("k4m2", plugin="jerasure",
                             technique="reed_sol_van", k="4", m="2")
        cl.create_pool("ecpool", "erasure", erasure_code_profile="k4m2")
        rad = cl.rados(timeout=30)
        rad.objecter.op_timeout = 60.0
        io = rad.open_ioctx("ecpool")
        cl.wait_for_clean(60)
        io.write_full("big", payload)
        osdmap = rad.objecter.osdmap
        _, _, acting, _ = osdmap.pg_to_up_acting_osds(
            osdmap.object_locator_to_pg("big", io.pool_id))
        victim = acting[1]
        cl.kill_osd(victim)
        cl.wait_for_osd_down(victim, 30)
        rad.wait_for_epoch(cl.mon.osdmap.epoch, 10)
        log_dir = str(tmp_path_factory.mktemp("trace"))
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 2
        jax.profiler.start_trace(log_dir, profiler_options=opts)
        try:
            got = io.read("big", length=4 * MiB)
        finally:
            jax.profiler.stop_trace()
    # each section with the names of those it nests under
    seen = []
    for evs in spans.load(log_dir)["lines"]:
        stack = []
        for name, start, dur, meta in sorted(evs, key=lambda e: e[1]):
            while stack and stack[-1][0] <= start:
                stack.pop()
            seen.append((name, [n for _, n in stack], meta))
            stack.append((start + dur, name))
    return {"payload": payload, "got": got, "seen": seen}


def _copied(degraded, name, under=None, **match):
    rows = [meta for n, anc, meta in degraded["seen"] if n == name
            and (under is None or under in anc)
            and all(meta.get(k) == v for k, v in match.items())]
    assert rows, f"no {name} section under {under} matching {match}"
    return [int(meta["copied"]) for meta in rows]


def test_the_degraded_read_is_bit_exact(degraded):
    assert degraded["got"] == degraded["payload"]


def test_no_sub_read_copies_after_the_gather(degraded):
    store = _copied(degraded, "store.read", under="ec.sub_read")
    crc = _copied(degraded, "crc.host", under="ec.sub_read")
    # three of the four sub-reads are remote: their replies go out
    sent = _copied(degraded, "msgr.encode", type="MOSDECSubOpReadReply")
    # (a resent op under load reads again: at least one read's worth)
    assert len(store) >= 4 and len(crc) >= 4 and len(sent) >= 3
    assert set(store) == set(crc) == set(sent) == {0}
