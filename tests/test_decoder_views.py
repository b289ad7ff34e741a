"""``Decoder`` alone: the same values over ``bytes``, a ``bytearray``
and a read-only ``memoryview``; ``buffer()`` mirrors ``Encoder``'s
``ZC_MIN`` rule (``bytes`` under it, a read-only view of the decoder's
buffer at or above it); and a ``Transaction`` decoded from a view
round-trips its fragments (ISSUE 35).
"""
import numpy as np
import pytest

from ceph_tpu.store.objectstore import GHObject, Transaction
from ceph_tpu.utils import encoding
from ceph_tpu.utils.encoding import ZC_MIN, DecodeError, Decoder, Encoder

BACKINGS = {
    "bytes": bytes,
    "bytearray": bytearray,
    "view": lambda b: memoryview(bytearray(b)).toreadonly(),
}

# (Encoder call, Decoder method, value) for every field method
FIELDS = [
    ("u8", "u8", 0xAB), ("u16", "u16", 0xBEEF), ("u32", "u32", 0xDEADBEEF),
    ("u64", "u64", 2 ** 64 - 2), ("i32", "i32", -7), ("i64", "i64", -2 ** 62),
    ("f64", "f64", 2.5), ("bool", "bool", True), ("bytes", "bytes", b"abc"),
    ("bytes", "bytes", b""), ("bytes", "buffer", b"xy"),
    ("bytes", "bytes", b"L" * (ZC_MIN + 9)),
    ("str", "str", "héllo"), ("str_list", "str_list", ["a", "", "c"]),
    ("i64_list", "i64_list", [1, -2, 3]),
    ("str_bytes_map", "str_bytes_map", {"k": b"v", "z": b""}),
    ("str_str_map", "str_str_map", {"a": "b"}),
]


def _encoded():
    e = Encoder()
    for enc, _, value in FIELDS:
        getattr(e, enc)(value)
    e.struct(3, 1, Encoder().u32(11).str("in"))
    e.u8(0x5A)
    return e.build()


@pytest.mark.parametrize("backing", BACKINGS)
def test_every_method_over_every_backing(backing):
    d = Decoder(BACKINGS[backing](_encoded()))
    for _, dec, value in FIELDS:
        got = getattr(d, dec)()
        assert got == value, dec
        # nothing a field method returns is a view, whatever it lay in
        assert type(got) is type(value), (dec, type(got))
    v, sub = d.struct(3)
    assert v == 3 and sub.u32() == 11 and sub.str() == "in"
    assert sub.remaining() == 0
    assert d.u8() == 0x5A and d.remaining() == 0
    with pytest.raises(DecodeError):
        d.u8()


@pytest.mark.parametrize("backing", BACKINGS)
@pytest.mark.parametrize("size", (0, 1, ZC_MIN - 1, ZC_MIN, ZC_MIN + 1,
                                  1 << 16))
def test_buffer_follows_zc_min(backing, size):
    blob = bytes(np.random.default_rng(size).integers(
        0, 256, size, dtype=np.uint8))
    raw = BACKINGS[backing](Encoder().bytes(blob).str("tail").build())
    d = Decoder(raw)
    got = d.buffer()
    assert got == blob
    if size < ZC_MIN:
        assert type(got) is bytes
    else:
        assert isinstance(got, memoryview) and got.readonly
        want_obj = raw.obj if isinstance(raw, memoryview) else raw
        assert got.obj is want_obj
        with pytest.raises(TypeError):
            got[0] = 1
    # the cursor moved past it either way
    assert d.str() == "tail" and d.remaining() == 0


@pytest.mark.parametrize("method", ("bytes", "buffer", "str", "u32",
                                    "u64", "f64"))
def test_truncated_buffer_raises(method):
    raw = Encoder().bytes(b"q" * 5000).build()
    # inside the length (every method), inside the payload (those
    # that read one)
    cuts = (2, 4 + 100) if method in ("bytes", "buffer", "str") else (2,)
    for cut in cuts:
        with pytest.raises(DecodeError):
            getattr(Decoder(memoryview(raw[:cut])), method)()
    # a sub-decoder is bounded by its struct, not by the buffer
    body = Encoder().u8(1)
    _, sub = Decoder(Encoder().struct(1, 1, body).u64(7).build()).struct(1)
    sub.u8()
    with pytest.raises(DecodeError):
        getattr(sub, method)()


def test_copied_count_is_what_bytes_copied():
    raw = memoryview(Encoder().bytes(b"a" * 100).bytes(b"b" * 5000)
                     .bytes(b"c" * 5000).str("dd").u64(1).build())
    d = Decoder(raw)
    c0 = encoding.copied_bytes()
    d.bytes()
    assert encoding.copied_bytes() - c0 == 100
    d.bytes()
    assert encoding.copied_bytes() - c0 == 5100
    assert isinstance(d.buffer(), memoryview)     # a view copies nothing
    assert encoding.copied_bytes() - c0 == 5100
    d.str(), d.u64()
    assert encoding.copied_bytes() - c0 == 5102


def _txn():
    rng = np.random.default_rng(35)
    big = rng.integers(0, 256, 512 << 10, dtype=np.uint8)
    t = Transaction()
    o = GHObject("obj", 2)
    t.create_collection("1.0s2")
    t.touch("1.0s2", o)
    t.write("1.0s2", o, 0, memoryview(big))
    t.write("1.0s2", o, 1 << 20, b"small")
    t.xor_write("1.0s2", o, 4096, bytes(big[:ZC_MIN]))
    t.zero("1.0s2", o, 10, 20)
    t.truncate("1.0s2", o, 1 << 21)
    t.setattr("1.0s2", o, "hinfo", b"H" * 3000)
    t.omap_setkeys("1.0s2", o, {"k": b"V" * 3000})
    t.omap_setheader("1.0s2", o, b"hdr")
    t.clone("1.0s2", o, GHObject("obj2", 2))
    return t


def _plain(op):
    return tuple(bytes(x) if isinstance(x, (memoryview, np.ndarray))
                 else x for x in op)


@pytest.mark.parametrize("backing", BACKINGS)
def test_transaction_decodes_from_a_view(backing):
    t = _txn()
    parts = t.encode_parts()
    joined = b"".join(parts)
    assert joined == t.encode()
    got = Transaction.decode(BACKINGS[backing](joined))
    assert [_plain(op) for op in got.ops] == [_plain(op) for op in t.ops]
    by_name = {}
    for op in got.ops:
        by_name.setdefault(op[0], []).append(op)
    # a write's payload is a view from ZC_MIN up, bytes under it;
    # attrs, omap values and headers are bytes however large
    big, small = by_name["write"]
    assert isinstance(big[4], memoryview) and big[4].readonly
    assert type(small[4]) is bytes
    assert isinstance(by_name["xor_write"][0][4], memoryview)
    assert type(by_name["setattr"][0][4]) is bytes
    assert type(by_name["omap_setkeys"][0][3]["k"]) is bytes
    assert type(by_name["omap_setheader"][0][3]) is bytes
    # and it encodes again to the same bytes, views riding by reference
    assert b"".join(got.encode_parts()) == joined
    assert any(p is big[4] or (isinstance(p, memoryview) and
                               p.obj is big[4].obj)
               for p in got.encode_parts())


def test_transaction_decodes_from_fragments():
    t = _txn()
    got = Transaction.decode(t.encode_parts())
    assert [_plain(op) for op in got.ops] == [_plain(op) for op in t.ops]
