"""Object-store suite run over every backend.

Mirrors the reference's store_test.cc approach (reference
src/test/objectstore/store_test.cc): one suite, parametrized over
MemStore and FileStore; plus FileStore-only persistence/journal cases
and LogDB replay/compaction cases (reference FileJournal semantics).
"""
import os
import threading

import pytest

from ceph_tpu.store import (BlockStore, BlueStore, FileStore,
                            GHObject, LogDB, MemStore, Transaction,
                            WriteBatch)

C = "1.0s0"


@pytest.fixture(params=["mem", "file", "block", "bluestore"])
def store(request, tmp_path):
    if request.param == "mem":
        s = MemStore()
    elif request.param == "block":
        s = BlockStore(str(tmp_path / "store"))
    elif request.param == "bluestore":
        s = BlueStore(str(tmp_path / "store"))
    else:
        s = FileStore(str(tmp_path / "store"))
    s.mkfs()
    s.mount()
    t = Transaction().create_collection(C)
    s.queue_transactions([t])
    yield s
    s.umount()


def obj(name="foo", shard=0):
    return GHObject(name, shard)


def test_write_read_roundtrip(store):
    t = Transaction().write(C, obj(), 0, b"hello world")
    store.queue_transactions([t])
    assert store.read(C, obj()) == b"hello world"
    assert store.read(C, obj(), 6, 5) == b"world"
    assert store.stat(C, obj()).size == 11


def test_write_at_offset_pads_with_zeros(store):
    store.queue_transactions([Transaction().write(C, obj(), 4, b"data")])
    assert store.read(C, obj()) == b"\x00\x00\x00\x00data"


def test_overwrite_extends(store):
    store.queue_transactions([Transaction().write(C, obj(), 0, b"aaaa")])
    store.queue_transactions([Transaction().write(C, obj(), 2, b"bbbb")])
    assert store.read(C, obj()) == b"aabbbb"


def test_zero_and_truncate(store):
    store.queue_transactions([Transaction().write(C, obj(), 0, b"x" * 8)])
    store.queue_transactions([Transaction().zero(C, obj(), 2, 3)])
    assert store.read(C, obj()) == b"xx\x00\x00\x00xxx"
    store.queue_transactions([Transaction().truncate(C, obj(), 4)])
    assert store.read(C, obj()) == b"xx\x00\x00"
    store.queue_transactions([Transaction().truncate(C, obj(), 6)])
    assert store.read(C, obj()) == b"xx\x00\x00\x00\x00"


def test_touch_remove_exists(store):
    assert not store.exists(C, obj())
    store.queue_transactions([Transaction().touch(C, obj())])
    assert store.exists(C, obj())
    assert store.stat(C, obj()).size == 0
    store.queue_transactions([Transaction().remove(C, obj())])
    assert not store.exists(C, obj())
    with pytest.raises(FileNotFoundError):
        store.read(C, obj())


def test_missing_object_raises(store):
    with pytest.raises(FileNotFoundError):
        store.read(C, obj("nope"))
    with pytest.raises(FileNotFoundError):
        store.stat(C, obj("nope"))


def test_missing_collection_raises(store):
    with pytest.raises(FileNotFoundError):
        store.read("9.9s9", obj())


def test_xattrs(store):
    t = Transaction().setattrs(C, obj(), {"hinfo": b"\x01\x02", "v": b"3"})
    store.queue_transactions([t])
    assert store.getattr(C, obj(), "hinfo") == b"\x01\x02"
    assert store.getattrs(C, obj()) == {"hinfo": b"\x01\x02", "v": b"3"}
    store.queue_transactions([Transaction().rmattr(C, obj(), "v")])
    assert store.getattrs(C, obj()) == {"hinfo": b"\x01\x02"}
    with pytest.raises(KeyError):
        store.getattr(C, obj(), "v")


def test_omap(store):
    t = Transaction().omap_setkeys(
        C, obj(), {"k1": b"v1", "k2": b"v2", "k3": b"v3"})
    t.omap_setheader(C, obj(), b"HDR")
    store.queue_transactions([t])
    assert store.omap_get(C, obj()) == {
        "k1": b"v1", "k2": b"v2", "k3": b"v3"}
    assert store.omap_get_header(C, obj()) == b"HDR"
    assert store.omap_get_keys(C, obj()) == ["k1", "k2", "k3"]
    assert store.omap_get_keys(C, obj(), start_after="k1") == ["k2", "k3"]
    assert store.omap_get_keys(C, obj(), max_return=2) == ["k1", "k2"]
    store.queue_transactions([Transaction().omap_rmkeys(C, obj(), ["k2"])])
    assert store.omap_get(C, obj()) == {"k1": b"v1", "k3": b"v3"}
    store.queue_transactions([Transaction().omap_clear(C, obj())])
    assert store.omap_get(C, obj()) == {}
    assert store.omap_get_header(C, obj()) == b"HDR"


def test_clone_is_deep(store):
    t = Transaction().write(C, obj(), 0, b"original")
    t.setattr(C, obj(), "a", b"1")
    t.omap_setkeys(C, obj(), {"k": b"v"})
    store.queue_transactions([t])
    dst = obj("foo-clone")
    store.queue_transactions([Transaction().clone(C, obj(), dst)])
    assert store.read(C, dst) == b"original"
    assert store.getattrs(C, dst) == {"a": b"1"}
    assert store.omap_get(C, dst) == {"k": b"v"}
    store.queue_transactions([Transaction().write(C, dst, 0, b"CLONED!!")])
    assert store.read(C, obj()) == b"original"


def test_coll_move_rename(store):
    C2 = "1.1s0"
    store.queue_transactions([Transaction().create_collection(C2)])
    t = Transaction().write(C, obj(), 0, b"payload")
    t.setattr(C, obj(), "a", b"1")
    t.omap_setkeys(C, obj(), {"k": b"v"})
    store.queue_transactions([t])
    dst = obj("foo", shard=1)
    store.queue_transactions(
        [Transaction().collection_move_rename(C, obj(), C2, dst)])
    assert not store.exists(C, obj())
    assert store.read(C2, dst) == b"payload"
    assert store.getattrs(C2, dst) == {"a": b"1"}
    assert store.omap_get(C2, dst) == {"k": b"v"}


def test_collections(store):
    assert store.collection_exists(C)
    assert C in store.list_collections()
    C2 = "2.0s-1"
    store.queue_transactions([Transaction().create_collection(C2)])
    store.queue_transactions([Transaction().touch(C2, obj("a"))])
    store.queue_transactions([Transaction().remove_collection(C2)])
    assert not store.collection_exists(C2)


def test_collection_list_sorted(store):
    t = Transaction()
    for name in ("zeta", "alpha", "mu"):
        t.touch(C, obj(name))
    store.queue_transactions([t])
    names = [o.oid for o in store.collection_list(C)]
    assert names == ["alpha", "mu", "zeta"]
    assert [o.oid for o in store.collection_list(C, start_after="alpha")] \
        == ["mu", "zeta"]
    assert len(store.collection_list(C, max_return=2)) == 2


def test_commit_callbacks(store):
    applied = threading.Event()
    committed = threading.Event()
    aggregate = threading.Event()
    t = Transaction().write(C, obj(), 0, b"x")
    t.register_on_applied(applied.set)
    t.register_on_commit(committed.set)
    store.queue_transactions([t], on_commit=aggregate.set)
    assert committed.wait(5)      # commit via finisher thread
    assert aggregate.wait(5)
    # synchronous backends deliver on_applied inline; deferred-apply
    # backends (BlueStore) deliver it from the applier — flush()
    # bounds both
    store.flush()
    assert applied.wait(5)


def test_transaction_atomic_ordering(store):
    # ops within one transaction apply in order (write then truncate)
    t = Transaction().write(C, obj(), 0, b"abcdef").truncate(C, obj(), 3)
    store.queue_transactions([t])
    assert store.read(C, obj()) == b"abc"


def test_transaction_encode_decode_roundtrip():
    t = Transaction()
    t.create_collection(C)
    t.touch(C, obj())
    t.write(C, obj(), 16, b"\xff" * 8)
    t.zero(C, obj(), 0, 4)
    t.truncate(C, obj(), 20)
    t.setattr(C, obj(), "hinfo_key", b"\x00\x01")
    t.rmattr(C, obj(), "old")
    t.omap_setkeys(C, obj(), {"pglog_1": b"entry"})
    t.omap_rmkeys(C, obj(), ["pglog_0"])
    t.omap_setheader(C, obj(), b"hdr")
    t.omap_clear(C, obj("other", 2))
    t.clone(C, obj(), obj("dup", 1))
    t.collection_move_rename(C, obj(), "1.1s1", obj("moved", 1))
    t.remove(C, obj("gone"))
    t.remove_collection("1.2s0")
    rt = Transaction.decode(t.encode())
    assert rt.ops == t.ops


def test_shard_qualified_objects_distinct(store):
    store.queue_transactions([Transaction().write(C, obj("x", 0), 0, b"s0")])
    store.queue_transactions([Transaction().write(C, obj("x", 1), 0, b"s1")])
    assert store.read(C, obj("x", 0)) == b"s0"
    assert store.read(C, obj("x", 1)) == b"s1"


def test_clone_sees_same_transaction_writes(store):
    """clone of an object created earlier in the same transaction."""
    t = Transaction()
    t.touch(C, obj("fresh"))
    t.write(C, obj("fresh"), 0, b"hello")
    t.setattr(C, obj("fresh"), "a", b"1")
    t.clone(C, obj("fresh"), obj("fresh-copy"))
    store.queue_transactions([t])
    assert store.read(C, obj("fresh-copy")) == b"hello"
    assert store.getattrs(C, obj("fresh-copy")) == {"a": b"1"}


def test_move_rename_into_collection_created_same_txn(store):
    t = Transaction()
    t.create_collection("7.0s0")
    t.touch(C, obj("mover"))
    t.collection_move_rename(C, obj("mover"), "7.0s0", obj("mover", 3))
    store.queue_transactions([t])
    assert store.exists("7.0s0", obj("mover", 3))
    assert not store.exists(C, obj("mover"))


def test_invalid_transaction_rejected_whole(store):
    """An invalid op anywhere rejects the transaction before any
    mutation (atomicity contract)."""
    t = Transaction()
    t.write(C, obj("partial"), 0, b"data")
    t.clone(C, obj("never-existed"), obj("dup"))
    with pytest.raises(FileNotFoundError):
        store.queue_transactions([t])
    assert not store.exists(C, obj("partial"))
    assert not store.exists(C, obj("dup"))


def test_invalid_txn_leaves_no_journal(tmp_path):
    path = str(tmp_path / "fs")
    s = FileStore(path)
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    with pytest.raises(FileNotFoundError):
        s.queue_transactions(
            [Transaction().write("no.such.coll", obj(), 0, b"x")])
    assert list(s._db.get_prefix("J/")) == []
    # the store still works and a remount sees nothing of the failure
    s.queue_transactions([Transaction().write(C, obj(), 0, b"v2")])
    s.umount()
    s2 = FileStore(path)
    s2.mount()
    assert s2.read(C, obj()) == b"v2"
    s2.umount()


def test_non_ascii_keys_cleared(store):
    """omap_clear / remove must cover keys above U+007F."""
    t = Transaction().omap_setkeys(C, obj(), {"ékey": b"v", "日本": b"w"})
    t.setattr(C, obj(), "áttr", b"x")
    store.queue_transactions([t])
    assert store.omap_get(C, obj()) == {"ékey": b"v", "日本": b"w"}
    store.queue_transactions([Transaction().omap_clear(C, obj())])
    assert store.omap_get(C, obj()) == {}
    store.queue_transactions([Transaction().remove(C, obj())])
    store.queue_transactions([Transaction().touch(C, obj())])
    assert store.getattrs(C, obj()) == {}
    assert store.omap_get(C, obj()) == {}


def test_clone_and_move_replace_destination_wholesale(store):
    """An existing destination's metadata/data must not leak through
    clone or coll_move_rename."""
    t = Transaction()
    t.write(C, obj("dst"), 0, b"OLDDATA")
    t.setattr(C, obj("dst"), "stale", b"S")
    t.omap_setkeys(C, obj("dst"), {"stalek": b"sv"})
    t.omap_setheader(C, obj("dst"), b"OLDHDR")
    t.touch(C, obj("src"))             # data-less, metadata-less source
    store.queue_transactions([t])
    store.queue_transactions([Transaction().clone(C, obj("src"),
                                                  obj("dst"))])
    assert store.read(C, obj("dst")) == b""
    assert store.getattrs(C, obj("dst")) == {}
    assert store.omap_get(C, obj("dst")) == {}
    assert store.omap_get_header(C, obj("dst")) == b""

    t2 = Transaction()
    t2.write(C, obj("dst2"), 0, b"OLDDATA")
    t2.omap_setheader(C, obj("dst2"), b"OLDHDR")
    t2.touch(C, obj("src2"))
    store.queue_transactions([t2])
    store.queue_transactions([Transaction().collection_move_rename(
        C, obj("src2"), C, obj("dst2"))])
    assert store.read(C, obj("dst2")) == b""
    assert store.omap_get_header(C, obj("dst2")) == b""
    assert not store.exists(C, obj("src2"))


def test_logdb_empty_file_is_fresh_log(tmp_path):
    """Crash between creation and magic flush leaves a 0-byte log; it
    must open as empty, not fail forever."""
    path = str(tmp_path / "kv.log")
    open(path, "wb").close()
    db = LogDB(path)
    db.open()
    db.submit(WriteBatch().set("k", b"v"))
    db.close()
    db2 = LogDB(path)
    db2.open()
    assert db2.get("k") == b"v"
    db2.close()


# -- FileStore persistence ------------------------------------------------

def test_filestore_survives_remount(tmp_path):
    path = str(tmp_path / "fs")
    s = FileStore(path)
    s.mkfs()
    s.mount()
    t = Transaction().create_collection(C)
    t.write(C, obj(), 0, b"durable")
    t.setattr(C, obj(), "a", b"1")
    t.omap_setkeys(C, obj(), {"k": b"v"})
    s.queue_transactions([t])
    s.umount()

    s2 = FileStore(path)
    s2.mount()
    assert s2.read(C, obj()) == b"durable"
    assert s2.getattr(C, obj(), "a") == b"1"
    assert s2.omap_get(C, obj()) == {"k": b"v"}
    assert s2.list_collections() == [C]
    s2.umount()


def test_filestore_replays_pending_journal(tmp_path):
    """A journaled-but-unapplied transaction applies on mount (crash
    between WAL append and apply)."""
    path = str(tmp_path / "fs")
    s = FileStore(path)
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    # simulate the crash: journal a txn directly without applying it
    t = Transaction().write(C, obj(), 0, b"replayed")
    s._db.submit(WriteBatch().set("J/0000000000000099", t.encode()),
                 sync=True)
    s.umount()

    s2 = FileStore(path)
    s2.mount()
    assert s2.read(C, obj()) == b"replayed"
    assert list(s2._db.get_prefix("J/")) == []   # journal drained
    s2.umount()


def test_filestore_mount_requires_mkfs(tmp_path):
    with pytest.raises(IOError):
        FileStore(str(tmp_path / "missing")).mount()


# -- LogDB ----------------------------------------------------------------

def test_logdb_replay(tmp_path):
    path = str(tmp_path / "kv.log")
    db = LogDB(path)
    db.open()
    db.submit(WriteBatch().set("a", b"1").set("b", b"2"))
    db.submit(WriteBatch().rm("a").set("c", b"3"))
    db.close()
    db2 = LogDB(path)
    db2.open()
    assert db2.get("a") is None
    assert db2.get("b") == b"2"
    assert db2.get("c") == b"3"
    db2.close()


def test_logdb_discards_torn_tail(tmp_path):
    path = str(tmp_path / "kv.log")
    db = LogDB(path)
    db.open()
    db.submit(WriteBatch().set("good", b"1"))
    db.close()
    with open(path, "ab") as fh:        # simulate a torn write
        fh.write(b"\xff\xff\xff\x7f partial record")
    db2 = LogDB(path)
    db2.open()
    assert db2.get("good") == b"1"
    db2.submit(WriteBatch().set("after", b"2"))
    db2.close()
    db3 = LogDB(path)
    db3.open()
    assert db3.get("after") == b"2"
    db3.close()


def test_logdb_compaction_preserves_data(tmp_path):
    path = str(tmp_path / "kv.log")
    db = LogDB(path, compact_factor=2)
    db.open()
    for i in range(200):                # churn one key to bloat the log
        db.submit(WriteBatch().set("hot", bytes(64)).set(f"k{i}", b"v"))
    size_after = os.path.getsize(path)
    live = sum(len(k) + 64 + 13 for k in ["hot"]) + 200 * 20
    assert size_after < live * 20       # compaction actually ran
    db.close()
    db2 = LogDB(path)
    db2.open()
    assert db2.get("hot") == bytes(64)
    assert all(db2.get(f"k{i}") == b"v" for i in range(200))
    db2.close()


def test_logdb_rm_range(tmp_path):
    db = LogDB(str(tmp_path / "kv.log"))
    db.open()
    db.submit(WriteBatch().set("p/a", b"1").set("p/b", b"2")
              .set("q/a", b"3"))
    db.submit(WriteBatch().rm_range("p/", "p/\x7f"))
    assert db.get_prefix("p/") == {}
    assert db.get("q/a") == b"3"
    db.close()


# -- BlockStore (reference os/bluestore) ----------------------------------


def test_blockstore_survives_remount(tmp_path):
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mkfs()
    s.mount()
    t = Transaction().create_collection(C)
    t.write(C, obj("p"), 0, b"block-data" * 1000)
    t.setattr(C, obj("p"), "a1", b"v1")
    t.omap_setkeys(C, obj("p"), {"k": b"v"})
    s.queue_transactions([t])
    s.umount()
    s2 = BlockStore(path)
    s2.mount()
    assert s2.read(C, obj("p")) == b"block-data" * 1000
    assert s2.getattr(C, obj("p"), "a1") == b"v1"
    assert s2.omap_get(C, obj("p"))["k"] == b"v"
    s2.umount()


def test_blockstore_cow_frees_blocks(tmp_path):
    """Overwrites COW into new blocks and release the old ones; delete
    returns everything (reference allocator accounting/statfs)."""
    s = BlockStore(str(tmp_path / "bs"))
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    payload = bytes(range(256)) * 64          # 16 KiB = 4 blocks
    s.queue_transactions([Transaction().write(C, obj("o"), 0, payload)])
    used_after_write = s.usage()["blocks_used"]
    assert used_after_write >= 4
    # full overwrite: usage stays flat (old blocks freed)
    s.queue_transactions([Transaction().write(C, obj("o"), 0, payload)])
    assert s.usage()["blocks_used"] == used_after_write
    assert s.read(C, obj("o")) == payload
    # partial overwrite mid-block: RMW preserved
    s.queue_transactions([Transaction().write(C, obj("o"), 100,
                                              b"PATCH")])
    want = bytearray(payload)
    want[100:105] = b"PATCH"
    assert s.read(C, obj("o")) == bytes(want)
    assert s.usage()["blocks_used"] == used_after_write
    # delete releases all data blocks
    s.queue_transactions([Transaction().remove(C, obj("o"))])
    assert s.usage()["blocks_used"] == 0
    s.umount()


def test_blockstore_replays_pending_journal(tmp_path):
    """Crash between WAL and apply: the journaled txn applies on the
    next mount (reference deferred-write replay)."""
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    t = Transaction().write(C, obj("j"), 0, b"journaled!")
    enc = t.encode()
    s._db.submit(WriteBatch().set("J/9999999999999999", enc),
                 sync=True)
    s.umount()                           # "crash" before apply
    s2 = BlockStore(path)
    s2.mount()                           # replay
    assert s2.read(C, obj("j")) == b"journaled!"
    assert list(s2._db.iterate("J/")) == []
    s2.umount()


def test_blockstore_sparse_and_truncate(tmp_path):
    s = BlockStore(str(tmp_path / "bs"))
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    # sparse write far into the object: holes read as zeros
    s.queue_transactions([Transaction().write(C, obj("sp"), 20000,
                                              b"tail")])
    data = s.read(C, obj("sp"))
    assert data[:20000] == b"\x00" * 20000 and data[20000:] == b"tail"
    # truncate shrinks + frees whole blocks past the end
    used = s.usage()["blocks_used"]
    s.queue_transactions([Transaction().truncate(C, obj("sp"), 100)])
    assert s.stat(C, obj("sp")).size == 100
    assert s.usage()["blocks_used"] <= used
    s.umount()


def test_blockstore_grow_truncate_and_rmcoll(tmp_path):
    """Review regressions: grow-truncate must zero-pad like the other
    stores; removing a collection must purge objects AND free blocks
    (no resurrection on recreate)."""
    s = BlockStore(str(tmp_path / "bs"))
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    s.queue_transactions([Transaction().write(C, obj("g"), 0, b"abc")])
    s.queue_transactions([Transaction().truncate(C, obj("g"), 10000)])
    data = s.read(C, obj("g"))
    assert len(data) == 10000
    assert data[:3] == b"abc" and data[3:] == b"\x00" * 9997
    # zero punches holes without allocating
    used0 = s.usage()["blocks_used"]
    s.queue_transactions([Transaction().zero(C, obj("g"), 0, 8192)])
    assert s.read(C, obj("g"))[:8192] == b"\x00" * 8192
    assert s.usage()["blocks_used"] <= used0
    # rmcoll purge + allocator reclaim
    s.queue_transactions([Transaction().remove_collection(C)])
    assert s.usage()["blocks_used"] == 0
    s.queue_transactions([Transaction().create_collection(C)])
    assert not s.exists(C, obj("g"))
    with pytest.raises(FileNotFoundError):
        s.read(C, obj("g"))
    s.umount()


def test_blockstore_csum_detects_bitrot(tmp_path):
    """Every read verifies the per-block CRC32C (reference BlueStore
    _verify_csum, BlueStore.cc:10425): flipping bits in the raw block
    device surfaces as EIO, not silent corruption (VERDICT r4 Next
    #9)."""
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    payload = bytes(range(256)) * 64
    s.queue_transactions([Transaction().write(C, obj("rot"), 0,
                                              payload)])
    assert s.read(C, obj("rot")) == payload
    # find the object's first physical block and flip a byte under
    # the store's feet
    ext = s._load_extents(C, obj("rot"))
    phys = next(p for p in ext.blocks if p >= 0)
    with open(os.path.join(path, "block.dev"), "r+b") as f:
        f.seek(phys * 4096 + 17)
        b = f.read(1)
        f.seek(phys * 4096 + 17)
        f.write(bytes([b[0] ^ 0xFF]))
    with pytest.raises(OSError):
        s.read(C, obj("rot"))
    assert s.usage()["csum_failures"] >= 1
    s.umount()


def test_blockstore_compression_roundtrip(tmp_path):
    """Inline compression (reference bluestore_compression_algorithm):
    a large compressible write stores as a compressed segment (fewer
    blocks than logical), reads back bit-exact — including after a
    partial overwrite that re-materializes the segment — and the
    ratio shows in usage()."""
    s = BlockStore(str(tmp_path / "bs"), compression="zlib")
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    payload = b"compress me! " * 5000          # 65 KB, compressible
    s.queue_transactions([Transaction().write(C, obj("z"), 0,
                                              payload)])
    u = s.usage()
    logical_blocks = (len(payload) + 4095) // 4096
    assert u["blocks_used"] < logical_blocks
    assert u["compress_stored_bytes"] < u["compress_logical_bytes"]
    assert s.read(C, obj("z")) == payload
    # partial overwrite inside the compressed span: the segment's
    # survivors re-home as raw blocks, content stays exact
    patch_at = 10000
    s.queue_transactions([Transaction().write(C, obj("z"), patch_at,
                                              b"PATCH")])
    want = bytearray(payload)
    want[patch_at:patch_at + 5] = b"PATCH"
    assert s.read(C, obj("z")) == bytes(want)
    # truncate into the (re-homed or remaining) span
    s.queue_transactions([Transaction().truncate(C, obj("z"), 9000)])
    assert s.read(C, obj("z")) == bytes(want)[:9000]
    # clone of a compressed object is deep and exact
    s.queue_transactions([Transaction().write(C, obj("z2"), 0,
                                              payload)])
    s.queue_transactions([Transaction().clone(C, obj("z2"),
                                              obj("z3"))])
    assert s.read(C, obj("z3")) == payload
    # remove releases the segment's physical blocks too
    for o in ("z", "z2", "z3"):
        s.queue_transactions([Transaction().remove(C, obj(o))])
    assert s.usage()["blocks_used"] == 0
    s.umount()


def test_blockstore_compressed_survives_remount_and_detects_rot(
        tmp_path):
    """Segments persist across remount (decompression follows the
    segment's recorded algorithm, not the mount option) and a
    corrupted compressed block still surfaces as EIO through the
    per-logical-block CRC."""
    path = str(tmp_path / "bs")
    s = BlockStore(path, compression="zlib")
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    payload = b"persistent segment " * 4000
    s.queue_transactions([Transaction().write(C, obj("ps"), 0,
                                              payload)])
    s.umount()
    s2 = BlockStore(path)                      # compression OFF
    s2.mount()
    assert s2.read(C, obj("ps")) == payload
    ext = s2._load_extents(C, obj("ps"))
    assert ext.segs, "expected a compressed segment"
    phys = next(iter(ext.segs.values()))["phys"][0]
    with open(os.path.join(path, "block.dev"), "r+b") as f:
        f.seek(phys * 4096 + 5)
        b = f.read(1)
        f.seek(phys * 4096 + 5)
        f.write(bytes([b[0] ^ 0x55]))
    with pytest.raises(OSError):
        s2.read(C, obj("ps"))
    assert s2.usage()["csum_failures"] >= 1
    s2.umount()


def test_blockstore_overwrite_of_rotten_segment_succeeds(tmp_path):
    """A full overwrite needs none of the old bytes, so a CORRUPT
    compressed segment must not brick the write that would replace it
    (flatten skips decompression when every member is dropped);
    reads of the new data then verify clean."""
    path = str(tmp_path / "bs")
    s = BlockStore(path, compression="zlib")
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    payload = b"rotting segment " * 4000
    s.queue_transactions([Transaction().write(C, obj("rw"), 0,
                                              payload)])
    ext = s._load_extents(C, obj("rw"))
    phys = next(iter(ext.segs.values()))["phys"][0]
    with open(os.path.join(path, "block.dev"), "r+b") as f:
        f.seek(phys * 4096 + 3)
        b = f.read(1)
        f.seek(phys * 4096 + 3)
        f.write(bytes([b[0] ^ 0x3C]))
    with pytest.raises(OSError):
        s.read(C, obj("rw"))
    # full-cover overwrite (writefull shape: new size >= old): every
    # old segment member is replaced, so no decompression is needed
    fresh = b"fresh bytes " * 6000
    assert len(fresh) >= len(payload)
    t = Transaction().write(C, obj("rw"), 0, fresh)
    t.truncate(C, obj("rw"), len(fresh))
    s.queue_transactions([t])            # must not raise
    assert s.read(C, obj("rw")) == fresh
    s.umount()


def test_blockstore_rmw_over_rot_raises_and_store_survives(tmp_path):
    """A partial overwrite whose RMW base block is rotten must fail
    with EIO — NOT merge over the garbage and stamp a fresh CRC
    (which would launder the corruption as valid data) — and the
    failed, already-journaled transaction must not poison the WAL:
    the store stays mountable and later writes work."""
    path = str(tmp_path / "bs")
    s = BlockStore(path)
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    payload = bytes(range(256)) * 64
    s.queue_transactions([Transaction().write(C, obj("rm"), 0,
                                              payload)])
    ext = s._load_extents(C, obj("rm"))
    phys = ext.blocks[0]
    with open(os.path.join(path, "block.dev"), "r+b") as f:
        f.seek(phys * 4096 + 200)
        b = f.read(1)
        f.seek(phys * 4096 + 200)
        f.write(bytes([b[0] ^ 0x11]))
    with pytest.raises(OSError):
        s.queue_transactions([Transaction().write(C, obj("rm"), 0,
                                                  b"tiny")])
    # the rot is still detected (not laundered under a fresh CRC)
    with pytest.raises(OSError):
        s.read(C, obj("rm"))
    s.umount()
    # the failed txn's WAL entry must not brick the next mount
    s2 = BlockStore(path)
    s2.mount()
    with pytest.raises(OSError):
        s2.read(C, obj("rm"))
    # and the store still takes writes (full overwrite needs no base)
    s2.queue_transactions([Transaction().write(C, obj("other"), 0,
                                               b"fine")])
    assert s2.read(C, obj("other")) == b"fine"
    s2.umount()


def test_blockstore_live_apply_rollback_covers_all_exceptions(
        tmp_path):
    """Regression (PR 5 fix, PR 6 test): a LIVE transaction that
    fails with a non-OSError mid-apply (here: a malformed write
    payload raising TypeError after an earlier write op already
    allocated blocks) must roll those allocations back — only the
    replay path may swallow OSErrors, and no path may leak bitmap
    blocks from a transaction whose batch never commits.  The
    malformed op passes check_ops (which validates names and
    existence, not payloads), so the failure lands mid-apply."""
    s = BlockStore(str(tmp_path / "bsrb"))
    s.mkfs()
    s.mount()
    try:
        s.queue_transactions([Transaction().create_collection(C)])
        s.queue_transactions(
            [Transaction().write(C, obj("keep"), 0, b"k" * 4096)])
        used_before = s._alloc.used()
        t = Transaction().write(C, obj("doomed"), 0, b"d" * 8192)
        t.ops.append(("write", C, obj("doomed"), 0, None))
        with pytest.raises(TypeError):
            s.queue_transactions([t])
        assert s._alloc.used() == used_before, \
            "failed live apply leaked allocator blocks"
        # the store stays consistent and writable after the rollback
        assert not s.exists(C, obj("doomed"))
        assert s.read(C, obj("keep")) == b"k" * 4096
        s.queue_transactions(
            [Transaction().write(C, obj("after"), 0, b"a" * 4096)])
        assert s.read(C, obj("after")) == b"a" * 4096
    finally:
        s.umount()


# ------------------------------------------------- ranged reads (ISSUE 26)
#
# BlockStore.read gathers only the blocks its range covers and checks
# them in one native call after it has let go of the store's mutex.

BLK = 4096


def _block_family(kind, tmp_path, **kw):
    if kind == "block":
        s = BlockStore(str(tmp_path / "rs"), **kw)
    elif kind == "bluestore-ram":
        s = BlueStore("", start_applier=False, **kw)
    else:
        s = BlueStore(str(tmp_path / "rs"), start_applier=False, **kw)
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    return s


def _flush(s):
    """BlueStore: everything admitted is applied; BlockStore applies
    inline."""
    if hasattr(s, "flush"):
        s.flush()


def _pattern(n, salt=0):
    return bytes((i * 7 + (i >> 8) * 13 + salt) & 0xFF for i in range(n))


def _lay_plain(s):
    want = _pattern(16 * BLK)
    s.queue_transactions([Transaction().write(C, obj("r"), 0, want)])
    return want


def _lay_hole(s):
    """Blocks 4..7 were never written, 9..10 are punched out."""
    head, tail = _pattern(4 * BLK, 1), _pattern(6 * BLK, 2)
    s.queue_transactions([Transaction().write(C, obj("r"), 0, head)])
    s.queue_transactions(
        [Transaction().write(C, obj("r"), 8 * BLK, tail)])
    s.queue_transactions(
        [Transaction().zero(C, obj("r"), 9 * BLK, 2 * BLK)])
    want = bytearray(head + b"\x00" * (4 * BLK) + tail)
    want[9 * BLK:11 * BLK] = b"\x00" * (2 * BLK)
    return bytes(want)


def _lay_ragged_eof(s):
    want = _pattern(5 * BLK + 1234, 3)
    s.queue_transactions([Transaction().write(C, obj("r"), 0, want)])
    s.queue_transactions([Transaction().write(C, obj("r"), 100, b"ab")])
    return want[:100] + b"ab" + want[102:]


def _lay_compressed(s):
    """A compressed segment over blocks 2..13 between raw blocks."""
    body = (b"squeeze me " * 6000)[:12 * BLK]
    want = bytearray(os.urandom(2 * BLK) + body + os.urandom(2 * BLK))
    s.queue_transactions(
        [Transaction().write(C, obj("r"), 0, bytes(want[:2 * BLK]))])
    s.queue_transactions([Transaction().write(C, obj("r"), 2 * BLK, body)])
    s.queue_transactions(
        [Transaction().write(C, obj("r"), 14 * BLK,
                             bytes(want[14 * BLK:]))])
    return bytes(want)


def _lay_pending(s):
    """The last overwrite is admitted and not yet applied when the read
    comes (BlueStore: the read crosses _barrier and steals the apply;
    BlockStore applies inline)."""
    want = bytearray(_pattern(12 * BLK, 4))
    s.queue_transactions([Transaction().write(C, obj("r"), 0,
                                              bytes(want))])
    _flush(s)
    patch = _pattern(3 * BLK + 17, 5)
    s.queue_transactions(
        [Transaction().write(C, obj("r"), 5 * BLK - 9, patch)])
    want[5 * BLK - 9:5 * BLK - 9 + len(patch)] = patch
    return bytes(want)


def _lay_clone_in_txn(s):
    """One transaction overwrites the source and clones it: the clone
    reads blocks that are still in BlueStore's _wbuf."""
    base = bytearray(_pattern(10 * BLK + 77, 6))
    s.queue_transactions([Transaction().write(C, obj("src"), 0,
                                              bytes(base))])
    patch = _pattern(2 * BLK, 7)
    t = Transaction().write(C, obj("src"), 3 * BLK + 5, patch)
    t.clone(C, obj("src"), obj("r"))
    s.queue_transactions([t])
    base[3 * BLK + 5:3 * BLK + 5 + len(patch)] = patch
    return bytes(base)


_LAYOUTS = {"plain": (_lay_plain, {}),
            "hole": (_lay_hole, {}),
            "ragged_eof": (_lay_ragged_eof, {}),
            "compressed": (_lay_compressed, {"compression": "zlib"}),
            "pending": (_lay_pending, {}),
            "clone_in_txn": (_lay_clone_in_txn, {})}
_KINDS = ["block", "bluestore-ram", "bluestore-path"]


@pytest.mark.parametrize("layout", sorted(_LAYOUTS))
@pytest.mark.parametrize("kind", _KINDS)
def test_ranged_read_equals_slice_of_full_read(kind, layout, tmp_path):
    import random
    lay, kw = _LAYOUTS[layout]
    s = _block_family(kind, tmp_path, **kw)
    try:
        want = lay(s)
        size = len(want)
        if layout == "compressed":
            _flush(s)
            assert s._load_extents(C, obj("r")).segs, "no segment"
        if layout == "pending" and kind != "block":
            with s._qcond:
                assert s._applied_seq < s._wal_seq
            assert s.read(C, obj("r"), 5 * BLK, BLK) == \
                want[5 * BLK:6 * BLK]          # crosses the barrier
        assert s.read(C, obj("r")) == want
        assert s.stat(C, obj("r")).size == size
        rng = random.Random(size)
        ranges = [(0, None), (0, size), (0, 0), (7, 0), (BLK, BLK),
                  (BLK - 1, 2), (BLK + 1, 3 * BLK - 2),
                  (3 * BLK, 9 * BLK), (size - 5, 5), (size - 5, 500),
                  (size, 10), (size + BLK, 10), (size + 1, None),
                  (5 * BLK + 100, None), (0, size + 9 * BLK)]
        ranges += [(o, rng.randrange(0, size - o + 2 * BLK))
                   for o in (rng.randrange(0, size) for _ in range(40))]
        for off, ln in ranges:
            got = s.read(C, obj("r"), off, ln)
            assert type(got) is bytes
            assert got == (want[off:] if ln is None
                           else want[off:off + ln]), (off, ln)
        # a 4 KiB read gathered one block, whatever the object has
        before = s.usage()
        assert s.read(C, obj("r"), 2 * BLK, BLK) == \
            want[2 * BLK:3 * BLK]
        after = s.usage()
        nblocks = -(-size // BLK)
        assert after["read_calls"] - before["read_calls"] == 1
        assert after["read_blocks"] - before["read_blocks"] == 1
        assert after["read_obj_blocks"] - before["read_obj_blocks"] \
            == nblocks
        assert s.usage()["csum_failures"] == 0
    finally:
        s.umount()


def _flip_bit(s, coll, o, lb, at=17):
    """Rot under the store's feet: one bit of logical block ``lb``."""
    _flush(s)
    with s._lock:
        phys = s._load_extents(coll, o).blocks[lb]
        assert phys >= 0
        s._dev.seek(phys * BLK + at)
        b = s._dev.read(1)
        s._dev.seek(phys * BLK + at)
        s._dev.write(bytes([b[0] ^ 0x04]))
        s._dev.flush()


@pytest.mark.parametrize("where", ["inside", "outside"])
@pytest.mark.parametrize("kind", _KINDS)
def test_rot_is_found_by_the_read_that_touches_it(kind, where, tmp_path):
    """A flipped bit inside the range is EIO and counted; the same bit
    outside it leaves the ranged read sound, and the full read — what
    deep scrub issues (ECBackend.be_scan: store.read(coll, obj)) —
    still raises."""
    import errno
    s = _block_family(kind, tmp_path)
    try:
        want = _lay_plain(s)
        _flip_bit(s, C, obj("r"), 9)
        off, ln = (8 * BLK + 100, 2 * BLK) if where == "inside" \
            else (2 * BLK + 100, 6 * BLK - 100)
        if where == "inside":
            with pytest.raises(OSError) as ei:
                s.read(C, obj("r"), off, ln)
            assert ei.value.errno == errno.EIO
            assert "logical block 9" in str(ei.value)
            assert s.usage()["csum_failures"] == 1
        else:
            assert s.read(C, obj("r"), off, ln) == want[off:off + ln]
            assert s.read(C, obj("r"), 10 * BLK, None) == \
                want[10 * BLK:]
            assert s.usage()["csum_failures"] == 0
        with pytest.raises(OSError) as ei:
            s.read(C, obj("r"))
        assert ei.value.errno == errno.EIO
        assert s.usage()["csum_failures"] >= 1
    finally:
        s.umount()


class _Counted:
    """A utils.crc entry wrapped by a counter that also notes whether
    the calling thread owned the store's mutex."""

    def __init__(self, fn, store):
        self.fn, self.store = fn, store
        self.calls = 0
        self.owned = []

    def __call__(self, *a, **kw):
        self.calls += 1
        self.owned.append(self.store._lock._is_owned())
        return self.fn(*a, **kw)


@pytest.mark.parametrize("kind", _KINDS)
def test_read_verifies_in_one_native_call_outside_the_lock(
        kind, tmp_path, monkeypatch):
    """The mechanism, not the speed: a 4 KiB read of a 1 MiB object
    gathers 1 block of 256 and a full read all 256, each with ONE
    crc32c_blocks call, no per-block crc32c, and the verify runs with
    the store's mutex not held by the reader."""
    from ceph_tpu.store import blockstore, bluestore
    s = _block_family(kind, tmp_path)
    try:
        want = os.urandom(1 << 20)
        s.queue_transactions([Transaction().write(C, obj("m"), 0, want)])
        _flush(s)
        one = _Counted(blockstore.crc32c, s)
        many = _Counted(blockstore.crc32c_blocks, s)
        monkeypatch.setattr(blockstore, "crc32c", one)
        monkeypatch.setattr(bluestore, "crc32c", one)
        monkeypatch.setattr(blockstore, "crc32c_blocks", many)
        u0 = s.usage()
        assert s.read(C, obj("m"), 77 * BLK, BLK) == \
            want[77 * BLK:78 * BLK]
        u1 = s.usage()
        assert (one.calls, many.calls) == (0, 1)
        assert u1["read_blocks"] - u0["read_blocks"] == 1
        assert u1["read_obj_blocks"] - u0["read_obj_blocks"] == 256
        assert s.read(C, obj("m")) == want
        u2 = s.usage()
        assert (one.calls, many.calls) == (0, 2)
        assert u2["read_blocks"] - u1["read_blocks"] == 256
        assert many.owned == [False, False]
    finally:
        s.umount()


def test_full_read_of_compressed_object_adds_one_decompress(
        tmp_path, monkeypatch):
    from ceph_tpu.store import blockstore
    s = _block_family("block", tmp_path, compression="zlib")
    try:
        want = _lay_compressed(s)
        many = _Counted(blockstore.crc32c_blocks, s)
        one = _Counted(blockstore.crc32c, s)
        unzip = _Counted(s._decompress_seg, s)
        monkeypatch.setattr(blockstore, "crc32c_blocks", many)
        monkeypatch.setattr(blockstore, "crc32c", one)
        monkeypatch.setattr(s, "_decompress_seg", unzip)
        assert s.read(C, obj("r"), 0, 2 * BLK) == want[:2 * BLK]
        assert (many.calls, one.calls, unzip.calls) == (1, 0, 0)
        assert s.read(C, obj("r"), BLK, 3 * BLK) == want[BLK:4 * BLK]
        assert (many.calls, one.calls, unzip.calls) == (2, 0, 1)
        assert s.read(C, obj("r")) == want
        assert (many.calls, one.calls, unzip.calls) == (3, 0, 2)
    finally:
        s.umount()


@pytest.mark.parametrize("kind", _KINDS)
def test_ranged_readers_race_cow_overwriters(kind, tmp_path):
    """Readers of random ranges against COW overwriters of the same
    object: a block's physical home is freed and reused by the next
    apply while a reader verifies outside the lock, so the gather has
    to have made its bytes private.  No reader sees EIO, and every
    block returned is one acknowledged version of that block."""
    import random
    import sys
    import time
    nblk, nver = 24, 40
    s = _block_family(kind, tmp_path)
    if kind != "block":
        s.umount()
        s._start_applier = True          # the background applier races
        s.mount()
        s.queue_transactions([Transaction().create_collection(C)])

    def version(v, lb):
        return bytes([v & 0xFF, lb]) * (BLK // 2)
    s.queue_transactions([Transaction().write(
        C, obj("race"), 0, b"".join(version(0, lb)
                                    for lb in range(nblk)))])
    acked = [0] * nblk               # highest version written per block
    stop = threading.Event()
    errors = []

    def writer(seed):
        rng = random.Random(seed)
        try:
            for v in range(1, nver + 1):
                lb0 = rng.randrange(nblk)
                n = rng.randrange(1, min(4, nblk - lb0) + 1)
                for lb in range(lb0, lb0 + n):
                    acked[lb] = max(acked[lb], v)   # admitted below
                s.queue_transactions([Transaction().write(
                    C, obj("race"), lb0 * BLK,
                    b"".join(version(v, lb)
                             for lb in range(lb0, lb0 + n)))])
        except Exception as e:
            errors.append(e)

    def reader(seed):
        rng = random.Random(seed)
        try:
            while not stop.is_set():
                lb0 = rng.randrange(nblk)
                n = rng.randrange(1, nblk - lb0 + 1)
                got = s.read(C, obj("race"), lb0 * BLK, n * BLK)
                assert len(got) == n * BLK
                for i in range(n):
                    blk = got[i * BLK:(i + 1) * BLK]
                    assert blk == version(blk[0], lb0 + i), \
                        f"torn block {lb0 + i}"
                    assert blk[0] <= acked[lb0 + i]
        except Exception as e:
            errors.append(e)

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    try:
        readers = [threading.Thread(target=reader, args=(i,))
                   for i in range(6)]
        writers = [threading.Thread(target=writer, args=(100 + i,))
                   for i in range(3)]
        for t in readers + writers:
            t.start()
        deadline = time.monotonic() + 60
        for t in writers:
            t.join(max(0.1, deadline - time.monotonic()))
        stop.set()
        for t in readers:
            t.join(10)
        assert not any(t.is_alive() for t in readers + writers)
    finally:
        sys.setswitchinterval(old)
        stop.set()
    try:
        assert not errors, errors[:3]
        assert s.usage()["csum_failures"] == 0
        final = s.read(C, obj("race"))
        for lb in range(nblk):
            blk = final[lb * BLK:(lb + 1) * BLK]
            assert blk == version(blk[0], lb)
    finally:
        s.umount()
