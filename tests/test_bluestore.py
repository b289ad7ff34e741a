"""BlueStore-class async store (ISSUE 17): WAL group commit,
deferred apply, commit-vs-apply semantics, abort-path ledger hygiene,
and the crash-consistency torture matrix.

The torture test simulates a daemon crash with a BaseException-derived
kill (so no ``except Exception`` recovery path can defuse it) at each
phase boundary of the transaction pipeline — post-journal_append,
post-journal_fsync, mid-apply, pre-kv_commit — then remounts and
asserts bit-exact replay, idempotent re-apply, and zero leaked
allocator blocks, against BOTH the synchronous BlockStore and the
async BlueStore (reference store_test.cc + the deferred-replay cases
of bluestore_types tests).
"""
import os
import random
import threading
import time

import pytest

from ceph_tpu.store import (BlockStore, BlueStore, GHObject,
                            Transaction)
from ceph_tpu.store.blockstore import BLOCK, _Extents
from ceph_tpu.utils.crc import crc32c
from ceph_tpu.utils.store_ledger import PHASE_ORDER, charge

C = "1.0s0"


def obj(name, shard=0):
    return GHObject(name, shard)


class _SimCrash(BaseException):
    """Simulated daemon death: BaseException so the stores' own
    ``except Exception`` recovery paths cannot swallow it — exactly
    like a SIGKILL, nothing after the kill point runs."""


# ------------------------------------------------------- commit-vs-apply
def test_read_your_writes_in_apply_pending_window(tmp_path):
    """With the applier parked, committed-but-unapplied state must be
    fully readable: existence from the admission overlay, content via
    the read barrier's work-stealing apply."""
    s = BlueStore(str(tmp_path / "bs"), start_applier=False)
    s.mkfs()
    s.mount()
    try:
        s.queue_transactions([Transaction().create_collection(C)])
        t = Transaction().write(C, obj("w"), 0, b"pending" * 1000)
        t.setattr(C, obj("w"), "a", b"v")
        s.queue_transactions([t])
        with s._qcond:
            assert s._applied_seq < s._wal_seq   # genuinely pending
        # overlay answers existence without forcing the apply
        assert s.exists(C, obj("w"))
        assert s.collection_exists(C)
        assert not s.exists(C, obj("ghost"))
        # content reads steal the apply and see the committed txn
        assert s.read(C, obj("w")) == b"pending" * 1000
        assert s.getattr(C, obj("w"), "a") == b"v"
        assert s.stat(C, obj("w")).size == 7000
        # remove in the pending window: overlay flips existence back
        s.queue_transactions([Transaction().remove(C, obj("w"))])
        assert not s.exists(C, obj("w"))
        with pytest.raises(FileNotFoundError):
            s.queue_transactions(
                [Transaction().clone(C, obj("w"), obj("w2"))])
    finally:
        s.umount()


def test_xattr_overlay_serves_pending_values_without_apply(tmp_path):
    """getattr on a pending setattr must resolve from the admission
    overlay without forcing the apply — the EC write path reads the
    hinfo/object-info xattrs before every sub-write, so a barrier here
    would re-serialize the deferred pipeline."""
    s = BlueStore(str(tmp_path / "bs"), start_applier=False)
    s.mkfs()
    s.mount()
    try:
        s.queue_transactions([Transaction().create_collection(C)])
        t = Transaction().write(C, obj("x"), 0, b"d" * 4096)
        t.setattr(C, obj("x"), "hi", b"v1")
        s.queue_transactions([t])
        applied_before = s._applied_seq
        # pending value served, apply untouched
        assert s.getattr(C, obj("x"), "hi") == b"v1"
        assert s._applied_seq == applied_before
        # newer pending setattr wins over the older one
        t = Transaction().setattr(C, obj("x"), "hi", b"v2")
        s.queue_transactions([t])
        assert s.getattr(C, obj("x"), "hi") == b"v2"
        assert s._applied_seq == applied_before
        # pending rmattr is a tombstone, not a fall-through to the KV
        s.queue_transactions([Transaction().rmattr(C, obj("x"), "hi")])
        with pytest.raises(KeyError):
            s.getattr(C, obj("x"), "hi")
        assert s._applied_seq == applied_before
        # attr never set on an object created in the window: KeyError,
        # not FileNotFoundError, and still no apply
        with pytest.raises(KeyError):
            s.getattr(C, obj("x"), "other")
        assert s._applied_seq == applied_before
        # missing object stays FileNotFoundError
        with pytest.raises(FileNotFoundError):
            s.getattr(C, obj("ghost"), "hi")
        # identity change (clone dst) can't be synthesized: the read
        # barriers and sees the post-apply truth
        t = Transaction().setattr(C, obj("x"), "hi", b"v3")
        t.clone(C, obj("x"), obj("y"))
        s.queue_transactions([t])
        assert s.getattr(C, obj("y"), "hi") == b"v3"
        assert s._applied_seq > applied_before
        # after full drain the KV agrees with everything served above
        s.flush()
        assert s.getattr(C, obj("x"), "hi") == b"v3"
        with pytest.raises(KeyError):
            s.getattr(C, obj("x"), "other")
    finally:
        s.umount()


def test_on_commit_fires_before_apply(tmp_path):
    """The ack semantics the rewrite exists for: on_commit callbacks
    ride WAL durability and must fire while apply is still pending;
    on_applied waits for the applier."""
    s = BlueStore(str(tmp_path / "bs"), start_applier=False)
    s.mkfs()
    s.mount()
    try:
        s.queue_transactions([Transaction().create_collection(C)])
        committed = threading.Event()
        applied = threading.Event()
        t = Transaction().write(C, obj("o"), 0, b"x" * 4096)
        t.register_on_commit(committed.set)
        t.register_on_applied(applied.set)
        s.queue_transactions([t])
        assert committed.wait(5)
        assert not applied.is_set()      # applier is parked
        s.flush()                        # drains via work-stealing
        assert applied.wait(5)
    finally:
        s.umount()


def test_group_commit_amortizes_fsyncs_and_orders_callbacks(tmp_path):
    """Concurrent committers share WAL fsyncs (group_syncs < txns)
    and per-thread on_commit ordering is preserved — the EC backend's
    sub-write acks are exactly these callbacks, so their ordering IS
    the peer-ack ordering."""
    s = BlueStore(str(tmp_path / "bs"),
                  group_commit_window_s=0.002)
    s.mkfs()
    s.mount()
    try:
        s.queue_transactions([Transaction().create_collection(C)])
        base_syncs = s.wal_group_syncs
        per_thread = 12
        n_threads = 8
        orders = {w: [] for w in range(n_threads)}

        def worker(wid):
            for i in range(per_thread):
                t = Transaction().write(C, obj(f"g{wid}_{i}"), 0,
                                        b"z" * 8192)
                t.register_on_commit(
                    lambda w=wid, j=i: orders[w].append(j))
                s.queue_transactions([t])

        ws = [threading.Thread(target=worker, args=(w,))
              for w in range(n_threads)]
        for w in ws:
            w.start()
        for w in ws:
            w.join()
        s.flush()
        total = per_thread * n_threads
        assert s.wal_group_txns >= total
        # amortization: strictly fewer fsyncs than transactions
        assert 0 < s.wal_group_syncs - base_syncs < total
        # per-submitter commit order preserved under the group
        for w in range(n_threads):
            assert orders[w] == list(range(per_thread))
        # every write readable after the drain
        for w in range(n_threads):
            for i in range(per_thread):
                assert s.stat(C, obj(f"g{w}_{i}")).size == 8192
    finally:
        s.umount()


def test_deferred_ledgers_keep_charge_sum_equals_wall(tmp_path):
    """The async split must not break the store-ledger invariant:
    every finalized ledger's charged phases sum to its wall exactly,
    with the deferred_queue phase present and stamps monotone —
    commit acks riding WAL durability change WHERE time is charged,
    never the total."""
    s = BlueStore(str(tmp_path / "bs"))
    s.mkfs()
    s.mount()
    try:
        s.queue_transactions([Transaction().create_collection(C)])

        def worker(wid):
            for i in range(6):
                s.queue_transactions(
                    [Transaction().write(C, obj(f"l{wid}_{i}"), 0,
                                         b"y" * 16384)],
                    op="client_write")

        ws = [threading.Thread(target=worker, args=(w,))
              for w in range(4)]
        for w in ws:
            w.start()
        for w in ws:
            w.join()
        s.flush()
        recent = s._store_accum().recent()
        assert len(recent) >= 25
        saw_deferred = False
        for led in recent:
            stamps = [led[p] for p in PHASE_ORDER if p in led]
            assert stamps == sorted(stamps)     # monotone
            assert sum(dt for _, dt in charge(led)) == \
                pytest.approx(stamps[-1] - stamps[0], abs=1e-9)
            # no backend-private handshake keys may leak into the
            # observed ledgers
            assert not any(isinstance(k, str) and k.startswith("_")
                           for k in led)
            saw_deferred |= "deferred_queue" in led
        assert saw_deferred
        dump = s.dump_store()
        assert dump["phase_seconds"].get("deferred_queue", 0) >= 0
        assert sum(dump["phase_seconds"].values()) == \
            pytest.approx(dump["txn_seconds"], abs=1e-6)
    finally:
        s.umount()


# --------------------------------------------------- abort-path hygiene
def test_abort_discards_ledger_whole(tmp_path):
    """A queue_transactions call that raises (check_ops reject or
    mid-apply error) must discard its TLS ledger WHOLE — no dangling
    stamps bleeding into the next transaction on the same thread —
    and count the abort."""
    s = BlueStore(str(tmp_path / "bs"))
    s.mkfs()
    s.mount()
    try:
        s.queue_transactions([Transaction().create_collection(C)])
        s.flush()
        accum = s._store_accum()
        before = len(accum.recent())
        aborts0 = accum.aborts
        # check_ops reject: missing clone source
        with pytest.raises(FileNotFoundError):
            s.queue_transactions(
                [Transaction().clone(C, obj("nope"), obj("dst"))])
        assert accum.aborts == aborts0 + 1
        # the aborted call observed NO ledger
        s.flush()
        assert len(accum.recent()) == before
        # the next txn on this same thread starts clean: its ledger
        # carries only its own stamps and sums to its own wall
        s.queue_transactions(
            [Transaction().write(C, obj("clean"), 0, b"c" * 4096)])
        s.flush()
        recent = accum.recent()
        assert len(recent) == before + 1
        led = recent[-1]
        stamps = [led[p] for p in PHASE_ORDER if p in led]
        assert stamps == sorted(stamps)
        assert sum(dt for _, dt in charge(led)) == \
            pytest.approx(stamps[-1] - stamps[0], abs=1e-9)
        assert s.dump_store()["aborts"] == aborts0 + 1
    finally:
        s.umount()


def test_abort_mid_apply_blockstore_ledger_hygiene(tmp_path):
    """Same hygiene on the synchronous backend, with the failure
    landing mid-apply (malformed payload passes check_ops)."""
    s = BlockStore(str(tmp_path / "bs"))
    s.mkfs()
    s.mount()
    try:
        s.queue_transactions([Transaction().create_collection(C)])
        accum = s._store_accum()
        before = len(accum.recent())
        t = Transaction()
        t.ops.append(("write", C, obj("bad"), 0, None))
        with pytest.raises(TypeError):
            s.queue_transactions([t])
        assert accum.aborts == 1
        assert len(accum.recent()) == before
        s.queue_transactions(
            [Transaction().write(C, obj("ok"), 0, b"o" * 4096)])
        led = accum.recent()[-1]
        stamps = [led[p] for p in PHASE_ORDER if p in led]
        assert stamps == sorted(stamps)
    finally:
        s.umount()


# ------------------------------------------------- crash torture matrix
def _stamp_killer(store, phase):
    """Kill the daemon the instant ``phase`` is stamped (the stamp is
    the last instruction of that pipeline step, so state is exactly
    post-step)."""
    orig = store._stamp_txn

    def stamp(name):
        orig(name)
        if name == phase:
            raise _SimCrash(phase)
    store._stamp_txn = stamp


def _write_block_killer(store, after_blocks):
    """Kill mid-apply: after ``after_blocks`` device block writes the
    daemon dies with the extent maps un-flipped."""
    orig = store._write_block
    seen = [0]

    def wb(phys, data):
        seen[0] += 1
        if seen[0] > after_blocks:
            raise _SimCrash("mid_apply")
        orig(phys, data)
    store._write_block = wb


def _flush_dev_killer(store):
    """Kill pre-kv_commit: data landed and flushed, the atomic KV
    flip never ran."""
    orig = store._flush_dev

    def fd(dirty):
        orig(dirty)
        raise _SimCrash("pre_kv_commit")
    store._flush_dev = fd


def _alloc_leak_audit(store):
    """Every allocator-used block must be referenced by some extent
    map (direct phys or compressed segment) — anything else leaked."""
    referenced = set()
    for _, raw in store._db.iterate("X/"):
        ext = _Extents.load(raw)
        for v in ext.blocks:
            if v >= 0:
                referenced.add(v)
        for seg in ext.segs.values():
            referenced.update(seg["phys"])
    assert store._alloc.used() == len(referenced), \
        f"allocator holds {store._alloc.used()} blocks, extent maps " \
        f"reference {len(referenced)} — leak"


_KILL_POINTS = ("journal_append", "journal_fsync", "mid_apply",
                "pre_kv_commit")


@pytest.mark.parametrize("kill", _KILL_POINTS)
@pytest.mark.parametrize("backend", ["blockstore", "bluestore"])
def test_crash_torture(tmp_path, kill, backend):
    path = str(tmp_path / "bs")
    zombies = []          # crashed instances stay referenced so no
    #                       gc-time flush races the remount

    def make(arm=None):
        if backend == "bluestore":
            s = BlueStore(path, start_applier=False)
        else:
            s = BlockStore(path)
        if not zombies:
            s.mkfs()
        s.mount()
        if arm:
            arm(s)
        zombies.append(s)
        return s

    # durable baseline state, cleanly unmounted
    s = make()
    s.queue_transactions([Transaction().create_collection(C)])
    base = bytes(range(256)) * 32            # 8 KiB
    s.queue_transactions([Transaction().write(C, obj("keep"), 0,
                                              base)])
    if backend == "bluestore":
        s.flush()
    zombies.pop()
    s.umount()

    # the doomed transaction: overwrite + a fresh object
    doomed = Transaction()
    doomed.write(C, obj("keep"), 4096, b"P" * 4096)
    doomed.write(C, obj("fresh"), 0, b"F" * 12288)

    def arm(s):
        if kill in ("journal_append", "journal_fsync"):
            _stamp_killer(s, kill)
        elif kill == "mid_apply":
            _write_block_killer(s, 2)
        else:
            _flush_dev_killer(s)

    s = make(arm)
    with pytest.raises(_SimCrash):
        s.queue_transactions([doomed])
        if backend == "bluestore":
            # client-side kill points raise from queue_transactions;
            # apply-side ones raise from the work-stealing pump
            s.flush()
    # CRASH: no umount, instance abandoned mid-pipeline

    # -- remount #1: replay must yield a consistent, exact state ----
    s2 = make()
    assert s2.read(C, obj("keep"), 0, 4096) == base[:4096]
    tail = s2.read(C, obj("keep"), 4096)
    applied = s2.exists(C, obj("fresh"))
    if applied:
        # the whole txn replayed: every op of it, bit-exact
        assert tail == b"P" * 4096
        assert s2.read(C, obj("fresh")) == b"F" * 12288
    else:
        # the whole txn vanished: the overwrite too (atomicity)
        assert tail == base[4096:]
    if backend == "bluestore":
        s2.flush()
    _alloc_leak_audit(s2)
    state1 = (s2.read(C, obj("keep")),
              s2.read(C, obj("fresh")) if applied else None)
    used1 = s2._alloc.used()
    zombies.pop()
    s2.umount()

    # -- remount #2: re-apply is idempotent ---------------------------
    s3 = make()
    assert s3.read(C, obj("keep")) == state1[0]
    assert s3.exists(C, obj("fresh")) == applied
    if applied:
        assert s3.read(C, obj("fresh")) == state1[1]
    assert s3._alloc.used() == used1
    _alloc_leak_audit(s3)
    # the store stays writable after recovery
    s3.queue_transactions(
        [Transaction().write(C, obj("post"), 0, b"alive" * 100)])
    assert s3.read(C, obj("post")) == b"alive" * 100
    zombies.pop()
    s3.umount()


def test_torture_durability_of_committed_txns(tmp_path):
    """The commit contract under crash: every transaction whose
    on_commit fired BEFORE the crash must survive the remount, even
    though apply never ran (WAL durability is the promise the async
    ack makes)."""
    path = str(tmp_path / "bs")
    s = BlueStore(path, start_applier=False)
    s.mkfs()
    s.mount()
    s.queue_transactions([Transaction().create_collection(C)])
    committed = []
    for i in range(8):
        t = Transaction().write(C, obj(f"d{i}"), 0,
                                bytes([i]) * 8192)
        t.register_on_commit(lambda j=i: committed.append(j))
        s.queue_transactions([t])
    s._finisher.wait_for_empty()     # drain acks, NOT the applier
    assert sorted(committed) == list(range(8))
    with s._qcond:
        assert s._applied_seq < s._wal_seq   # nothing applied yet
    # crash (no umount), remount fresh
    s2 = BlueStore(path)
    s2.mount()
    try:
        for i in range(8):
            assert s2.read(C, obj(f"d{i}")) == bytes([i]) * 8192
        _alloc_leak_audit(s2)
    finally:
        s2.umount()
    del s


# --------------------------------------------------------- persistence
def test_bluestore_survives_remount_with_wal_retire(tmp_path):
    """Clean-shutdown path: WAL segments retire once applied, applied
    watermark persists, and a remount serves everything without
    replay work."""
    path = str(tmp_path / "bs")
    s = BlueStore(path, wal_segment_bytes=1 << 20)
    s.mkfs()
    s.mount()
    t = Transaction().create_collection(C)
    s.queue_transactions([t])
    for i in range(6):
        s.queue_transactions(
            [Transaction().write(C, obj(f"r{i}"), 0, b"R" * (256 << 10))])
    s.queue_transactions(
        [Transaction().omap_setkeys(C, obj("r0"), {"k": b"v"})])
    s.flush()
    s.umount()
    s2 = BlueStore(path)
    s2.mount()
    try:
        for i in range(6):
            assert s2.read(C, obj(f"r{i}")) == b"R" * (256 << 10)
        assert s2.omap_get(C, obj("r0"))["k"] == b"v"
        u = s2.usage()
        assert u["wal"]["records"] == 0      # nothing replayed
    finally:
        s2.umount()


def test_backpressure_bounds_deferred_queue(tmp_path):
    """deferred_queue_depth bounds the commit→apply window: a
    submitter that finds the queue full becomes an applier
    (work-steal) instead of parking — so even with no applier thread
    at all, writes complete and the queue never grows past the
    bound."""
    s = BlueStore(str(tmp_path / "bs"), start_applier=False,
                  deferred_queue_depth=4, apply_batch_txns=2)
    s.mkfs()
    s.mount()
    try:
        s.queue_transactions([Transaction().create_collection(C)])
        hwm = [0]
        orig_pump = s._pump_once

        def pump():
            with s._qcond:
                hwm[0] = max(hwm[0], len(s._pending))
            return orig_pump()

        s._pump_once = pump
        for i in range(20):
            s.queue_transactions(
                [Transaction().write(C, obj(f"b{i}"), 0,
                                     b"q" * 4096)])
        # every admission held the bound (small overshoot allowed for
        # concurrent racers; single-threaded here, so exact)
        assert hwm[0] <= 4
        with s._qcond:
            assert len(s._pending) <= 4
        s.flush()
        for i in range(20):
            assert s.read(C, obj(f"b{i}")) == b"q" * 4096
    finally:
        s.umount()


# ------------------------------------------------------------ checksums
def _seeded_blocks(n, seed=31):
    rng = random.Random(seed * 1000 + n)
    return [rng.randbytes(BLOCK) for _ in range(n)]


@pytest.mark.parametrize("n", [1, 2, 7, 112, 128])
def test_crc_batch_is_the_per_block_loop_bit_for_bit(n):
    """Whatever the batch (112 and 128 are a shard's blocks in the two
    write cells), the one call gives what a loop over crc32c gives."""
    s = BlueStore("", start_applier=False)
    blocks = _seeded_blocks(n)
    assert s._crc_batch(blocks) == [crc32c(b) for b in blocks]
    assert (s.csum_batches, s.csum_blocks) == (1, n)


def test_crc_batch_refuses_a_ragged_block():
    s = BlueStore("", start_applier=False)
    with pytest.raises(ValueError):
        s._crc_batch([b"\x01" * BLOCK, b"\x02" * (BLOCK - 1)])


def test_fold_is_one_native_call_and_never_the_device_route(monkeypatch):
    """A 128-block write folds through ONE crc32c_blocks call and no
    per-block crc32c, and nothing of a bluestore write reaches
    ops/crclinear (its ``shared`` raises here)."""
    from ceph_tpu.ops import crclinear
    from ceph_tpu.store import bluestore as bs_mod

    def no_device_route(*a, **kw):
        raise AssertionError("a bluestore write reached ops/crclinear")
    monkeypatch.setattr(crclinear, "shared", no_device_route)
    calls = {"crc32c": 0, "crc32c_blocks": []}
    real_one, real_many = bs_mod.crc32c, bs_mod.crc32c_blocks

    def one(data, crc=0):
        calls["crc32c"] += 1
        return real_one(data, crc)

    def many(buf, block_len):
        calls["crc32c_blocks"].append((len(buf), block_len))
        return real_many(buf, block_len)
    monkeypatch.setattr(bs_mod, "crc32c", one)
    monkeypatch.setattr(bs_mod, "crc32c_blocks", many)
    s = BlueStore("", start_applier=False)
    s.mount()
    try:
        s.queue_transactions([Transaction().create_collection(C)])
        payload = b"".join(_seeded_blocks(128))
        s.queue_transactions([Transaction().write(C, obj("f"), 0,
                                                  payload)])
        s.flush()
        assert calls == {"crc32c": 0,
                         "crc32c_blocks": [(128 * BLOCK, BLOCK)]}
        assert (s.csum_batches, s.csum_blocks) == (1, 128)
        assert set(s.dump_store()["csum"]) >= {"batches", "blocks"}
        assert not any("device" in key for key in s.dump_store()["csum"])
        # the stamps are the per-block values, none left at the
        # placeholder, and the read verifies against them
        ext = s._load_extents(C, obj("f"))
        assert [ext.crcs[lb] for lb in range(128)] == \
            [real_one(payload[lb * BLOCK:(lb + 1) * BLOCK])
             for lb in range(128)]
        assert s.read(C, obj("f")) == payload
        assert s.usage()["csum_failures"] == 0
    finally:
        s.umount()


def test_bluestore_csum_detects_bitrot_after_a_batched_fold(tmp_path):
    """test_store.py::test_blockstore_csum_detects_bitrot on bluestore:
    a multi-block write reads back clean, and one flipped bit in one
    of its blocks on the raw device is EIO, not silent corruption."""
    path = str(tmp_path / "bs")
    s = BlueStore(path)
    s.mkfs()
    s.mount()
    try:
        s.queue_transactions([Transaction().create_collection(C)])
        payload = b"".join(_seeded_blocks(7))
        s.queue_transactions([Transaction().write(C, obj("rot"), 0,
                                                  payload)])
        s.flush()
        assert s.read(C, obj("rot")) == payload
        assert s.usage()["csum_failures"] == 0
        ext = s._load_extents(C, obj("rot"))
        assert all(ext.crcs[lb] for lb in range(7))
        phys = ext.blocks[5]
        assert phys >= 0
        with open(os.path.join(path, "block.dev"), "r+b") as f:
            f.seek(phys * BLOCK + 17)
            b = f.read(1)
            f.seek(phys * BLOCK + 17)
            f.write(bytes([b[0] ^ 0x04]))
        with pytest.raises(OSError):
            s.read(C, obj("rot"))
        assert s.usage()["csum_failures"] >= 1
        # the blocks before the rotten one still verify
        assert s.read(C, obj("rot"), 0, 5 * BLOCK) == \
            payload[:5 * BLOCK]
    finally:
        s.umount()
