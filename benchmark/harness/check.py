"""The comparison that decides ``correct``.

Every number compared is a count of answers that differ from the plain
reference, so every limit is 0.  The reference is the seeded byte model
(``seeded.py``: what a read must return) and the numpy encoder of the
configuration's erasure code (``references/<code>.py``, found by
``spec.reference_name``: what the k+m shards of an object must be),
neither of which shares code with ceph_tpu.  It is run on what the
timed window itself wrote and read, once the window has closed, the
load has drained and the device's peak memory has been read.
"""
from collections import deque

import numpy as np

from . import spec
from .loadgen import op_ok

READBACK_DEPTH = 16


def _in_flight(items, submit, check, depth, timeout):
    """chip_smoke.py's read-back loop: quiet, oldest retired first."""
    pending = deque()

    def retire():
        item, comp = pending.popleft()
        try:
            rc = comp.wait(timeout)
        except TimeoutError:
            rc = -110
        check(item, rc, comp)
    for item in items:
        if len(pending) >= depth:
            retire()
        pending.append((item, submit(item)))
    while pending:
        retire()


def populate(io, model, n_objects: int, depth: int = 16,
             timeout: float = 120.0) -> None:
    """Write the populated set through the client path (set-up)."""
    bad = []

    def check(n, rc, comp):
        if rc != 0:
            bad.append((n, rc))
    _in_flight(range(n_objects),
               lambda n: io.aio_write_full(model.name(n), model.base(n)),
               check, depth, timeout)
    if bad:
        raise RuntimeError(f"populate: writes failed: {bad[:4]}")


def apply_writes(model, gen) -> set:
    """Bring the byte model up to what was acknowledged; -> numbers
    of the objects that partial writes touched."""
    touched = set()
    for rec in gen.records:             # a caller's own ops, in order
        for (t_s, t_a, c, n, off, pay, rc, got, kept) in rec:
            if gen.ops[c]["op"] == "write" and rc == 0:
                model.patch(n, off, pay)
                touched.add(n)
    return touched


def sample_objects(gen, window, touched: set, chk: dict, rng):
    """-> (objects to read back, objects whose shards are checked):
    what partial writes touched, a seeded part of what the window
    wrote whole with the object acked last among it, or, for a window
    that only read, a seeded part of the set it read from."""
    limit = int(chk.get("parity_objects", 16))
    sample = set(touched)
    wrote_full = [r for r in window
                  if gen.ops[r[2]]["op"] == "write_full" and r[6] == 0]
    if wrote_full:
        nums = sorted({r[3] for r in wrote_full})
        sample |= set(rng.choice(nums, size=min(limit, len(nums)),
                                 replace=False).tolist())
        sample.add(max(wrote_full, key=lambda r: r[1])[3])
    if not sample:
        sample = set(rng.choice(gen.n_populated,
                                size=min(limit, gen.n_populated),
                                replace=False).tolist())
    sample = sorted(sample)
    if len(sample) <= limit + 1:
        return sample, sample
    return sample, sorted(rng.choice(sample, size=limit,
                                     replace=False).tolist())


def read_back_wrong(dep, model, sample, timeout: float) -> int:
    """Objects that the client does not read back as the model has them."""
    wrong = []

    def compare(n, rc, comp):
        if rc != 0 or bytes(comp.reply.out_data[0]) != model.current(n):
            wrong.append(n)
    _in_flight(sample, lambda n: dep.io.aio_read(model.name(n)),
               compare, READBACK_DEPTH, timeout)
    return len(wrong)


def stored_shards(dep, model, sample):
    """-> (shards in the live stores that differ from the reference
    encoder's, shards that are in no live store)."""
    k, m, su = dep.k, dep.m, dep.stripe_unit
    encoder = spec.reference(spec.reference_name(dep.config))
    profile = dep.config["pool"]["profile"]
    index = dep.shard_index([model.name(n) for n in sample])
    wrong = missing = 0
    for n in sample:
        obj = model.current(n)
        # the last stripe of an object is stored padded with zeros
        obj += bytes(-len(obj) % (k * su))
        want = encoder.shards_of(obj, profile, su)
        found = 0
        for s in range(k + m):
            copies = index.get((model.name(n), s), [])
            found += bool(copies)
            wrong += sum(1 for (osd_id, store, coll, obj) in copies
                         if bytes(store.read(coll, obj)) != want[s])
        missing += max(0, (k + m - len(dep.dead)) - found)
    return wrong, missing


def run(dep, cell, model, gen, t0: float, seconds: float, seed: int,
        lanes_window: dict):
    """-> ({name: (value, limit)}, what was looked at); ``correct`` is
    every value <= limit."""
    chk = cell.traffic.get("check", {})
    window = gen.in_window(t0, seconds)
    numbers = {}
    # a window in which nothing was acknowledged has checked nothing
    numbers["window_empty"] = (0 if window else 1, 0)
    # every op the generator sent, in the window or not, was answered
    numbers["ops_failed"] = (
        sum(1 for r in gen.all_records() if not op_ok(r, gen.ops))
        + len(gen.errors), 0)

    # reads of the window: a sample kept by a rule fixed before it
    # (every keep_every-th op of each caller), against the model.  A
    # mix of reads and writes keeps none: a read races the writes in
    # flight, and the read-back below covers it
    touched = apply_writes(model, gen)
    kept = [r for r in window if r[8] is not None]
    if all(o["op"] == "read" for o in gen.ops):
        bad = sum(1 for (t_s, t_a, c, n, off, pay, rc, got, data) in kept
                  if bytes(data) != model.base(n)[off:off + got])
        numbers["window_reads_wrong"] = (bad, 0)
        numbers["window_reads_unchecked"] = (0 if kept else 1, 0)

    # what the window wrote: read back through the client, and the k+m
    # shards the stores hold against the reference encoder
    rng = np.random.default_rng([abs(int(seed)), 0xC4EC])
    sample, shard_sample = sample_objects(gen, window, touched, chk, rng)
    numbers["readback_objects_wrong"] = (
        read_back_wrong(dep, model, sample, gen.op_timeout), 0)
    wrong, missing = stored_shards(dep, model, shard_sample)
    numbers["stored_shards_wrong"] = (wrong, 0)
    numbers["stored_shards_missing"] = (missing, 0)

    # the deployment forbids the CPU twin: every lane request of the
    # window ran on the device, and the device raised nothing
    twin = sum(v["twin_reqs"] for v in lanes_window["lanes"].values())
    numbers["lane_requests_on_twin"] = (twin, 0)
    numbers["device_errors"] = (lanes_window["device_errors"], 0)
    return numbers, {"objects_read_back": len(sample),
                     "objects_shard_checked": len(shard_sample),
                     "window_reads_compared": len(kept)}


def correct(numbers: dict) -> bool:
    return all(v <= limit for v, limit in numbers.values())
