"""The deployment ``ec_k8m4_13osd_fastread`` and its cell
``k8m4.fast_read_4m`` (ISSUE 36): a pool whose every read fans to all
k+m shards and is rebuilt from the first k to answer.

Compared with a plain any-k reference that shares nothing with
``ceph_tpu`` (``tests/reference_anyk.py``), at a small size on the CPU:

(a) the codec: every one of the 495 have-sets of k=8 m=4, and seeded
    have-sets over several stripes through ``decode_batch_async``;
(b) a 13-OSD cluster whose pool has ``fast_read``: the replies of
    seeded sets of 4 shards are held back at the primary (from outside:
    the program has no option for it), so reads complete on have-sets
    with 1, 2, 3 and 4 data shards missing; the stragglers arrive after
    completion, change nothing and are counted;
(c) the benchmark's files: the configuration is ``ec_k8m4_13osd`` but
    for the one ``conf`` value, the two new readers compute what their
    docstrings say, and the whole harness runs the cell at a tiny size.
"""
import itertools
import json
import os
import subprocess
import sys
import time

import numpy as np
import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))
sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))

import reference_anyk as ref  # noqa: E402
from harness import spec, trace  # noqa: E402

K, M, N = 8, 4, 12
CELL = "k8m4.fast_read_4m"
READ_CELLS = ["k4m2.degraded_read_4m", "cauchy_k10m4.degraded_read_4m",
              CELL]


def make_codec():
    from ceph_tpu.ec import registry as ecreg
    return ecreg.instance().factory(
        "tpu", {"k": str(K), "m": str(M), "technique": "reed_sol_van"})


def seeded_object(seed: int, stripes: int, unit: int) -> bytes:
    return np.random.default_rng([seed, 0xFA57]).integers(
        0, 256, stripes * K * unit, dtype=np.uint8).tobytes()


def data_of(have: dict, rebuilt: dict, stripes: int, unit: int) -> bytes:
    """The object from the data chunks a read holds and those a decode
    rebuilt, interleaved as the PG does."""
    cols = [np.frombuffer(bytes(have[i]) if i in have
                          else np.ascontiguousarray(rebuilt[i]).tobytes(),
                          dtype=np.uint8).reshape(stripes, unit)
            for i in range(K)]
    return np.stack(cols, axis=1).tobytes()


# -- (a) the codec ------------------------------------------------------------
@pytest.mark.parametrize("missing", range(M + 1))
def test_every_have_set_decodes_to_the_reference_bit_for_bit(missing):
    """All C(12, 8) = 495 have-sets, one stripe each, grouped by how
    many data shards they lack (1 + 32 + 168 + 224 + 70)."""
    unit = 128
    codec = make_codec()
    obj = seeded_object(missing, 1, unit)
    shards = ref.shards_of(obj, K, M, unit)
    seen = 0
    for ids in itertools.combinations(range(N), K):
        if sum(1 for i in range(K) if i not in ids) != missing:
            continue
        seen += 1
        have = {i: shards[i] for i in ids}
        rebuilt = codec.decode_batch(
            {i: np.frombuffer(b, dtype=np.uint8).reshape(1, unit)
             for i, b in have.items()}, unit)
        assert sorted(rebuilt) == [i for i in range(N) if i not in ids]
        assert data_of(have, rebuilt, 1, unit) == obj, ids
        assert ref.decode(have, K, M, unit) == obj, ids
        for e, chunk in rebuilt.items():     # the parity rows too
            assert np.ascontiguousarray(chunk).tobytes() == shards[e], \
                (ids, e)
    assert seen == [1, 32, 168, 224, 70][missing]


@pytest.mark.parametrize("stripes", [2, 3, 8, 16])
def test_seeded_have_sets_through_the_async_decode_entry(stripes):
    unit = 256
    codec = make_codec()
    rng = np.random.default_rng([stripes, 36])
    for round_ in range(6):
        obj = seeded_object(100 * stripes + round_, stripes, unit)
        shards = ref.shards_of(obj, K, M, unit)
        ids = sorted(rng.choice(N, size=K, replace=False).tolist())
        have = {i: shards[i] for i in ids}
        rebuilt = codec.decode_batch_async(
            {i: np.frombuffer(b, dtype=np.uint8).reshape(stripes, unit)
             for i, b in have.items()}, unit).wait()
        assert data_of(have, rebuilt, stripes, unit) == obj, ids
        assert ref.decode(have, K, M, unit) == obj, ids


def test_the_reference_refuses_fewer_than_k_shards():
    unit = 16
    shards = ref.shards_of(seeded_object(1, 1, unit), K, M, unit)
    with pytest.raises(ValueError):
        ref.decode({i: shards[i] for i in range(K - 1)}, K, M, unit)


# -- (b) the cluster ------------------------------------------------------------
UNIT = 4096
STRIPES = 3


class HeldReplies:
    """Holds back, at one primary PG, the sub-read replies of chosen
    shards until ``release``: the read completes on the other shards'
    answers, then these arrive as its stragglers."""

    def __init__(self, pg):
        from ceph_tpu.msg.messages import MOSDECSubOpReadReply
        self.pg = pg
        self.reply_type = MOSDECSubOpReadReply
        self.shards = frozenset()
        self.stash = []
        self._orig = pg.backend.handle_message
        pg.backend.handle_message = self._handle

    def _handle(self, msg):                  # under the PG lock
        if isinstance(msg, self.reply_type) and msg.shard in self.shards:
            self.stash.append(msg)
            return True
        return self._orig(msg)

    def release(self) -> int:
        self.shards = frozenset()
        with self.pg.lock:
            held, self.stash = self.stash, []
            for msg in held:
                self._orig(msg)
        return len(held)

    def undo(self) -> None:
        self.release()
        self.pg.backend.handle_message = self._orig


@pytest.fixture(scope="module")
def fast_pool():
    """13 OSDs, the pool made by the mon's default as the benchmark's
    configuration makes it, one object, its primary PG."""
    from ceph_tpu.cluster import Cluster, test_config
    conf = test_config(osd_pool_default_ec_fast_read=True,
                       osd_pool_erasure_code_stripe_unit=UNIT,
                       osd_heartbeat_grace=20.0,
                       ec_tpu_fallback_cpu=False)
    with Cluster(n_osds=13, conf=conf) as c:
        for i in range(13):
            c.wait_for_osd_up(i, 60)
        c.create_ec_profile("fr", plugin="tpu", technique="reed_sol_van",
                            k=str(K), m=str(M), w="8")
        c.create_pool("frp", "erasure", pg_num=4,
                      erasure_code_profile="fr")
        c.wait_for_clean(120)
        io = c.rados(timeout=60).open_ioctx("frp")
        obj = seeded_object(36, STRIPES, UNIT)
        io.write_full("fast0", obj)
        osdmap = next(o for o in c.osds.values() if o is not None).osdmap
        pool_id = osdmap.pool_name_to_id["frp"]
        assert osdmap.get_pool("frp").fast_read
        pgid = osdmap.object_locator_to_pg("fast0", pool_id)
        _, _, acting, primary = osdmap.pg_to_up_acting_osds(pgid)
        pg = c.osds[primary].pgs[pgid]
        yield {"cluster": c, "io": io, "obj": obj, "pg": pg,
               "own": acting.index(primary), "osd": c.osds[primary]}


def held_set(own: int, missing: int, seed: int) -> frozenset:
    """4 shards to hold back, ``missing`` of them data shards, never
    the primary's own (it answers from its store, first)."""
    rng = np.random.default_rng([seed, missing])
    data = [i for i in range(K) if i != own]
    parity = [i for i in range(K, N) if i != own]
    return frozenset(
        rng.choice(data, size=missing, replace=False).tolist()
        + rng.choice(parity, size=M - missing, replace=False).tolist())


@pytest.mark.parametrize("missing", [1, 2, 3, 4])
def test_a_fast_read_completes_on_the_first_k_and_counts_its_stragglers(
        fast_pool, missing):
    pg, io, obj = fast_pool["pg"], fast_pool["io"], fast_pool["obj"]
    held = held_set(fast_pool["own"], missing, 36)
    backend, batcher = pg.backend, fast_pool["osd"].encode_batcher
    before = (backend.fast_reads, backend.fast_read_stragglers,
              backend.fast_read_straggler_bytes, batcher.dec_reqs,
              batcher.dec_cpu_reqs)
    gathered = []
    submit = batcher.submit_decode

    def spy(ec_impl, sinfo, have, want, cb):
        gathered.append({s: bytes(b) for s, b in have.items()})
        return submit(ec_impl, sinfo, have, want, cb)
    batcher.submit_decode = spy
    holder = HeldReplies(pg)
    try:
        holder.shards = held
        got = io.read("fast0", len(obj))
        assert got == obj
        # completed on exactly the k shards that were not held back
        assert len(gathered) == 1
        have = gathered[0]
        assert sorted(have) == [i for i in range(N) if i not in held]
        assert sum(1 for i in range(K) if i not in have) == missing
        # each is the plain encoder's shard, and the plain decoder
        # gives the object from those same k
        want = ref.shards_of(obj, K, M, UNIT)
        assert all(have[i] == want[i] for i in have)
        assert ref.decode(have, K, M, UNIT) == obj
        assert backend.fast_reads == before[0] + 1
        assert backend.fast_read_stragglers == before[1]   # none yet
        # the stragglers arrive: dropped, counted, nothing else moves
        for _ in range(200):
            if len(holder.stash) == M:
                break
            time.sleep(0.05)
        assert holder.release() == M
        assert backend.fast_read_stragglers == before[1] + M
        assert backend.fast_read_straggler_bytes == \
            before[2] + M * STRIPES * UNIT
        assert not backend._fast_read_tails
        assert backend.fast_reads == before[0] + 1
        assert io.read("fast0", len(obj)) == obj
    finally:
        holder.undo()
        batcher.submit_decode = submit
    # rebuilt on the device lane, never on the CPU twin
    assert batcher.dec_reqs > before[3]
    assert batcher.dec_cpu_reqs == before[4]
    out = fast_pool["osd"]._exec_command({"prefix": "dump_device"})[2]
    assert out["ec_reads"]["fast_reads"] >= backend.fast_reads
    assert out["ec_reads"]["fast_read_stragglers"] >= M
    assert out["recovery_rows_misses"] >= 1


def test_a_shard_that_errors_is_never_counted_towards_the_k(fast_pool):
    """Answers that report an error do not complete the read: with
    four shards held back and one of the rest failing, only 7 good
    answers are in, and the read waits for a held shard."""
    from ceph_tpu.osd import ecbackend
    pg = fast_pool["pg"]
    backend = pg.backend
    done = []
    rop = ecbackend._ReadOp(
        backend.new_tid(), "fast0", 0, UNIT, {s: 0 for s in range(N)},
        lambda received, errors: done.append((dict(received),
                                              dict(errors))), need=K)
    with pg.lock:
        backend.in_flight_reads[rop.tid] = rop
        for s in range(K - 1):
            backend._read_piece(rop, s, b"x" * UNIT, 0)
        backend._read_piece(rop, K - 1, b"", -5)
        assert not done and rop.tid in backend.in_flight_reads
        backend._read_piece(rop, K, b"y" * UNIT, 0)
    assert len(done) == 1
    received, errors = done[0]
    assert sorted(received) == list(range(K - 1)) + [K] and not errors
    assert K - 1 not in received


# -- (c) the benchmark's files ------------------------------------------------
def bench() -> dict:
    return spec.benchmark()


def test_the_configuration_is_the_headline_pool_but_for_one_value():
    rows = {c["name"]: c for c in bench()["configs"]}
    row = rows["ec_k8m4_13osd_fastread"]
    assert bench()["configs"][-1] == row
    assert len(row["source"]) <= 200 and len(row["why"]) <= 200
    assert "fast_read" in row["source"]
    assert row["source"] != rows["ec_k8m4_13osd"]["source"]
    with open(os.path.join(ROOT, row["file"]), encoding="utf-8") as fh:
        fast = json.load(fh)
    with open(os.path.join(ROOT, rows["ec_k8m4_13osd"]["file"]),
              encoding="utf-8") as fh:
        base = json.load(fh)
    assert fast["name"] == row["name"] and fast["source"] == row["source"]
    assert sorted(fast["reduced"]) == sorted(row["reduced"]) == \
        sorted(base["reduced"])
    texts = {"name", "source", "deployment", "conf_why", "geometry_why",
             "guarantees", "assumed", "conf"}
    assert set(fast) == set(base)
    for key in set(base) - texts:
        assert fast[key] == base[key], key
    conf = dict(fast["conf"])
    assert conf.pop("osd_pool_default_ec_fast_read") is True
    assert conf == base["conf"]
    assert "osd_pool_default_ec_fast_read" in fast["conf_why"]
    assert len(fast["guarantees"]) == 3
    assert fast["guarantees"][0] == base["guarantees"][0]
    assert fast["guarantees"][2] == base["guarantees"][2]
    assert "whichever k" in fast["guarantees"][1]
    assert set(base["assumed"]) < set(fast["assumed"])
    profile = fast["pool"]["profile"]
    assert (profile["k"], profile["m"], profile["w"]) == (8, 4, 8)
    assert fast["stripe_unit"] == 4096
    assert spec.reference_name(fast) == "reed_sol_van_w8"
    # the option is the program's, and off unless a deployment sets it
    from ceph_tpu.utils.config import Config
    assert Config({})["osd_pool_default_ec_fast_read"] is False


def test_the_cell_is_that_pool_under_the_read_cells_traffic():
    cell = spec.Cell(CELL)
    assert bench()["workloads"][-1]["name"] == CELL
    assert cell.chips == 1 and len(cell.row["why"]) <= 200
    assert cell.row["config"] == "ec_k8m4_13osd_fastread"
    assert cell.row["traffic"] == "radosbench_seq_4m_qd16"
    assert cell.state == {}                  # nothing is killed
    assert [w["name"] for w in bench()["workloads"]
            if w["config"] == cell.row["config"]] == [CELL]
    from harness import faults
    assert faults.control_for(cell) == "read_reply_altered"


@pytest.mark.parametrize("metric", [
    "pg.decode_share", "pg.shard_read_share", "batcher.device_share",
    "batcher.reqs_per_dispatch", "kernel.gf_roofline",
    "dispatch.programs_per_signature", "decode.unwanted_row_share",
    "msgr.rx_copies_per_byte", "decode.new_binding_share",
    "decode.solves_per_request"])
def test_the_metrics_that_find_something_to_read_list_the_cell(metric):
    row = [m for m in bench()["per_layer"] if m["name"] == metric][0]
    assert row["workloads"][-1] == CELL
    if metric.startswith("decode.new") or metric.startswith("decode.sol"):
        assert row["workloads"] == READ_CELLS
        # appended after msgr.rx_copies_per_byte; later entries follow
        names = [m["name"] for m in bench()["per_layer"]]
        after = names.index("msgr.rx_copies_per_byte") + 1
        assert names[after:after + 2] == ["decode.new_binding_share",
                                          "decode.solves_per_request"]
    reader = spec.metric_reader(metric)
    assert (reader.SOURCE, reader.LAYER, reader.MOVES) == \
        (row["source"], row["layer"], row["moves"])
    assert metric in [m["name"] for m in spec.Cell(CELL).per_layer()]


def spans_of(sections) -> dict:
    """The plain form of a trace: a 10 s window and one section a row
    of (name, start s, keywords)."""
    line = [(name, s * 1e9, 1e6, dict(meta)) for name, s, meta in sections]
    return {"lines": [[(trace.WINDOW_SPAN, 1e9, 10e9, {})], line],
            "device_ops": []}


CALL = "dispatch.call"


@pytest.mark.parametrize("sections,want", [
    ([(CALL, 2, {"bound": "new"}), (CALL, 3, {"bound": "hit"}),
      (CALL, 4, {"bound": "hit"}), (CALL, 5, {"bound": "hit"})], 25.0),
    ([(CALL, 2, {"bound": "hit"})], 0.0),
    # before the window (the warm-up's first calls): not counted
    ([(CALL, 0.5, {"bound": "new"}), (CALL, 2, {"bound": "hit"})], 0.0),
    # a call that carries no ``bound`` (a tree before PR 34) is no call
    # of a binding
    ([(CALL, 2, {"kernel": "gf_mxu_pallas"}),
      (CALL, 3, {"bound": "new"})], 100.0),
    ([(CALL, 2, {"kernel": "gf_mxu_pallas"})], None),
    ([], None)])
def test_new_binding_share_reads_the_windows_calls(sections, want):
    reader = spec.metric_reader("decode.new_binding_share")
    got = reader.read({"spans": spans_of(sections)})
    assert got == want if want is None else got == pytest.approx(want)


SOLVE = "ec.solve_rows"


DISPATCH = ("batcher.dispatch", 2, {"lane": "dec"})


@pytest.mark.parametrize("sections,reqs,want", [
    ([(SOLVE, 2, {"k": 8}), (SOLVE, 3, {"k": 8}), DISPATCH], 4, 0.5),
    ([DISPATCH], 4, 0.0),                   # every request hit the cache
    ([(SOLVE, 0.5, {"k": 8}), DISPATCH], 4, 0.0),   # solved in set-up
    ([(SOLVE, 2, {"k": 8})], 0, None),      # no decode in the window
    ([], 4, None)])                         # a trace without sections
def test_solves_per_request_reads_the_windows_solves(sections, reqs, want):
    reader = spec.metric_reader("decode.solves_per_request")
    ctx = {"spans": spans_of(sections),
           "lanes_window": {"lanes": {"decode": {"reqs": reqs}}}}
    got = reader.read(ctx)
    assert got == want if want is None else got == pytest.approx(want)


def test_solves_per_request_on_a_tree_without_the_shared_cache(
        monkeypatch):
    from ceph_tpu.ops import engine
    monkeypatch.delattr(engine, "RecoveryRowsCache")
    ctx = {"spans": spans_of([]),
           "lanes_window": {"lanes": {"decode": {"reqs": 4}}}}
    assert spec.metric_reader(
        "decode.solves_per_request").read(ctx) is None


CHILD = """
import os, sys
sys.path.insert(0, os.path.join({root!r}, "benchmark"))
sys.path.insert(0, {root!r})
from ceph_tpu.ops import jax_engine as je
je.gf8_kernel = lambda: "gf_mxu_pallas"
je.JaxBackend.gf8_fast_path = lambda self: True
program = je.rows_program
je.rows_program = lambda kernel, w, packetsize=0, donate=False: \\
    program(kernel, w, packetsize, donate, interpret=True)
import run
code = run.main(["--workload", {cell!r}, "--seed", "36", "--seconds", "3",
                 "--trace", "1", "--rehearsal"])
sys.stdout.flush()
os._exit(code)
"""


def test_rehearsal_of_the_cell_is_correct_and_reports_the_new_metrics():
    """``benchmark/run.py --workload k8m4.fast_read_4m --seed 36
    --seconds 3 --trace 1 --rehearsal`` in a process of its own: every
    path of the harness on the cell, on a CPU at a tiny size.  Off a
    TPU the byte lane is the runtime-argument bit-plane program, which
    binds nothing; what the chip runs is the row-operand program, so
    the child steers the kernel chooser to the Pallas family, built
    for the interpreter, before it hands over to ``run.main`` (from
    here: the program has no option for it).  One device, as the cell
    has one chip: conftest's eight virtual ones would make a mesh.  At
    a low priority: 13 daemons and a load generator for half a minute
    would otherwise starve the heartbeats of tier-1's other workers."""
    out = subprocess.run(
        ["nice", "-n", "15", sys.executable, "-c",
         CHILD.format(root=ROOT, cell=CELL)],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu", XLA_FLAGS=""))
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    info, result = json.loads(lines[-2])["info"], json.loads(lines[-1])
    assert result["rehearsal"] and "not_a_chip_run" in result
    assert result["correct"], (result["compared"], out.stderr[-6000:])
    assert result["attempted"] > 0 and result["failed"] == 0
    lanes = info["lanes_window"]["lanes"]
    assert lanes["decode"]["reqs"] > 0
    assert lanes["decode"]["twin_reqs"] == 0
    assert lanes["encode"]["reqs"] == 0      # a window of reads
    assert set(info["lanes_window"]["kernels"]) == {"gf_mxu_pallas"}
    assert info["programs_warmed_at_batch_sizes"] > 0
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["dispatch.compiles_in_window"] == 0
    assert metrics["batcher.device_share"] == 100.0
    assert metrics["dispatch.programs_per_signature"] < 1.0
    assert 0.0 <= metrics["decode.new_binding_share"] <= 100.0
    # solved once a process: far fewer systems than requests
    assert 0.0 <= metrics["decode.solves_per_request"] < 1.0
    assert 0.0 < metrics["decode.unwanted_row_share"] < 100.0
    assert metrics["pg.decode_share"] > 0
