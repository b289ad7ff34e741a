"""The cell cauchy_k10m4.degraded_read_4m and its two metrics (ISSUE 34).

What a CPU can hold of the cell: that ``BENCHMARK.json`` and the files
it names say what the issue asks (the pool of ``cauchy_k10m4.write_4m``,
the traffic of ``k4m2.degraded_read_4m``, osd.14 and osd.13 killed with
their data), that the two new readers compute what their docstrings
say and give nothing on a tree without the counters, and (``slow``:
15 OSD daemons in one interpreter take this sandbox's 8 cores for two
minutes, and starve the heartbeats of tier-1's other workers) the whole
harness on the cell at a tiny size.
"""
import json
import os
import subprocess
import sys
import types

import pytest

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
sys.path.insert(0, os.path.join(ROOT, "benchmark"))

from harness import spec, trace  # noqa: E402

CELL = "cauchy_k10m4.degraded_read_4m"
DEGRADED = ["k4m2.degraded_read_4m", CELL]


def bench() -> dict:
    return spec.benchmark()


def test_the_cell_is_the_write_cells_pool_under_the_read_cells_traffic():
    cell = spec.Cell(CELL)
    assert cell.chips == 1
    assert cell.row["config"] == "ec_cauchy_k10m4_15osd_2down"
    healthy = spec.Cell("cauchy_k10m4.write_4m").config
    # the same pool key for key: only the state and its wording differ
    for key in ("osds", "mons", "pg_num", "store_medium", "pool",
                "stripe_unit", "conf"):
        assert cell.config[key] == healthy[key], key
    assert cell.row["traffic"] == spec.Cell("k4m2.degraded_read_4m") \
        .row["traffic"] == "radosbench_seq_4m_qd16"
    # the fourth cell; later PRs append theirs after it
    assert [w["name"] for w in bench()["workloads"]][3] == CELL
    assert len(cell.row["why"]) <= 200


def test_the_degraded_deployment_is_a_configuration_of_its_own():
    rows = {c["name"]: c for c in bench()["configs"]}
    row = rows["ec_cauchy_k10m4_15osd_2down"]
    assert bench()["configs"][3] == row     # later PRs append after it
    assert row["source"] != rows["ec_cauchy_k10m4_15osd"]["source"]
    assert row["file"] != rows["ec_cauchy_k10m4_15osd"]["file"]
    assert len(row["source"]) <= 200 and len(row["why"]) <= 200
    with open(os.path.join(ROOT, row["file"]), encoding="utf-8") as fh:
        config = json.load(fh)
    assert config["name"] == row["name"]
    assert config["source"] == row["source"]
    assert sorted(config["reduced"]) == sorted(row["reduced"])
    assert [w["name"] for w in bench()["workloads"]
            if w["config"] == row["name"]] == [CELL]
    assert spec.reference_name(config) == "cauchy_good_w8"


def test_the_cell_kills_osd_14_then_osd_13_with_their_data():
    cell = spec.Cell(CELL)
    assert cell.state["after_populate"] == [
        {"kill_osd_with_data": "last"}, {"kill_osd_with_data": 13}]
    osds = int(cell.config["osds"])
    # what the configuration states is what the cell's file does
    assert cell.config["osds_down"] == [osds - 1, 13]
    profile = cell.config["pool"]["profile"]
    assert osds - 1 == 14
    # 12 shards survive of 14, min_size is k+1: every PG stays active
    assert int(profile["k"]) + int(profile["m"]) - 2 > int(profile["k"])
    assert float(cell.config["conf"]["mon_osd_down_out_interval"]) >= 600


@pytest.mark.parametrize("metric,cells", [
    ("pg.decode_share", DEGRADED), ("pg.shard_read_share", DEGRADED),
    ("batcher.device_share", None), ("batcher.reqs_per_dispatch", None),
    ("kernel.gf_roofline", None),
    ("dispatch.pad_share", ["cauchy_k10m4.write_4m", CELL]),
    ("dispatch.programs_per_signature", DEGRADED),
    ("decode.unwanted_row_share", DEGRADED)])
def test_the_metrics_that_find_something_to_read_list_the_cell(metric,
                                                               cells):
    row = [m for m in bench()["per_layer"] if m["name"] == metric][0]
    # PR 34 appended the cell; a later PR's cells come after it
    assert CELL in row["workloads"]
    if cells is not None:
        assert row["workloads"][:len(cells)] == cells
    else:
        assert row["workloads"].index(CELL) == 3
    assert row["moves"] == "throughput"
    reader = spec.metric_reader(metric)
    assert (reader.SOURCE, reader.LAYER, reader.MOVES) == \
        (row["source"], row["layer"], row["moves"])
    assert metric in [m["name"] for m in spec.Cell(CELL).per_layer()]


def spans_of(dispatches) -> dict:
    """The plain form of a trace: a 10 s window and one
    ``batcher.dispatch`` section a row of (start s, keywords)."""
    line = [("batcher.dispatch", s * 1e9, 1e6, dict(meta))
            for s, meta in dispatches]
    return {"lines": [[(trace.WINDOW_SPAN, 1e9, 10e9, {})], line],
            "device_ops": []}


@pytest.mark.parametrize("dispatches,want", [
    # a read that lost two data shards of ten and one that lost one:
    # 4 rows out a stripe, 7 stripes; 2 and 1 of them wanted
    ([(2, {"lane": "dec", "rows_out": 28, "rows_wanted": 14}),
      (3, {"lane": "dec", "rows_out": 28, "rows_wanted": 7})], 62.5),
    # the k4m2 cell: 2 rows out, 1 wanted
    ([(2, {"lane": "dec", "rows_out": 512, "rows_wanted": 256})], 50.0),
    # before the window: not counted
    ([(0.5, {"lane": "dec", "rows_out": 28, "rows_wanted": 28}),
      (2, {"lane": "dec", "rows_out": 28, "rows_wanted": 14})], 50.0),
    # encode dispatches carry no rows; a parent's decode ones neither
    ([(2, {"lane": "enc", "stripes": 8})], None),
    ([(2, {"lane": "dec", "stripes": 7})], None),
    ([], None)])
def test_unwanted_row_share_reads_the_windows_decode_dispatches(
        dispatches, want):
    reader = spec.metric_reader("decode.unwanted_row_share")
    got = reader.read({"spans": spans_of(dispatches)})
    assert got == want if want is None else got == pytest.approx(want)


@pytest.mark.parametrize("built,signatures,want", [
    (9, 27, 9 / 27),            # rows an operand: programs follow shapes
    (135, 27, 5.0),             # the parent's shape, had it the counter
    (9, 0, None),               # no decode dispatched: nothing to divide
    (None, 27, None)])          # a tree without the counter
def test_programs_per_signature_reads_the_programs_counters(
        monkeypatch, built, signatures, want):
    from ceph_tpu.ec.plugins import tpu
    from ceph_tpu.osd.batcher import EncodeBatcher
    backend = types.SimpleNamespace()
    if built is not None:
        backend.row_programs_built = built
    monkeypatch.setattr(tpu, "shared_backend", lambda: backend)
    monkeypatch.setattr(EncodeBatcher, "_dec_signatures",
                        set(range(signatures)))
    reader = spec.metric_reader("dispatch.programs_per_signature")
    got = reader.read({})
    assert got == want if want is None else got == pytest.approx(want)


def test_programs_per_signature_on_a_batcher_without_the_set(monkeypatch):
    from ceph_tpu.osd.batcher import EncodeBatcher
    monkeypatch.delattr(EncodeBatcher, "_dec_signatures")
    assert spec.metric_reader(
        "dispatch.programs_per_signature").read({}) is None


# -- the whole harness on the cell -------------------------------------------
CHILD = """
import os, sys
sys.path.insert(0, os.path.join({root!r}, "benchmark"))
sys.path.insert(0, {root!r})
from ceph_tpu.ops import jax_engine as je
je.packet_kernel = lambda packetsize: "packet_mxu_pallas"
program = je.rows_program
je.rows_program = lambda kernel, w, packetsize=0, donate=False: \
    program(kernel, w, packetsize, donate, interpret=True)
import run
code = run.main(["--workload", {cell!r}, "--seed", "1", "--seconds", "2",
                 "--trace", "1", "--rehearsal"])
sys.stdout.flush()
os._exit(code)
"""


@pytest.mark.slow
def test_rehearsal_reads_every_object_and_reports_the_new_metrics():
    """``benchmark/run.py --workload cauchy_k10m4.degraded_read_4m
    --seed 1 --seconds 2 --trace 1 --rehearsal`` in a process of its
    own.  On a CPU the packet lane is the static XOR chain, which
    compiles per erasure signature and batch bucket and leaves a 2 s
    window empty more often than not; what the chip runs is the
    row-operand program, so the child steers the kernel chooser to
    the Pallas family, built for the interpreter, before it hands over to
    ``run.main`` (from here: the program has no option for it)."""
    out = subprocess.run(
        [sys.executable, "-c", CHILD.format(root=ROOT, cell=CELL)],
        capture_output=True, text=True, timeout=900, cwd=ROOT,
        env=dict(os.environ, JAX_PLATFORMS="cpu"))
    assert out.returncode == 0, out.stderr[-4000:]
    lines = [ln for ln in out.stdout.splitlines() if ln.startswith("{")]
    info, result = json.loads(lines[-2])["info"], json.loads(lines[-1])
    assert result["rehearsal"] and "not_a_chip_run" in result
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    lanes = info["lanes_window"]
    assert lanes["lanes"]["decode"]["reqs"] > 0
    assert lanes["lanes"]["decode"]["twin_reqs"] == 0
    assert set(lanes["kernels"]) == {"packet_mxu_pallas"}, lanes
    metrics = {k: v["value"] for k, v in result["metrics"].items()}
    assert metrics["dispatch.compiles_in_window"] == 0
    # every signature the load met ran the executable of its shape
    assert 0 < metrics["dispatch.programs_per_signature"] <= 1.5
    # a read gathers 10 of 14 shards: 4 rows out, 1 or 2 wanted
    assert 50.0 <= metrics["decode.unwanted_row_share"] <= 75.0
    for name in ("pg.decode_share", "pg.shard_read_share",
                 "batcher.device_share", "batcher.reqs_per_dispatch",
                 "dispatch.pad_share"):
        assert name in metrics, sorted(metrics)
