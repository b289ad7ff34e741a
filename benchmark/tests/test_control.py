"""`correct` has to come out false when the timed path is broken.

    python3 -m pytest benchmark/tests/test_control.py -q      (CPU, ~3 min)

Skips the harness's look for a chip (``rehearsal``: tiny objects, a 2 s
window, XLA on the CPU) and drives the rest of a run: cluster, load
generator, window, drain, reference check.  Once sound, which has to
come out correct, and once with each fault a cell can have planted
under the timed path (harness/faults.py): an answer altered where it is
produced, which is the kernel's output for the encode, delta and decode
lanes and the primary's reply for a healthy read.  The other faults of
the builder's list (a step that returns its state unchanged, half a
batch left out, the exchange between chips left out) have no
counterpart in a one-chip object store.  On the chip the same control
is run at the cell's own size by ``benchmark/control.py``.
"""
import argparse
import copy
import os
import sys

import pytest

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, os.path.dirname(HERE))
os.environ.setdefault("JAX_PLATFORMS", "cpu")

import run  # noqa: E402
from harness import faults, spec  # noqa: E402


def bench_with_kept_mix() -> dict:
    """BENCHMARK.json and, in memory only, the cell PR 29 took out as
    too noisy to hold to a bound (PERF.md, sections 2 and 7).  Its mix
    ``traffic/fio_randwrite_4k_qd32.json`` is kept for its return, and
    is the only traffic on the parity-delta lane and on the generator's
    overwrite class, so the harness's side of both stays driven."""
    bench = copy.deepcopy(spec.benchmark())
    bench["workloads"].append({
        "name": "k4m2.randwrite_4k", "config": "ec_k4m2_7osd",
        "traffic": "fio_randwrite_4k_qd32", "chips": 1})
    for metric in bench["per_layer"]:
        if "k8m4.write_4m" in metric.get("workloads", []):
            metric["workloads"].append("k4m2.randwrite_4k")
    return bench


BENCH = bench_with_kept_mix()
CELLS = [w["name"] for w in BENCH["workloads"]]


def drive(workload: str, fault=None, seed: int = 2147483659) -> dict:
    args = argparse.Namespace(workload=workload, seed=seed, seconds=2.0,
                              trace=0, rehearsal=True, record_trace=None)
    plant = faults.Planter(fault) if fault else None
    try:
        result = run.run_cell(args, plant=plant, bench=BENCH)
    finally:
        if plant:
            plant.undo()
    result.pop("_info")
    return result


@pytest.mark.parametrize("workload", CELLS)
def test_sound_run_is_correct(workload):
    result = drive(workload)
    assert result["correct"], result["compared"]
    assert result["attempted"] > 0 and result["failed"] == 0
    assert result["rehearsal"] and "not_a_chip_run" in result
    assert all(v["value"] <= v["limit"] for v in result["compared"].values())


@pytest.mark.parametrize("workload", CELLS)
def test_control_fault_is_caught(workload):
    fault = faults.control_for(spec.Cell(workload, bench=BENCH))
    result = drive(workload, fault)
    assert not result["correct"], (fault, result["compared"])
    assert any(v["value"] > v["limit"] for v in result["compared"].values())


def test_an_empty_window_is_not_correct():
    from harness import check
    assert not check.correct({"window_empty": (1, 0)})
    assert check.correct({"window_empty": (0, 0), "ops_failed": (0, 0)})
