#!/usr/bin/env python3
"""The control: the cell's own load with one guarantee broken must come
out as NOT correct.

    python3 benchmark/control.py --workload <cell> --seeds 1,2,3 --seconds 8

Runs the cell once per seed in this one process (one JAX start), each
time with the cell's control fault planted under the timed path
(harness/faults.py), and prints what each number read.  Exits 0 only
if every run came out not correct.  The benchmark's own runs never
call this.
"""
import argparse
import json
import os
import sys

HERE = os.path.dirname(os.path.abspath(__file__))
sys.path.insert(0, HERE)
sys.path.insert(0, os.path.dirname(HERE))


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=8)
    ap.add_argument("--fault", default=None)
    ap.add_argument("--rehearsal", action="store_true")
    ns = ap.parse_args(argv)
    if ns.rehearsal:
        os.environ.setdefault("JAX_PLATFORMS", "cpu")
    import run
    from harness import faults, spec
    fault = ns.fault or faults.control_for(spec.Cell(ns.workload))
    rows = []
    for seed in (int(s) for s in ns.seeds.split(",")):
        args = argparse.Namespace(workload=ns.workload, seed=seed,
                                  seconds=ns.seconds, trace=0,
                                  rehearsal=ns.rehearsal, record_trace=None)
        plant = faults.Planter(fault)
        try:
            result = run.run_cell(args, plant=plant)
        finally:
            plant.undo()
        result.pop("_info")
        failing = {k: v for k, v in result["compared"].items()
                   if v["value"] > v["limit"]}
        rows.append({"workload": ns.workload, "fault": fault, "seed": seed,
                     "correct": result["correct"], "failing": failing,
                     "attempted": result["attempted"]})
        print(json.dumps({"control": rows[-1]}), flush=True)
    caught = all(not r["correct"] for r in rows)
    print(json.dumps({"control_caught_on_every_seed": caught,
                      "workload": ns.workload, "fault": fault,
                      "seeds": len(rows)}), flush=True)
    return 0 if caught else 1


if __name__ == "__main__":
    try:
        code = main()
    except SystemExit as e:
        import run
        code = run.refusal_code(e)
    except BaseException:
        import traceback
        traceback.print_exc()
        code = 2
    sys.stdout.flush()
    sys.stderr.flush()
    os._exit(code)
