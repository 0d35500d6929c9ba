#!/usr/bin/env python3
"""The readings that the limits of ``correct`` are set from.

    python3 chipbench/control.py --workload fleet1k-steady --seconds 10 --seeds 1 2 3
    python3 chipbench/control.py --workload fleet1k-steady --seconds 10 --seeds 1 2 3 --faults state_unchanged always_partial

For each seed, in this one process, one run's window at the cell's own size
(``run.simulate``), then its sampled rounds compared twice: once as the
program produced them (the lower reading: the largest over the seeds), and
once with the control, the reference computed in bfloat16, in the pack's
place (the upper reading: the smallest over the seeds).  Then, for each of
``--faults`` in turn, the same seeds with that fault of ``faults.py``
planted (and taken out after), whose own readings are its upper ones.
The benchmark's own runs never run this.  Prints one JSON line per run and
a summary per reading.
"""
import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.abspath(__file__)))
import run  # noqa: E402

NUMBERS = ("pack_cost_gap", "pack_misplaced", "pack_mismatch_share",
           "plan_violations", "ensemble_wrong", "unplaced_rounds")
#: how much of the sample the comparison judged
COVERAGE = ("packs_checked", "rows_not_judged", "choices_judged",
            "choices_not_judged")


def readings(name: str, seeds, seconds: float, rehearse: bool = False,
             control: bool = True):
    from chipbench import check
    out = []
    for seed in seeds:
        r = run.simulate(name, seed, seconds, traced=False, rehearse=rehearse)
        program = run.judge(r)
        line = {"seed": seed, "rounds": r["attempted"],
                "rounds_checked": program["rounds_checked"],
                "tasks_checked": program["tasks_checked"],
                "program": {k: program[k] for k in NUMBERS + COVERAGE}}
        if control:
            ctl = run.judge(r, packer=check.bf16_packer)
            line["control"] = {k: ctl[k] for k in NUMBERS + COVERAGE
                               if k.startswith("pack")}
        print(json.dumps(line), flush=True)
        out.append(line)
    return out


def main(argv=None) -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, default=10.0)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--faults", nargs="*", default=[],
                    help="faults of faults.py to plant, one after another")
    args = ap.parse_args(argv)
    run._paths()
    from chipbench.faults import FAULTS, SPOT_FAULTS

    def summary(fault, lines, upper):
        print(json.dumps({
            "workload": args.workload, "fault": fault,
            "lower": {k: max(x["program"][k] for x in lines)
                      for k in NUMBERS},
            "upper": {k: min(x[upper][k] for x in lines)
                      for k in NUMBERS if k in lines[0][upper]}}),
            flush=True)

    summary(None, readings(args.workload, args.seeds, args.seconds),
            "control")
    for fault in args.faults:
        saved = []

        def put(obj, name, value):
            saved.append((obj, name, getattr(obj, name)))
            setattr(obj, name, value)

        {**FAULTS, **SPOT_FAULTS}[fault](put)
        try:
            lines = readings(args.workload, args.seeds, args.seconds,
                             control=False)
        finally:
            for obj, name, value in reversed(saved):
                setattr(obj, name, value)
        summary(fault, lines, "program")  # the fault's readings are its own


if __name__ == "__main__":
    main()
