#!/usr/bin/env python3
"""Readings from which the limits of ``correct`` are set; not part of a
benchmark run.

    python3 bench/control.py --workload <cell> --seeds 1,2,3 --seconds 5 \\
        [--fault unchanged|half_batch|altered]

For each seed, in one process: set-up, a short window at the cell's own
load, and every number ``correct`` compares, computed twice: for the
program (its lower reading) and for the control, the plain reference
computed in bfloat16 and put in the program's place (its upper reading).
With ``--fault`` the timed path is broken underneath (see
``harness.FAULTS``) and the program's numbers are the fault's readings.
One JSON line per seed on standard output.
"""

from __future__ import annotations

import argparse
import json
import pathlib
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True)
    ap.add_argument("--seconds", type=float, default=5.0)
    ap.add_argument("--fault", choices=harness.FAULTS, default=None)
    args = ap.parse_args(argv)
    cell = harness.Cell.find(args.workload)
    for seed in (int(s) for s in args.seeds.split(",")):
        out = harness.run_cell(
            cell, seed, args.seconds, False, fault=args.fault, control=True,
            log=lambda msg: print(msg, file=sys.stderr, flush=True))
        print(json.dumps({
            "workload": cell.name, "seed": seed, "fault": args.fault,
            "correct": out["correct"], "program": out["program_readings"],
            "control": out["control"], "attempted": out["attempted"],
            "failed": out["failed"], "metrics": out["metrics"],
            "diagnostics": out["_diagnostics"]}), flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
