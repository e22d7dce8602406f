#!/usr/bin/env python3
"""Run one cell of the benchmark once.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Set-up, a measured window of ``--seconds`` (closing at the end of the round
or burst under way), then the comparison with the plain reference.  Every
line but the last is diagnostics, on standard error, ending with each
number compared beside its limit; the last line of standard output is the
result object.  With ``--trace 0`` its metrics are the cell's end-to-end
metrics, with ``--trace 1`` its per-layer metrics read from a profiler
trace of the window.  Exits non-zero, printing no result, where JAX finds
no TPU or fewer chips than the cell asks for.
"""

from __future__ import annotations

import argparse
import pathlib
import shutil
import sys

sys.path.insert(0, str(pathlib.Path(__file__).resolve().parent))

import harness  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    try:
        cell = harness.Cell.find(args.workload)
    except (KeyError, FileNotFoundError) as e:
        print(f"bench: {e}", file=sys.stderr)
        return 2
    out_dir = harness.BENCH / "out"
    try:
        out = harness.run_cell(
            cell, args.seed, args.seconds, bool(args.trace), out_dir=out_dir,
            log=lambda msg: print(msg, file=sys.stderr, flush=True))
    except harness.NoDevice as e:
        print(f"bench: {e}", file=sys.stderr)
        return 3
    finally:
        shutil.rmtree(out_dir, ignore_errors=True)
    harness.emit(out)
    return 0


if __name__ == "__main__":
    sys.exit(main())
