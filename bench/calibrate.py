#!/usr/bin/env python3
"""Readings that the output check's limits are set from.

    python bench/calibrate.py --workload <cell> --seconds 10 \
        --seeds 1,2,3 --control-seeds 4,5,6

One process runs ``run.py``'s whole run once per seed, each with a
fresh archive cache and a short window at the cell's own load: first
the program on ``--seeds``, then the control on ``--control-seeds``
(the plain reference at bfloat16 put in the program's place, through
the same output check and verdict).  Each run prints its result line
with the compared numbers and their limits.  Needs the cell's TPU
chips, like ``run.py``; the benchmark's own runs do not run it.
"""

from __future__ import annotations

import argparse
import sys

import run


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", required=True)
    ap.add_argument("--seeds", default="")
    ap.add_argument("--control-seeds", default="")
    args = ap.parse_args(argv)
    runs = [(s, False) for s in args.seeds.split(",") if s] + \
        [(s, True) for s in args.control_seeds.split(",") if s]
    for seed, control in runs:
        print(f"calibrate seed={seed} control={int(control)}", flush=True)
        rc = run.main(["--workload", args.workload, "--seed", seed,
                       "--seconds", args.seconds], control=control)
        if rc:
            return rc
    return 0


if __name__ == "__main__":
    sys.exit(main())
