"""Readings of the control at a cell's own size: the plain reference put in
the program's place, computed in float32 (the precision below the
configuration's float64), compared as a run compares the program.

    python3 bench/tests/control.py --workload bbd-20k.newton --seeds 1 2 3

Prints, per seed, the number the cell's check compares and its limit: the
largest step residual for a Newton cell (over ``--steps`` steps), the
plan's solve residual for an analyze cell (the reference's symbolic
outputs are the reference, so they differ in nothing).  ``test_correct.py``
runs the same control through the harness at a test's size.
"""
from __future__ import annotations

import argparse
import json
import os
import sys

import numpy as np

ROOT = os.path.dirname(os.path.dirname(os.path.dirname(
    os.path.abspath(__file__))))
sys.path.insert(0, ROOT)

from bench import run_cell as rc  # noqa: E402
from bench.lib import patterns as P  # noqa: E402
from bench.lib import reference as R  # noqa: E402


def readings(cell: rc.Cell, seed: int, steps: int) -> float:
    if cell.traffic["kind"] == "newton":
        p = P.generate(cell.config, cell.traffic["pattern_seed"])
    else:           # the first unit's pattern
        p = P.Relabeller(cell.config, seed)(0)
    base = P.base_values(p, seed)
    if cell.traffic["kind"] == "newton":
        jitter = cell.traffic["value_jitter"]
        pairs = [(P.step_values(base, seed, i, jitter), P.rhs(p.n, seed, i))
                 for i in range(steps)]
    else:
        pairs = [(base, P.rhs(p.n, seed, 0))]
    return max(R.residual(p, v, R.solve(p, v, b, dtype=np.float32), b)
               for v, b in pairs)


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.split("\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, nargs="+", required=True)
    ap.add_argument("--steps", type=int, default=8)
    args = ap.parse_args()
    cell = rc.Cell.load(args.workload)
    limit = cell.config["residual_limit"]
    for seed in args.seeds:
        print(json.dumps({"workload": args.workload, "seed": seed,
                          "control_residual": readings(cell, seed,
                                                       args.steps),
                          "limit": limit}), flush=True)


if __name__ == "__main__":
    main()
