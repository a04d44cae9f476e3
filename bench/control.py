#!/usr/bin/env python3
"""Readings the judge's limits are set from: the program's numbers and the
bfloat16 control's, on many seeds of one cell, in one process.

    python3 bench/control.py --workload <cell> --seeds 12 --seconds 10

Each seed runs the cell as ``bench/run.py`` does, at the cell's own load
and a window of ``--seconds``, and the judge compares the program's
decisions with the float64 reference and, at the same sampled decisions,
the reference computed in bfloat16 (the control) put in the program's
place.  One JSON line per seed goes to standard output and to
``chiprun_out/control_<cell>.jsonl``; the last line gives, per number, the
largest program reading (the lower reading) and the smallest control
reading (the upper one).
"""
from __future__ import annotations

import argparse
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import judge, spec  # noqa: E402
from bench.run import Run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", type=int, default=12)
    ap.add_argument("--first-seed", type=int, default=3_000_000_000)
    ap.add_argument("--seconds", type=float, default=10.0)
    args = ap.parse_args(argv)
    cell = spec.load_cell(args.workload)
    os.makedirs("chiprun_out", exist_ok=True)
    path = os.path.join("chiprun_out", f"control_{args.workload}.jsonl")
    lower = {n: 0.0 for n in judge.NUMBERS}
    upper = {n: float("inf") for n in judge.SAMPLED}
    with open(path, "w") as fh:
        for i in range(args.seeds):
            seed = args.first_seed + 7919 * i
            run = Run(cell, seed, args.seconds, False, control=True,
                      log=lambda *a: None)
            out = run.execute()
            prog = {n: c["value"] for n, c in out["checks"].items()}
            for n, v in prog.items():
                lower[n] = max(lower[n], v)
            for n, v in run.control_numbers.items():
                upper[n] = min(upper[n], v)
            line = {"seed": seed, "correct": out["correct"],
                    "attempted": out["attempted"], "program": prog,
                    "control": run.control_numbers,
                    "control_correct": judge.is_correct(
                        run.control_numbers, cell.config["limits"])}
            print(json.dumps(line), flush=True)
            fh.write(json.dumps(line) + "\n")
        summary = {"lower": lower, "upper": upper}
        print(json.dumps(summary), flush=True)
        fh.write(json.dumps(summary) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
