#!/usr/bin/env python3
"""Knee sweep of a paced cell: the highest offered rate at which the
backlog of due but undecided slots does not grow across the window.

    python3 bench/sweep.py --workload <cell> --rates 60,80,100 --seconds 20

Runs the cell once per rate, in one process, on one seed, with the rate
put in place of the traffic file's ``rate_jobs_per_s``, and prints one
JSON line per rate: the backlog's mean over the window's first and last
quarters and its least value in the last quarter (0: the backlog still
empties, the rate is sustained), the latency quantiles and the compiles
met inside the window.  The rate chosen is written into the traffic file
by hand; the benchmark never searches.
"""
from __future__ import annotations

import argparse
import dataclasses
import json
import os
import sys

sys.path.insert(0, os.path.dirname(os.path.dirname(os.path.abspath(
    __file__))))

from bench import spec  # noqa: E402
from bench.run import Run  # noqa: E402


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--rates", required=True,
                    help="comma-separated offered rates, jobs/s")
    ap.add_argument("--seconds", type=float, default=20.0)
    ap.add_argument("--seed", type=int, default=4_000_000_007)
    ap.add_argument("--check-sample", type=int, default=20,
                    help="decisions the judge solves again per rate")
    args = ap.parse_args(argv)
    base = spec.load_cell(args.workload)
    for rate in (float(r) for r in args.rates.split(",")):
        cell = dataclasses.replace(
            base, traffic=dict(base.traffic, rate_jobs_per_s=rate),
            config=dict(base.config, check_sample=args.check_sample))
        run = Run(cell, args.seed, args.seconds, False,
                  log=lambda m: print(m, file=sys.stderr, flush=True))
        out = run.execute()
        back = run.feed.backlog()
        q = max(len(back) // 4, 1)
        print(json.dumps({"rate_jobs_per_s": rate,
                          "correct": out["correct"],
                          "metrics": {k: v["value"] for k, v in
                                      out["metrics"].items()},
                          "backlog_first_quarter": sum(back[:q]) / q,
                          "backlog_last_quarter": sum(back[-q:]) / q,
                          "backlog_max": max(back),
                          "backlog_last_quarter_min": min(back[-q:]),
                          "compiles_in_window": run.counter.compiles}),
              flush=True)
    return 0


if __name__ == "__main__":
    sys.exit(main())
