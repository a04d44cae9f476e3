"""Reproduce the paper's headline comparison (Figs. 3-4) at configurable
scale and print a small ASCII chart.

    PYTHONPATH=src python examples/cluster_sim.py --jobs 60 --T 100

Or drive one of the sim-v2 scenarios (heterogeneous fleets, mid-run
cancellation, stragglers, U/L mis-estimation, 10x-paper scale):

    PYTHONPATH=src python examples/cluster_sim.py --scenario cancel
    PYTHONPATH=src python examples/cluster_sim.py --scenario straggler --quick

The scale scenario (alias ``scale10x``) accepts ``--scheduler`` to run a
single scheduler — including OASiS itself on the fused jit engine against
the device-resident price state, and the rl/ subsystem's learned policy
scheduler — and prints per-decision latency percentiles for plan-ahead
schedulers:

    PYTHONPATH=src python examples/cluster_sim.py --scenario scale10x \
        --scheduler oasis --quick
    PYTHONPATH=src python examples/cluster_sim.py --scenario scale10x \
        --scheduler learned --policy-ckpt runs/learned --quick

(``--policy-ckpt`` points at a ``repro.rl.train`` checkpoint directory
and is required for ``--scheduler learned`` — an untrained net is a
benchmark-harness pipeline exercise, not something to demo.)

The serving scenario streams an open-ended diurnal x bursty trace
through the rolling-window engine and prints sustained decisions/sec
plus the resident price-window bytes per scheduler:

    PYTHONPATH=src python examples/cluster_sim.py --scenario serving --quick

The churn scenario fails a seeded fraction of each server pool mid-run,
preempts the victims with checkpoint/restart cost, and prints the
utility-retention table (churned / churn-free utility per scheduler and
churn level — higher is better):

    PYTHONPATH=src python examples/cluster_sim.py --scenario churn --quick
"""
import argparse
import os
import sys

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

import numpy as np

from repro import obs as obslib
from repro.compile_cache import enable_compile_cache
from repro.sim import make_cluster, make_jobs, simulate
from repro.sim.scenarios import ALL_SCHEDULERS, SCENARIOS, run_scenario


def bar(v, vmax, width=40):
    n = int(width * v / max(vmax, 1e-9))
    return "#" * n


def run_figs(args):
    summaries = {}
    gaps = {}
    for seed in range(args.seeds):
        cluster = make_cluster(T=args.T, H=args.servers, K=args.servers)
        jobs = make_jobs(args.jobs, T=args.T, seed=seed, small=False)
        for name in ["oasis", "fifo", "drf", "rrh", "dorm"]:
            kw = dict(quantum=0) if name == "oasis" else {}
            r = simulate(cluster, jobs, scheduler=name, check=False, **kw)
            summaries.setdefault(name, []).append(r.summary())
            if r.target_gap:
                gaps.setdefault(name, []).extend(r.target_gap)

    def mean_of(name, key):
        vals = [s[key] for s in summaries[name] if s[key] is not None]
        return float(np.mean(vals)) if vals else float("nan")

    print(f"== per-scheduler episode summary "
          f"(mean of {args.seeds} seeds; Fig. 3) ==")
    means = {k: mean_of(k, "total_utility") for k in summaries}
    vmax = max(means.values())
    for k, v in sorted(means.items(), key=lambda kv: -kv[1]):
        print(f"{k:6s} {v:9.1f}  acc={mean_of(k, 'accept_rate'):5.2f} "
              f"comp={mean_of(k, 'completion_rate'):5.2f} "
              f"p50-lat={mean_of(k, 'p50_latency'):6.1f} "
              f"p95-lat={mean_of(k, 'p95_latency'):6.1f}  {bar(v, vmax)}")

    print("\n== completion - target time (mean abs; Fig. 4) ==")
    for k in means:
        g = gaps.get(k, [])
        print(f"{k:6s} {np.mean(np.abs(g)) if g else float('nan'):8.2f} "
              f"(n={len(g)})")


def run_one_scenario(args):
    name = "scale" if args.scenario == "scale10x" else args.scenario
    kw = {}
    if args.scheduler:
        kw["schedulers"] = (args.scheduler,)
    if args.policy_ckpt:
        kw["policy_ckpt"] = args.policy_ckpt
    rows = run_scenario(name, seed=args.seed, quick=args.quick, **kw)
    print(f"== scenario: {args.scenario} "
          f"(seed={args.seed}{', quick' if args.quick else ''}) ==")
    vmax = max(r.utility for r in rows)
    for r in rows:
        extra = f" canceled={r.canceled}" if r.canceled else ""
        print(f"{r.scheduler:6s} {r.variant:14s} {r.utility:9.1f} "
              f"acc={r.accepted:4d} comp={r.completed:4d} "
              f"util={r.utilization:5.2f} {r.wall_seconds:7.2f}s{extra}  "
              f"{bar(r.utility, vmax, width=24)}")
    decided = [r for r in rows if r.decision_p50 is not None]
    if decided:
        print("\n== per-decision latency (plan-ahead schedulers) ==")
        for r in decided:
            print(f"{r.scheduler:6s} {r.variant:14s} "
                  f"p50={r.decision_p50*1e3:8.2f}ms "
                  f"p95={r.decision_p95*1e3:8.2f}ms "
                  f"mean={r.decision_mean*1e3:8.2f}ms")
    churned = [r for r in rows if r.retention is not None]
    if churned:
        print("\n== utility retention under fleet churn "
              "(churned / churn-free; higher is better) ==")
        for r in churned:
            lf = f" live={r.live_frac:.2f}" if r.live_frac is not None else ""
            print(f"{r.scheduler:6s} {r.variant:14s} ret={r.retention:6.3f} "
                  f"preempted={r.preempted:3d} dropped={r.preempt_dropped:3d}"
                  f"{lf}  {bar(r.retention, 1.0, width=24)}")
    streamed = [r for r in rows if r.decisions_per_sec is not None]
    if streamed:
        print("\n== sustained throughput (streamed trace) ==")
        for r in streamed:
            wb = (f" window={r.window_bytes/1024:.0f}KiB"
                  if r.window_bytes else "")
            print(f"{r.scheduler:6s} {r.decisions_per_sec:10.1f} "
                  f"decisions/sec over {r.n_jobs} jobs{wb}")


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--jobs", type=int, default=60)
    ap.add_argument("--T", type=int, default=100)
    ap.add_argument("--servers", type=int, default=20)
    ap.add_argument("--seeds", type=int, default=3)
    ap.add_argument("--scenario", default=None,
                    choices=sorted(SCENARIOS) + ["scale10x"],
                    help="run a sim-v2 scenario instead of the Fig. 3/4 "
                         "comparison (scale10x = alias for scale)")
    ap.add_argument("--scheduler", default=None,
                    choices=list(ALL_SCHEDULERS) + ["learned"],
                    help="scale/serving scenarios only: run this single "
                         "scheduler (oasis uses the fused jit engine; "
                         "learned runs the rl/ policy scheduler)")
    ap.add_argument("--policy-ckpt", default=None,
                    help="checkpoint directory from repro.rl.train "
                         "(required for --scheduler learned)")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--quick", action="store_true",
                    help="shrink the scenario instance")
    ap.add_argument("--trace", default=None, metavar="OUT.json",
                    help="record the run with the flight recorder "
                         "(repro.obs) and write a Chrome-trace / Perfetto "
                         "JSON with the metrics snapshot embedded — open "
                         "it at https://ui.perfetto.dev")
    args = ap.parse_args()
    enable_compile_cache()
    if args.scheduler and args.scenario not in ("scale", "scale10x",
                                                "serving"):
        ap.error("--scheduler only applies to --scenario "
                 f"scale/scale10x/serving (got --scenario {args.scenario})")
    if args.policy_ckpt and args.scheduler != "learned":
        ap.error("--policy-ckpt only applies to --scheduler learned")
    if args.scheduler == "learned" and not args.policy_ckpt:
        ap.error("--scheduler learned requires --policy-ckpt "
                 "(a repro.rl.train checkpoint directory)")
    ob = obslib.enable() if args.trace else None
    if args.scenario:
        run_one_scenario(args)
    else:
        run_figs(args)
    if ob is not None:
        n = ob.export_chrome(args.trace)
        snap = ob.metrics.snapshot()
        print(f"\n== flight recorder ==\n{n} trace events -> {args.trace} "
              f"({len(snap['counters'])} counters, "
              f"{len(snap['histograms'])} histograms embedded)")
        obslib.disable()


if __name__ == "__main__":
    main()
