"""Paper-figure benchmarks (Sec. V-A), one function per figure.

Each returns a list of CSV rows ``name,value,derived`` and mirrors the
paper's comparison:  Fig.3 total utility vs #jobs; Fig.4 completion
timeliness; Fig.5 performance ratio vs the exact offline optimum;
Fig.6 sensitivity to inaccurate U/L estimates.
"""
from __future__ import annotations

import time
from typing import List, Optional

import numpy as np

from repro.core import OASiS, price_params_from_jobs
from repro.core.offline_opt import offline_optimum
from repro.sim import (make_cluster, make_jobs, scenarios, simulate,
                       simulate_reference)

SCHEDULERS = ["oasis", "fifo", "drf", "rrh", "dorm"]


def fig3_total_utility(T: int = 100, H: int = 20, K: int = 20,
                       sizes=(20, 40, 60, 80)) -> List[str]:
    rows = []
    for n in sizes:
        cluster = make_cluster(T=T, H=H, K=K)
        jobs = make_jobs(n, T=T, seed=3, small=False)
        for name in SCHEDULERS:
            kw = dict(quantum=0) if name == "oasis" else {}
            t0 = time.perf_counter()
            r = simulate(cluster, jobs, scheduler=name, check=False, **kw)
            us = (time.perf_counter() - t0) * 1e6
            rows.append(f"fig3_utility[{name};n={n}],{us:.0f},"
                        f"{r.total_utility:.2f}")
    return rows


def fig4_timeliness(T: int = 100, H: int = 20, K: int = 20,
                    n: int = 50) -> List[str]:
    """Mean |completion - target| over time-sensitive+critical jobs."""
    rows = []
    cluster = make_cluster(T=T, H=H, K=K)
    jobs = make_jobs(n, T=T, seed=7, small=False, time_insensitive=0.0,
                     time_sensitive=0.5)
    for name in SCHEDULERS:
        kw = dict(quantum=0) if name == "oasis" else {}
        t0 = time.perf_counter()
        r = simulate(cluster, jobs, scheduler=name, check=False, **kw)
        us = (time.perf_counter() - t0) * 1e6
        gap = float(np.mean(np.abs(r.target_gap))) if r.target_gap else -1.0
        rows.append(f"fig4_timeliness[{name}],{us:.0f},{gap:.2f}")
    return rows


def fig5_perf_ratio(seeds=(0, 1, 2, 3, 4)) -> List[str]:
    """OPT / OASiS on exhaustively-solvable instances.  The paper (Fig. 5,
    T=10, ~80 servers) reports 1.1-1.5; we report two capacity regimes —
    paper-like (ample) and adversarially scarce."""
    rows = []
    for label, H, scale in [("ample", 3, 1.0), ("scarce", 2, 0.6)]:
        ratios = []
        for seed in seeds:
            cluster = make_cluster(T=6, H=H, K=H, scale=scale)
            jobs = make_jobs(5, T=6, seed=seed, small=True)
            # literal U/L values (the Theorem-4 setting)
            params = price_params_from_jobs(jobs, cluster, floor_frac=0.0)
            sched = OASiS(cluster, params)
            t0 = time.perf_counter()
            for j in sorted(jobs, key=lambda x: x.arrival):
                sched.on_arrival(j)
            us = (time.perf_counter() - t0) * 1e6
            opt = offline_optimum(cluster, jobs, time_limit=60.0)
            ratio = opt / sched.total_utility if sched.total_utility > 1e-9 \
                else 1.0
            ratios.append(ratio)
            rows.append(f"fig5_ratio[{label};seed={seed}],{us:.0f},{ratio:.3f}")
        rows.append(f"fig5_ratio[{label};mean],0,{float(np.mean(ratios)):.3f}")
    return rows


def fig6_estimates(T: int = 100, H: int = 20, K: int = 20,
                   n: int = 60, factors=(0.25, 0.5, 1.0, 2.0, 4.0)
                   ) -> List[str]:
    """OASiS with mis-estimated U/L ratios (paper: underestimation beats
    overestimation under scarcity)."""
    rows = []
    cluster = make_cluster(T=T, H=H, K=K)
    jobs = make_jobs(n, T=T, seed=11, small=False)
    exact = price_params_from_jobs(jobs, cluster)
    for f in factors:
        params = exact.scaled(f)
        t0 = time.perf_counter()
        r = simulate(cluster, jobs, scheduler="oasis", params=params,
                     check=False, quantum=0)
        us = (time.perf_counter() - t0) * 1e6
        rows.append(f"fig6_estimate[x{f}],{us:.0f},{r.total_utility:.2f}")
    return rows


def latency_table(T: int = 300, H: int = 50, K: int = 50, n: int = 20
                  ) -> List[str]:
    """Footnote-4 claim: <1 s per decision at T=100-300, 50+50 servers."""
    rows = []
    for quantum, label in [(0, "auto"), (1, "exact")]:
        cluster = make_cluster(T=T, H=H, K=K)
        jobs = make_jobs(n, T=T, seed=13, small=False)
        r = simulate(cluster, jobs, scheduler="oasis", check=False,
                     quantum=quantum)
        dec = np.array(r.decision_seconds)
        rows.append(f"latency[q={label};mean],{dec.mean()*1e6:.0f},"
                    f"{dec.mean():.4f}")
        rows.append(f"latency[q={label};p95],{np.percentile(dec,95)*1e6:.0f},"
                    f"{np.percentile(dec,95):.4f}")
    return rows


def sim_v2_speedup(T: int = 100, H: int = 20, K: int = 20, n: int = 60,
                   seed: int = 3, stats_out: Optional[dict] = None
                   ) -> List[str]:
    """fig3-shaped workload: v1 per-slot loop (seed placement path) vs the
    sim-v2 event engine, per reactive scheduler plus OASiS sim overhead
    (wall minus decision time; OASiS decisions are scheduler work shared
    by both drivers, so they are excluded from the engine's speedup)."""
    rows = []
    cluster = make_cluster(T=T, H=H, K=K)
    jobs = make_jobs(n, T=T, seed=seed, small=False)
    agg = {"v1": 0.0, "v2": 0.0}
    stats = {} if stats_out is None else stats_out
    for name in ("fifo", "drf", "rrh", "dorm"):
        t0 = time.perf_counter()
        a = simulate_reference(cluster, jobs, scheduler=name, check=False)
        t1 = time.perf_counter()
        b = simulate(cluster, jobs, scheduler=name, check=False)
        t2 = time.perf_counter()
        assert a.completion == b.completion, f"sim v2 diverged on {name}"
        v1, v2 = t1 - t0, t2 - t1
        agg["v1"] += v1
        agg["v2"] += v2
        stats[name] = {"v1_seconds": v1, "v2_seconds": v2,
                       "speedup": v1 / max(v2, 1e-12)}
        rows.append(f"sim_v2[{name}],{v2*1e6:.0f},{v1/max(v2,1e-12):.2f}")
    for impl, fn in [("v1", simulate_reference), ("v2", simulate)]:
        t0 = time.perf_counter()
        r = fn(cluster, jobs, scheduler="oasis", check=False, quantum=0)
        over = time.perf_counter() - t0 - sum(r.decision_seconds)
        stats[f"oasis_overhead_{impl}_seconds"] = over
        rows.append(f"sim_v2[oasis_overhead;{impl}],{over*1e6:.0f},")
    speedup = agg["v1"] / max(agg["v2"], 1e-12)
    stats["reactive_total"] = {"v1_seconds": agg["v1"], "v2_seconds": agg["v2"],
                               "speedup": speedup}
    rows.append(f"sim_v2[reactive_total],{agg['v2']*1e6:.0f},{speedup:.2f}")
    return rows


def fig3_scale(quick: bool = False, include_oasis: bool = False,
               include_learned: bool = False,
               stats_out: Optional[dict] = None,
               dims: Optional[dict] = None,
               tag: str = "fig3_scale") -> List[str]:
    """fig3 at 10x the paper setting (T=500, 100+100 servers, 2000 jobs) on
    the sim-v2 engine; the v1 per-slot loop cannot finish this in
    reasonable time (see sim_v2_speedup for the controlled comparison).

    ``include_oasis=True`` adds the paper's own scheduler on the fused jit
    engine + device-resident price state (``impl="jax"``);
    ``include_learned=True`` adds the rl/ policy scheduler (untrained
    seed-init net — a decision-pipeline wall-clock column, not a quality
    claim; the trained-policy quality row lives in the ``rl`` section).
    ``stats_out`` receives machine-readable per-scheduler wall clocks,
    utilities, and — for plan-ahead schedulers — per-decision latency
    stats (the ``sim_scale`` record tracked in ``BENCH_decision.json`` —
    see ``benchmarks.run --only simscale``).  ``dims`` overrides the
    instance dimensions (e.g. ``scenarios.SCALE_DIMS_100X`` for the 100x
    record, with ``tag`` labelling its CSV rows)."""
    scheds = scenarios.ALL_SCHEDULERS if include_oasis else scenarios.REACTIVE
    if include_learned:
        scheds = tuple(scheds) + ("learned",)
    rows = []
    if dims is None:
        dims = scenarios.SCALE_DIMS_QUICK if quick else scenarios.SCALE_DIMS
    results = scenarios.run_scale(seed=0, quick=quick, schedulers=scheds,
                                  T=dims["T"], H=dims["H"], K=dims["K"],
                                  n=dims["n"])
    for r in results:
        rows.append(f"{tag}[{r.scheduler};{r.variant}],"
                    f"{r.wall_seconds*1e6:.0f},{r.utility:.2f}")
        if r.decision_p50 is not None:
            rows.append(f"{tag}[{r.scheduler};decision_p50],"
                        f"{r.decision_p50*1e6:.0f},{r.decision_p50:.6f}")
            rows.append(f"{tag}[{r.scheduler};decision_mean],"
                        f"{r.decision_mean*1e6:.0f},{r.decision_mean:.6f}")
    if stats_out is not None:
        decision = {r.scheduler: {"p50": r.decision_p50,
                                  "mean": r.decision_mean,
                                  "p95": r.decision_p95}
                    for r in results if r.decision_p50 is not None}
        stats_out.update({
            "T": dims["T"], "H": dims["H"], "K": dims["K"],
            "n_jobs": dims["n"], "quick": bool(quick),
            "wall_seconds": {r.scheduler: r.wall_seconds for r in results},
            "utility": {r.scheduler: r.utility for r in results},
            "decision": decision,
        })
    return rows


def rl_scoreboard(train_budget_seconds: float = 270.0,
                  iterations: int = 160, eval_seeds=(5, 6, 7),
                  quick: bool = False,
                  stats_out: Optional[dict] = None) -> List[str]:
    """The learned-scheduler acceptance row: train the rl/ policy for at
    most ``train_budget_seconds`` on CPU (REINFORCE + DL2-style warm
    start, paper-scale congested instances, training seeds disjoint from
    ``eval_seeds``) and evaluate greedy vs FIFO on the held-out seeded
    paper-scale instances.  ``--quick`` shrinks everything to a smoke
    (tiny instance, 2 iterations) whose numbers are pipeline checks, not
    quality claims.  ``stats_out`` receives the ``rl`` record for
    BENCH_decision.json."""
    from repro.rl.policy import PolicyConfig
    from repro.rl.train import TrainConfig, evaluate, smoke_config, train

    if quick:
        cfg, pcfg = smoke_config()
    else:
        cfg = TrainConfig(iterations=iterations,
                          budget_seconds=train_budget_seconds)
        pcfg = PolicyConfig()
    t0 = time.perf_counter()
    params, history = train(cfg, pcfg, log=None)
    train_seconds = time.perf_counter() - t0
    ev = evaluate(params, pcfg, eval_seeds, cfg=cfg,
                  schedulers=("learned", "fifo"))
    rows = []
    for name, stats in ev.items():
        rows.append(f"rl_scoreboard[{name};mean],0,"
                    f"{stats['mean_utility']:.2f}")
        for s, v in stats["per_seed"].items():
            rows.append(f"rl_scoreboard[{name};seed={s}],0,{v:.2f}")
    rows.append(f"rl_scoreboard[train],{train_seconds*1e6:.0f},"
                f"{len(history)}")
    if stats_out is not None:
        stats_out.update({
            "quick": bool(quick),
            "train_seconds": train_seconds,
            "train_iterations": len(history),
            "eval_seeds": [int(s) for s in eval_seeds],
            "instance": {"T": cfg.T, "H": cfg.H, "K": cfg.K,
                         "n_jobs": cfg.n_jobs},
            "utility": {name: stats["mean_utility"]
                        for name, stats in ev.items()},
            "per_seed": {name: stats["per_seed"]
                         for name, stats in ev.items()},
        })
    return rows


def serving_table(quick: bool = False,
                  stats_out: Optional[dict] = None) -> List[str]:
    """Continuous serving mode: every scheduler over the same seeded
    open-ended diurnal x bursty stream (``sim.workload.stream_jobs``)
    through the rolling-window engine (``sim.engine.run_stream``).

    The tracked record is throughput-shaped: sustained decisions/sec over
    the whole trace and the price-state's resident ``window_bytes`` (the
    peak-RSS proxy — constant in trace length by construction) next to the
    usual wall clock / utility / decision-latency columns.  ``stats_out``
    receives the ``serving`` (or, under ``quick``, ``serving_quick``)
    record for BENCH_decision.json."""
    results = scenarios.run_serving(seed=0, quick=quick)
    rows = []
    for r in results:
        rows.append(f"serving[{r.scheduler};{r.variant}],"
                    f"{r.wall_seconds*1e6:.0f},{r.utility:.2f}")
        rows.append(f"serving[{r.scheduler};decisions_per_sec],0,"
                    f"{r.decisions_per_sec:.1f}")
        if r.decision_p50 is not None:
            rows.append(f"serving[{r.scheduler};decision_p50],"
                        f"{r.decision_p50*1e6:.0f},{r.decision_p50:.6f}")
    if stats_out is not None:
        dims = (scenarios.SERVING_DIMS_QUICK if quick
                else scenarios.SERVING_DIMS)
        decision = {r.scheduler: {"p50": r.decision_p50,
                                  "mean": r.decision_mean,
                                  "p95": r.decision_p95}
                    for r in results if r.decision_p50 is not None}
        stats_out.update({
            "H": dims["H"], "K": dims["K"], "window": dims["window"],
            "slots": dims["slots"],
            "n_jobs": int(max(r.n_jobs for r in results)),
            "quick": bool(quick),
            "wall_seconds": {r.scheduler: r.wall_seconds for r in results},
            "utility": {r.scheduler: r.utility for r in results},
            "decisions_per_sec": {r.scheduler: r.decisions_per_sec
                                  for r in results},
            "window_bytes": {r.scheduler: r.window_bytes for r in results},
            "decision": decision,
        })
    return rows


def churn_table(quick: bool = False,
                stats_out: Optional[dict] = None) -> List[str]:
    """Fleet-churn robustness: utility retention (churned / churn-free
    utility, higher is better) per scheduler at each churn level of
    ``sim.scenarios.run_churn``, plus the preemption counters.  The
    churned runs execute with ``check=True`` — a capacity violation on
    the surviving fleet aborts the benchmark.  ``stats_out`` receives
    the ``churn`` (or, under ``quick``, ``churn_quick``) record for
    BENCH_decision.json."""
    results = scenarios.run_churn(seed=0, quick=quick)
    rows = []
    for r in results:
        rows.append(f"churn[{r.scheduler};{r.variant}],"
                    f"{r.wall_seconds*1e6:.0f},{r.utility:.2f}")
        if r.retention is not None:
            rows.append(f"churn[{r.scheduler};{r.variant};retention],0,"
                        f"{r.retention:.4f}")
            rows.append(f"churn[{r.scheduler};{r.variant};preempted],0,"
                        f"{r.preempted}")
    if stats_out is not None:
        dims = scenarios.CHURN_DIMS_QUICK if quick else scenarios.CHURN_DIMS
        wall: dict = {}
        utility: dict = {}
        retention: dict = {}
        preempted: dict = {}
        dropped: dict = {}
        for r in results:
            wall[r.scheduler] = wall.get(r.scheduler, 0.0) + r.wall_seconds
            utility.setdefault(r.scheduler, {})[r.variant] = r.utility
            if r.retention is not None:
                retention.setdefault(r.scheduler, {})[r.variant] = r.retention
                preempted.setdefault(r.scheduler, {})[r.variant] = r.preempted
                dropped.setdefault(r.scheduler, {})[r.variant] = \
                    r.preempt_dropped
        stats_out.update({
            "T": dims["T"], "H": dims["H"], "K": dims["K"],
            "n_jobs": dims["n"], "quick": bool(quick),
            "levels": [float(f) for f in dims["levels"]],
            "wall_seconds": wall,
            "utility": utility,
            "retention": retention,
            "preempted": preempted,
            "preempt_dropped": dropped,
        })
    return rows


def scenario_table(quick: bool = False,
                   names=("hetero", "cancel", "straggler", "misest")
                   ) -> List[str]:
    """One row per (scenario, scheduler, variant) from sim/scenarios.py."""
    rows = []
    for name in names:
        for r in scenarios.run_scenario(name, seed=0, quick=quick):
            rows.append(f"scenario[{r.scenario};{r.scheduler};{r.variant}],"
                        f"{r.wall_seconds*1e6:.0f},{r.utility:.2f}")
    return rows


def decision_latency(T: int = 96, H: int = 16, K: int = 16, n: int = 200,
                     stats_out: Optional[dict] = None) -> List[str]:
    """Per-decision scheduler latency (p50/p95 of ``decision_seconds``):
    seed per-slot-loop baseline vs vectorized numpy vs the fused jit engine.

    Each impl is run twice and the second (warm) run is reported — the jit
    engine compiles one executable per shape bucket on first contact, which
    a long-running scheduler amortises away; the one-off cost is reported
    separately as ``jax;cold_mean``.  The final row is the p50 speedup of
    impl="jax" over the seed per-slot-loop path.
    """
    rows = []
    cluster = make_cluster(T=T, H=H, K=K)
    jobs = make_jobs(n, T=T, seed=17, small=False)
    stats = {} if stats_out is None else stats_out
    for impl in ("loop", "fast", "jax"):
        # every impl gets a discarded first run so the comparison is
        # symmetric (jit compiles; numpy warms allocator/page cache)
        cold = simulate(cluster, jobs, scheduler="oasis", impl=impl,
                        check=False, quantum=0)
        r = simulate(cluster, jobs, scheduler="oasis", impl=impl,
                     check=False, quantum=0)
        dec = np.array(r.decision_seconds)
        stats[impl] = {"p50": float(np.percentile(dec, 50)),
                       "p95": float(np.percentile(dec, 95)),
                       "mean": float(dec.mean())}
        for label, val in stats[impl].items():
            rows.append(f"decision_latency[{impl};{label}],{val*1e6:.0f},"
                        f"{val:.6f}")
        if impl == "jax":
            cm = float(np.mean(cold.decision_seconds))
            stats["jax_cold_mean_seconds"] = cm
            rows.append(f"decision_latency[jax;cold_mean],{cm*1e6:.0f},"
                        f"{cm:.6f}")
    for label in ("p50", "p95", "mean"):
        rows.append(f"decision_latency[speedup_jax_vs_seed;{label}],0,"
                    f"{stats['loop'][label] / stats['jax'][label]:.2f}")
    return rows
