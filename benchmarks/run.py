"""Benchmark harness — one function per paper table/figure.

Prints ``name,us_per_call,derived`` CSV rows:
  fig3_utility[...]   total job utility per scheduler x #jobs   (Fig. 3)
  fig3_scale[...]     fig3-shaped workload at 10x paper scale (sim v2)
  fig4_timeliness[..] mean |completion - target| per scheduler  (Fig. 4)
  fig5_ratio[...]     OPT / OASiS on exact-solvable instances   (Fig. 5)
  fig6_estimate[...]  utility under mis-estimated U/L           (Fig. 6)
  latency[...]        per-decision scheduler latency            (fn. 4)
  decision_latency[.] loop vs fast vs fused-jax backend p50/p95
  sim_v2[...]         event-engine vs v1 per-slot-loop wall clock
  scenario[...]       sim-v2 scenario library (hetero/cancel/...)
  minplus[...]        scheduler DP kernel micro-benchmarks

Machine-readable perf tracking (``--json``, default
``BENCH_decision.json``, schema ``bench_decision/v4``; v2/v3 baselines
are read compatibly): the ``decision`` section writes p50/p95 per backend
plus the sim-v2 wall-clock comparison, and the ``simscale`` section
times the 10x-scale fig3 run per scheduler *including OASiS itself* on
the fused jit engine + device-resident price state (``sim_scale``: wall
clock, utility, and decision p50/mean; always the full T=500 /
100+100-server / 2000-job instance — it is the tracked configuration,
so ``--quick`` does not shrink it).  ``simscale_quick`` records the
shrunk instance with the oasis column as a separate ``sim_scale_quick``
section — the CI smoke that exercises the streaming decision pipeline
on every PR.  ``serving`` records the continuous-traffic mode (the
>=20k-slot diurnal x bursty stream over the paper-scale fleet through
the rolling-window engine): sustained decisions/sec and the resident
``window_bytes`` memory proxy per scheduler; ``serving_quick`` is its
CI-smoke shrink.  ``churn`` records the fleet-churn robustness table
(per-scheduler utility **retention** — churned / churn-free utility,
higher is better — at each churn level of ``sim.scenarios.run_churn``,
plus preemption counters; churned runs execute with capacity checks
on); ``churn_quick`` is its CI-smoke shrink.  ``minplus`` records the
structure-aware DP slot kernel micro-bench (chain vs monotone dispatch
vs plateau across band widths, convex and adversarial rows); its
per-case p50s are regression-gated.  ``obs`` (schema v5) runs a seeded
OASiS-on-jax episode plus a reactive episode under fleet churn with the
``repro.obs`` flight recorder installed and records the counter
snapshot plus derived health figures (early-exit tile fraction,
device uploads, preemptions) — the derived leaves are
regression-gated so a silent efficiency loss (cache stops hitting,
early exit stops firing, uploads reappear on the commit path) fails CI
even when wall clocks stay within ratio.  Sections *merge* into an
existing ``--json`` file, so
the committed baseline can accumulate all records; CI regenerates the
file and fails on >2x regressions via
``python -m benchmarks.check_regression``.

``--quick`` shrinks the other sections' instance sizes.  The roofline
table is a separate consumer of the dry-run artifacts:
``python -m benchmarks.roofline``.
"""
from __future__ import annotations

import argparse
import json
import os
import platform
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(__file__), "..", "src"))

SECTIONS = ("fig3", "fig4", "fig5", "fig6", "latency", "decision",
            "simspeed", "scale", "simscale", "simscale_quick", "serving",
            "serving_quick", "churn", "churn_quick", "scenarios", "rl",
            "kernels", "minplus", "obs")


def _is_num(x) -> bool:
    import math
    return isinstance(x, (int, float)) and not isinstance(x, bool) \
        and math.isfinite(x)


def _num_dict(sec: str, name: str, d, problems) -> None:
    if not isinstance(d, dict) or not all(_is_num(v) for v in d.values()):
        problems.append(f"{sec}.{name}: expected dict of finite numbers")


def validate_tracked(payload: dict) -> list:
    """Structural validation of a bench_decision payload (v2..v5; v3
    added the ``serving``/``serving_quick`` sections, v4 added
    ``churn``/``churn_quick``, v5 adds the flight-recorder ``obs``
    section — readers stay backward-compatible with committed v2..v4
    baselines).

    Returns a list of problems (empty = valid).  ``_merge_json`` refuses
    to write an invalid file: a malformed section used to be caught only
    much later, by ``check_regression`` diffing against it — by which
    time the broken file was already committed as the baseline.

    >>> from benchmarks.run import validate_tracked
    >>> validate_tracked({"schema": "bench_decision/v5"})
    []
    >>> validate_tracked({"schema": "bench_decision/v5",
    ...                   "decision_seconds": {"jax": {"p50": 0.01}}})
    ['decision_seconds.jax: needs finite p50/p95/mean']
    """
    problems = []
    if payload.get("schema") not in ("bench_decision/v2",
                                     "bench_decision/v3",
                                     "bench_decision/v4",
                                     "bench_decision/v5"):
        problems.append(f"schema: expected 'bench_decision/v2'..'v5', "
                        f"got {payload.get('schema')!r}")
    known = {"schema", "platform", "python", "decision_seconds", "sim_v2",
             "sim_scale", "sim_scale_quick", "sim_scale_100x", "serving",
             "serving_quick", "churn", "churn_quick", "rl", "minplus",
             "obs"}
    for sec in sorted(set(payload) - known):
        problems.append(f"{sec}: unknown section (known: {sorted(known)})")

    def _section(name):
        """Present section, or None; a non-dict section is a problem,
        not an AttributeError (the baseline file on disk may be
        arbitrarily corrupted — that is what this validator guards)."""
        sec = payload.get(name)
        if sec is None or isinstance(sec, dict):
            return sec
        problems.append(f"{name}: expected dict section, "
                        f"got {type(sec).__name__}")
        return None

    dec = _section("decision_seconds")
    if dec is not None:
        for impl, stats in dec.items():
            if impl == "quick":
                if not isinstance(stats, bool):
                    problems.append("decision_seconds.quick: expected bool")
            elif isinstance(stats, dict):
                if not {"p50", "p95", "mean"} <= set(stats) or \
                        not all(_is_num(stats[k])
                                for k in ("p50", "p95", "mean")):
                    problems.append(f"decision_seconds.{impl}: needs "
                                    "finite p50/p95/mean")
            elif not _is_num(stats):
                problems.append(f"decision_seconds.{impl}: expected "
                                "stats dict or number")
    sim = _section("sim_v2")
    if sim is not None:
        for key, stats in sim.items():
            if key == "quick":
                continue
            if isinstance(stats, dict):
                _num_dict("sim_v2", key, stats, problems)
            elif not _is_num(stats):
                problems.append(f"sim_v2.{key}: expected number")
    for sec in ("sim_scale", "sim_scale_quick", "sim_scale_100x"):
        scale = _section(sec)
        if scale is None:
            continue
        for dim in ("T", "H", "K", "n_jobs"):
            if not isinstance(scale.get(dim), int):
                problems.append(f"{sec}.{dim}: expected int")
        _num_dict(sec, "wall_seconds", scale.get("wall_seconds"), problems)
        _num_dict(sec, "utility", scale.get("utility"), problems)
        decision = scale.get("decision") or {}
        if not isinstance(decision, dict):
            problems.append(f"{sec}.decision: expected dict")
            decision = {}
        for sched, stats in decision.items():
            if not isinstance(stats, dict) or not all(
                    v is None or _is_num(v) for v in stats.values()):
                problems.append(f"{sec}.decision.{sched}: expected dict of "
                                "numbers/nulls")
    for sec in ("serving", "serving_quick"):
        srv = _section(sec)
        if srv is None:
            continue
        for dim in ("H", "K", "window", "slots", "n_jobs"):
            if not isinstance(srv.get(dim), int):
                problems.append(f"{sec}.{dim}: expected int")
        for name in ("wall_seconds", "utility", "decisions_per_sec",
                     "window_bytes"):
            _num_dict(sec, name, srv.get(name), problems)
        decision = srv.get("decision") or {}
        if not isinstance(decision, dict):
            problems.append(f"{sec}.decision: expected dict")
            decision = {}
        for sched, stats in decision.items():
            if not isinstance(stats, dict) or not all(
                    v is None or _is_num(v) for v in stats.values()):
                problems.append(f"{sec}.decision.{sched}: expected dict of "
                                "numbers/nulls")
    for sec in ("churn", "churn_quick"):
        ch = _section(sec)
        if ch is None:
            continue
        for dim in ("T", "H", "K", "n_jobs"):
            if not isinstance(ch.get(dim), int):
                problems.append(f"{sec}.{dim}: expected int")
        levels = ch.get("levels")
        if not isinstance(levels, list) or not levels or \
                not all(_is_num(f) for f in levels):
            problems.append(f"{sec}.levels: expected non-empty list of "
                            "finite numbers")
        _num_dict(sec, "wall_seconds", ch.get("wall_seconds"), problems)
        for name in ("utility", "retention", "preempted", "preempt_dropped"):
            per_sched = ch.get(name)
            if not isinstance(per_sched, dict):
                problems.append(f"{sec}.{name}: expected dict")
                continue
            for sched, per_variant in per_sched.items():
                _num_dict(sec, f"{name}.{sched}", per_variant, problems)
    ob = _section("obs")
    if ob is not None:
        for dim in ("T", "H", "K", "n_jobs"):
            if not isinstance(ob.get(dim), int):
                problems.append(f"obs.{dim}: expected int")
        if not isinstance(ob.get("quick"), bool):
            problems.append("obs.quick: expected bool")
        _num_dict("obs", "counters", ob.get("counters"), problems)
        _num_dict("obs", "derived", ob.get("derived"), problems)
    mp = _section("minplus")
    if mp is not None:
        for case, stats in mp.items():
            if case == "quick":
                if not isinstance(stats, bool):
                    problems.append("minplus.quick: expected bool")
            elif not isinstance(stats, dict) or not _is_num(
                    stats.get("p50")):
                problems.append(f"minplus.{case}: needs finite p50")
    rl = _section("rl")
    if rl is not None:
        if not _is_num(rl.get("train_seconds")):
            problems.append("rl.train_seconds: expected finite number")
        _num_dict("rl", "utility", rl.get("utility"), problems)
        per_seed = rl.get("per_seed") or {}
        if not isinstance(per_seed, dict):
            problems.append("rl.per_seed: expected dict")
            per_seed = {}
        for name, per in per_seed.items():
            _num_dict("rl", f"per_seed.{name}", per, problems)
    return problems


def _merge_json(path: str, updates: dict) -> None:
    """Merge freshly-measured sections into the tracked stats file.

    Existing sections not re-measured this run are preserved, so e.g.
    ``--only simscale`` does not drop the decision-latency record.  Each
    section carries its own ``quick`` flag (sections can be measured
    under different modes), so there is no top-level one.  The merged
    payload is validated against the bench_decision schema BEFORE
    writing; a malformed section aborts the run instead of poisoning the
    committed baseline."""
    payload = {}
    if os.path.exists(path):
        try:
            with open(path) as fh:
                prev = json.load(fh)
            if str(prev.get("schema", "")).startswith("bench_decision/"):
                payload = prev
        except (OSError, ValueError):
            pass
    payload.pop("quick", None)                  # v1 leftover
    payload.update(updates)
    payload.update({
        # always write the current version; reads accept v2..v4 baselines
        "schema": "bench_decision/v5",
        "platform": platform.platform(),
        "python": platform.python_version(),
    })
    problems = validate_tracked(payload)
    if problems:
        print(f"# NOT writing {path}: payload fails bench_decision "
              "validation:", file=sys.stderr)
        for p in problems:
            print(f"#   {p}", file=sys.stderr)
        raise SystemExit(1)
    with open(path, "w") as fh:
        json.dump(payload, fh, indent=2, sort_keys=True)
    print(f"# wrote {path}", file=sys.stderr)


def _kernel_micro() -> list:
    import jax
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.minplus.ref import minplus_ref
    from repro.core.subroutine import minplus_band

    rows = []
    rng = np.random.default_rng(0)
    prev = jnp.asarray(rng.random(4096).astype(np.float32))
    row = jnp.asarray(rng.random(257).astype(np.float32))
    f = jax.jit(minplus_ref)
    f(row, prev)[0].block_until_ready()
    t0 = time.perf_counter()
    for _ in range(20):
        f(row, prev)[0].block_until_ready()
    us = (time.perf_counter() - t0) / 20 * 1e6
    rows.append(f"minplus_xla[D=4096;DC=256],{us:.0f},")

    pnp = np.asarray(prev)
    rnp = np.asarray(row)
    t0 = time.perf_counter()
    for _ in range(20):
        minplus_band(pnp, rnp)
    us = (time.perf_counter() - t0) / 20 * 1e6
    rows.append(f"minplus_numpy[D=4096;DC=256],{us:.0f},")
    return rows


def _minplus_micro(quick: bool = False):
    """Chain vs monotone-dispatch vs plateau slot kernels across band
    widths, on certified-convex, staircase (few-run), and adversarial
    (many-run, non-convex) rows — the structure split real COST_t rows
    live on (see ``kernels/minplus/monotone.py``: real rows are
    staircases, so the plateau path is the one that matters and the
    convex D&C fires only on synthetic rows).

    Returns (CSV rows, tracked record): the record's per-case ``p50``
    (median of the timed reps, in seconds) is the leaf
    ``benchmarks.check_regression`` gates."""
    import jax
    import numpy as np
    import jax.numpy as jnp
    from repro.kernels.minplus.monotone import monotone_step, plateau_step
    from repro.kernels.minplus.tiled import minplus_chain_step

    rows_out = []
    tracked: dict = {"quick": bool(quick)}
    rng = np.random.default_rng(0)
    d1 = 1024 if quick else 4096
    reps = 5 if quick else 11
    chain = jax.jit(lambda r, p: minplus_chain_step(r[None], p[None])[0])
    mono = jax.jit(monotone_step)
    plat = jax.jit(plateau_step)
    prev = jnp.asarray((rng.random(d1) * 10).astype(np.float32))
    for dc1 in ((65,) if quick else (65, 513)):
        js = np.arange(dc1, dtype=np.float32)
        # integer-valued convex row: exact second difference 1 in f32
        convex = jnp.asarray(js * (js - 1.0) / 2.0)
        stair = jnp.asarray(np.repeat(
            (rng.random(max(dc1 // 8, 1)) * 5).astype(np.float32), 8)[:dc1])
        advers = jnp.asarray(rng.random(dc1).astype(np.float32))
        for name, fn, row in (("chain", chain, advers),
                              ("monotone_convex", mono, convex),
                              ("monotone_adversarial", mono, advers),
                              ("plateau_stair", plat, stair)):
            fn(row, prev).block_until_ready()
            times = []
            for _ in range(reps):
                t0 = time.perf_counter()
                fn(row, prev).block_until_ready()
                times.append(time.perf_counter() - t0)
            p50 = sorted(times)[len(times) // 2]
            rows_out.append(f"minplus[{name};DC={dc1 - 1};D={d1 - 1}],"
                            f"{p50 * 1e6:.0f},")
            tracked[f"{name}_dc{dc1 - 1}"] = {"p50": p50}
    return rows_out, tracked


def _obs_probe(quick: bool = False):
    """Flight-recorder probe: one seeded OASiS episode on the fused jax
    engine plus one reactive episode under deterministic fleet churn,
    both run with a ``repro.obs`` recorder installed.

    Returns (CSV rows, tracked record).  The record carries the raw
    counter snapshot and three derived health figures:

    * ``early_exit_frac``      — DP tiles actually visited / horizon
      tiles (lower is better: the monotone early-exit is working)
    * ``device_uploads``       — full-table host->device uploads on the
      commit path (lower is better: the slot-window add path holds)
    * ``preempted``            — checkpoint/restart preemptions under
      the seeded churn trace (deterministic; drift means the churn
      engine changed behaviour)

    All figures are deterministic in the seeds, so unlike the wall-clock
    leaves a drift here is semantic, not runner weather.
    """
    from repro import obs as obslib
    from repro.sim import engine
    from repro.sim.fleet import make_fleet_trace
    from repro.sim.workload import make_cluster, make_jobs

    # full mode spans 3 TILE-slot blocks (TILE=64), so the early exit
    # has tiles to skip
    T, HK, n_jobs = (48, 6, 24) if quick else (192, 10, 64)
    cluster = make_cluster(T=T, H=HK, K=HK)
    jobs = make_jobs(n_jobs, T=T, seed=0, small=True)
    ob = obslib.Obs()
    t0 = time.perf_counter()
    engine.run(cluster, jobs, scheduler="oasis", impl="jax", obs=ob)
    # MTBF/MTTR scaled to the horizon so both modes see failures land on
    # RUNNING jobs (the scoreboard churn_trace at these dims fails
    # servers between the short jobs — zero preemptions, no signal)
    fleet = make_fleet_trace(cluster, seed=1, mtbf=T / 1.6, mttr=T / 12)
    engine.run(cluster, jobs, scheduler="dorm", fleet=fleet, obs=ob)
    wall = time.perf_counter() - t0
    c = dict(ob.metrics.snapshot()["counters"])
    tiles_horizon = c.get("decide.tiles_horizon", 0.0)
    derived = {
        "early_exit_frac": (c.get("decide.tiles_visited", 0.0)
                            / tiles_horizon) if tiles_horizon else 1.0,
        "device_uploads": c.get("price.device_uploads", 0.0),
        "preempted": c.get("engine.preemptions", 0.0),
    }
    tracked = {"T": T, "H": HK, "K": HK, "n_jobs": n_jobs,
               "quick": bool(quick), "counters": c, "derived": derived}
    rows = [f"obs_probe[jobs={n_jobs};T={T}],{wall * 1e6:.0f},"
            f"early_exit={derived['early_exit_frac']:.3f};"
            f"uploads={derived['device_uploads']:.0f};"
            f"preempted={derived['preempted']:.0f}"]
    return rows, tracked


def main() -> None:
    # compiles land in the persistent cache: wall-clock rows then measure
    # the engine, not recompiles of bit-unchanged executables
    from repro.compile_cache import enable_compile_cache
    enable_compile_cache()
    ap = argparse.ArgumentParser()
    ap.add_argument("--quick", action="store_true")
    ap.add_argument("--only", default=None,
                    help="comma list: " + ",".join(SECTIONS))
    ap.add_argument("--json", default="BENCH_decision.json",
                    help="tracked stats file (bench_decision/v2): the "
                         "decision section records p50/p95 per backend + "
                         "sim-v2 wall clock, simscale records the "
                         "10x-scale per-scheduler wall clock; sections "
                         "merge into an existing file; empty disables")
    args = ap.parse_args()
    from benchmarks import figs

    which = set((args.only or ",".join(SECTIONS)).split(","))
    unknown = which - set(SECTIONS)
    if unknown:
        ap.error(f"unknown --only section(s): {sorted(unknown)}")
    rows = []
    t_all = time.time()
    if "fig3" in which:
        rows += figs.fig3_total_utility(
            sizes=(20, 40) if args.quick else (20, 40, 60, 80))
    if "fig4" in which:
        rows += figs.fig4_timeliness(n=30 if args.quick else 50)
    if "fig5" in which:
        rows += figs.fig5_perf_ratio(seeds=(0, 1) if args.quick
                                     else (0, 1, 2, 3, 4))
    if "fig6" in which:
        rows += figs.fig6_estimates(n=30 if args.quick else 60)
    if "latency" in which:
        rows += figs.latency_table(T=100 if args.quick else 300,
                                   n=10 if args.quick else 20)
    tracked: dict = {}
    if "decision" in which:
        dstats: dict = {}
        sstats: dict = {}
        rows += figs.decision_latency(n=60 if args.quick else 200,
                                      stats_out=dstats)
        rows += figs.sim_v2_speedup(
            **(dict(T=60, n=40) if args.quick else {}), stats_out=sstats)
        tracked["decision_seconds"] = {**dstats, "quick": bool(args.quick)}
        tracked["sim_v2"] = {**sstats, "quick": bool(args.quick)}
    if "simspeed" in which and "decision" not in which:
        rows += figs.sim_v2_speedup(
            **(dict(T=60, n=40) if args.quick else {}))
    if "scale" in which:
        rows += figs.fig3_scale(quick=args.quick)
    if "simscale" in which:
        # the tracked 10x configuration (incl. the oasis column on the
        # fused jit engine): never shrunk by --quick
        scstats: dict = {}
        rows += figs.fig3_scale(quick=False, include_oasis=True,
                                stats_out=scstats)
        tracked["sim_scale"] = scstats
        # the 100x rung (T=1000, 200+200 servers, 8000 jobs), oasis
        # included — the fused engine's scaling stays on the scoreboard
        from repro.sim import scenarios as _scen
        sc100: dict = {}
        rows += figs.fig3_scale(quick=False, include_oasis=True,
                                stats_out=sc100,
                                dims=_scen.SCALE_DIMS_100X,
                                tag="fig3_scale100x")
        tracked["sim_scale_100x"] = sc100
    if "simscale_quick" in which:
        # CI smoke: the shrunk scale instance with the oasis AND learned
        # columns, so the device-resident decision pipeline and the rl/
        # policy decision pipeline are exercised on every PR; kept as a
        # separate record (sim_scale_quick) so it is never diffed
        # against the full-instance baseline
        qstats: dict = {}
        rows += figs.fig3_scale(quick=True, include_oasis=True,
                                include_learned=True, stats_out=qstats)
        tracked["sim_scale_quick"] = qstats
    if "serving" in which:
        # the tracked continuous-serving configuration (>=20k-slot stream,
        # paper-scale fleet): never shrunk by --quick
        svstats: dict = {}
        rows += figs.serving_table(quick=False, stats_out=svstats)
        tracked["serving"] = svstats
    if "serving_quick" in which:
        # CI smoke: a short streamed trace through every scheduler; kept
        # as a separate record so it is never diffed against the
        # full-trace baseline
        sqstats: dict = {}
        rows += figs.serving_table(quick=True, stats_out=sqstats)
        tracked["serving_quick"] = sqstats
    if "churn" in which:
        # the tracked fleet-churn robustness configuration (full-size
        # jobs over the 40+40 fleet): never shrunk by --quick
        chstats: dict = {}
        rows += figs.churn_table(quick=False, stats_out=chstats)
        tracked["churn"] = chstats
    if "churn_quick" in which:
        # CI smoke: the shrunk churn instance through every scheduler
        # (capacity checks on under churn); kept as a separate record so
        # it is never diffed against the full-instance baseline
        cqstats: dict = {}
        rows += figs.churn_table(quick=True, stats_out=cqstats)
        tracked["churn_quick"] = cqstats
    if "rl" in which:
        # the learned-scheduler acceptance row: budgeted CPU training +
        # held-out eval vs FIFO (quality claim lives here; the
        # sim_scale_quick learned column is wall-clock only)
        rlstats: dict = {}
        rows += figs.rl_scoreboard(quick=args.quick, stats_out=rlstats)
        tracked["rl"] = rlstats
    if "minplus" in which:
        # structure-aware DP slot kernels (chain / monotone / plateau);
        # the tracked per-case p50s are regression-gated
        mp_rows, mp_tracked = _minplus_micro(quick=args.quick)
        rows += mp_rows
        tracked["minplus"] = mp_tracked
    if "obs" in which:
        # flight-recorder probe: deterministic efficiency counters
        # (row-cache hit rate, early-exit depth, uploads, preemptions)
        ob_rows, ob_tracked = _obs_probe(quick=args.quick)
        rows += ob_rows
        tracked["obs"] = ob_tracked
    if args.json and tracked:
        _merge_json(args.json, tracked)
    if "scenarios" in which:
        rows += figs.scenario_table(quick=args.quick)
    if "kernels" in which:
        rows += _kernel_micro()
    print("name,us_per_call,derived")
    for r in rows:
        print(r)
    print(f"# total benchmark wall time: {time.time()-t_all:.1f}s",
          file=sys.stderr)


if __name__ == "__main__":
    main()
