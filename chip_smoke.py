#!/usr/bin/env python3
"""Smoke run of OASiS's decision engine on one TPU chip.

    python chip_smoke.py [--seed 0]

Drives the fused decision engine (``impl="jax"``) once through the
entry points a user calls (``engine.run``, ``engine.run_stream`` via
``sim/scenarios.py``), in one process, in five phases; each prints its
own lines:

a. device     — the first JAX device must be a TPU;
b. kernel     — the Pallas min-plus sweep, compiled by Mosaic, against
                ``minplus_sweep_ref`` at the 10x shapes;
c. agreement  — the seeded paper-scale instance on the chip against the
                float64 ``impl="fast"`` run on the host, judged by the
                float32 decision policy in the README;
d. scale      — the 10x episodic instance with capacity checks on;
e. serving    — the rolling-window stream.

Any failure exits non-zero before the last line.  The last line of
standard output is one JSON object naming the device.  Times printed
are smoke readings of one cold run, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import numpy as np

# float32 decision policy (README, "Float32 decisions on TPU"): a job may
# be decided differently from the float64 oracle on the same price state
# only when the oracle's best payoff is within PAYOFF_TOL of zero, and
# two accepted schedules may differ in float64 payoff by at most that
# much; the whole-run utility may move by at most UTILITY_TOL (relative).
PAYOFF_TOL = 1e-3
UTILITY_TOL = 0.02


class SmokeFailure(RuntimeError):
    pass


def _check(ok: bool, msg: str) -> None:
    if not ok:
        raise SmokeFailure(msg)


def phase_device() -> dict:
    import jax
    devs = jax.devices()
    d = devs[0]
    print(f"[a] device: platform={d.platform} kind={d.device_kind} "
          f"count={len(devs)}")
    _check(d.platform == "tpu", f"no TPU: first device is {d.platform}")
    return {"platform": d.platform, "kind": d.device_kind,
            "count": len(devs)}


def phase_kernel(seed: int) -> None:
    """The compiled sweep kernel at T=500, d1=1280, bands 64 and 128."""
    import jax
    import jax.numpy as jnp
    from repro.kernels.minplus.kernel import minplus_sweep_pallas
    from repro.kernels.minplus.ref import minplus_sweep_ref
    rng = np.random.default_rng(seed)
    T, d_total = 500, 1279
    for m_pad in (64, 128):
        rows = rng.random((T, m_pad)).astype(np.float32)
        rows[rng.random((T, m_pad)) < 0.3] = np.inf
        rows[:, 0] = 0.0
        rows = jnp.asarray(rows)
        kern = jax.jit(lambda r: minplus_sweep_pallas(r, d_total,
                                                      interpret=False))
        compiled = kern.lower(rows).compile()
        _check("tpu_custom_call" in compiled.as_text(),
               "sweep launch holds no Mosaic kernel")
        cost, arg = jax.device_get(compiled(rows))
        want, warg = jax.device_get(jax.jit(
            minplus_sweep_ref, static_argnums=1)(rows, d_total))
        fin = np.isfinite(want)
        same_inf = bool(np.array_equal(fin, np.isfinite(cost)))
        diff = float(np.max(np.abs(cost[fin] - want[fin]))) if fin.any() \
            else 0.0
        agree = float(np.mean(arg == warg))
        print(f"[b] kernel T={T} m_pad={m_pad} d1={d_total + 1}: "
              f"max_abs_diff={diff!r} argmin_agreement={agree!r} "
              f"inf_pattern_equal={same_inf}")
        _check(same_inf and diff <= 1e-5 and agree == 1.0,
               f"kernel disagrees with minplus_sweep_ref (m_pad={m_pad})")


def _f64_payoff(state, job, sched) -> float:
    """Payoff of ``sched`` at the float64 prices of ``state``."""
    cost = 0.0
    for alloc, prices, res in ((sched.workers, state.worker_prices_at,
                                job.worker_res),
                               (sched.ps, state.ps_prices_at, job.ps_res)):
        if not alloc:
            continue
        ts = np.array(sorted(alloc))
        unit = prices(ts) @ res                              # (n, S)
        cost += float(np.sum(np.stack([alloc[t] for t in ts]) * unit))
    return sched.utility - cost


def agreement_instance():
    from repro.sim.workload import make_cluster, make_jobs
    return (make_cluster(T=100, H=50, K=50),
            make_jobs(200, T=100, seed=0, small=True))


def lockstep(cluster, jobs):
    """``impl="jax"`` run whose every decision is judged against the float64
    oracle (``impl="fast"``) on the identical price state: the oracle
    commits the chip's own schedules.  Returns the run's result and, per
    job either side accepted, ``(jid, f64_accepts, chip_accepts, margin)``
    — margin is the oracle's payoff for a job only it accepts, the float64
    payoff of the chip's schedule for one only the chip accepts, and the
    float64 payoff shortfall of the chip's schedule when both accept."""
    from repro.core.oasis import OASiS
    from repro.core.pricing import price_params_from_jobs
    from repro.sim import engine
    oracle = OASiS(cluster, price_params_from_jobs(jobs, cluster),
                   impl="fast")
    verdicts = []

    def judge(dp):
        job, cand = dp.job, dp.candidate
        ref = oracle.propose(job)
        if cand is not None:
            mine = _f64_payoff(oracle.state, job, cand)
            verdicts.append((job.jid, ref is not None, True,
                             float(ref.payoff - mine) if ref is not None
                             else mine))
            oracle.state.commit(job, cand.workers, cand.ps)
        elif ref is not None:
            verdicts.append((job.jid, True, False, float(ref.payoff)))
        return (1, 0) if cand is not None else (0, 0)

    r = engine.run(cluster, jobs, scheduler="oasis", impl="jax", quantum=0,
                   check=True, policy=judge)
    return r, verdicts


def phase_agreement() -> None:
    """Paper-scale instance: jax on the device, fast (float64) on the host,
    then a lockstep run that judges each chip decision against the
    float64 oracle on the identical price state."""
    from repro.sim import engine
    cluster, jobs = agreement_instance()
    runs = {impl: engine.run(cluster, jobs, scheduler="oasis", impl=impl,
                             quantum=0, check=True)
            for impl in ("jax", "fast")}
    for impl, r in runs.items():
        print(f"[c] {impl}: accepted={r.accepted} "
              f"utility={r.total_utility!r}")
    jx, fs = runs["jax"], runs["fast"]
    flipped = sorted(set(jx.completion) ^ set(fs.completion))
    print(f"[c] jobs decided differently by the two runs: {len(flipped)} "
          f"{flipped}")

    lock, verdicts = lockstep(cluster, jobs)
    both = [v for v in verdicts if v[1] and v[2]]
    flips = [v for v in verdicts if v[1] != v[2]]
    gap = float(max((v[3] for v in both), default=0.0))
    print(f"[c] lockstep: accepted={lock.accepted} "
          f"utility={lock.total_utility!r} both_accept={len(both)} "
          f"max_f64_payoff_gap={gap!r}")
    for jid, f64_acc, _, margin in flips:
        print(f"[c] flip jid={jid} f64={'accept' if f64_acc else 'reject'} "
              f"f64_payoff_margin={margin!r}")
    du = abs(jx.total_utility - fs.total_utility) / max(
        abs(fs.total_utility), 1e-12)
    print(f"[c] utility relative difference jax vs fast: {du!r}")
    _check(gap <= PAYOFF_TOL, f"accepted schedule {gap} below the f64 "
           "optimum")
    _check(all(abs(m) <= PAYOFF_TOL for *_, m in flips),
           f"decision flips outside the f32 policy: {flips}")
    _check(du <= UTILITY_TOL, f"utility moved by {du} (> {UTILITY_TOL})")


def phase_scale() -> None:
    """10x episodic instance: T=500, 100+100 servers, 2000 full-size jobs."""
    import jax
    from repro import obs as obslib
    from repro.sim import engine
    from repro.sim.scenarios import SCALE_DIMS
    from repro.sim.workload import make_cluster, make_jobs
    compile_s = []

    def on_duration(event, secs, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compile_s.append(secs)

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    T, H, K, n = (SCALE_DIMS[k] for k in ("T", "H", "K", "n"))
    cluster = make_cluster(T=T, H=H, K=K)
    jobs = make_jobs(n, T=T, seed=0, small=False)
    ob = obslib.Obs()
    t0 = time.perf_counter()
    r = engine.run(cluster, jobs, scheduler="oasis", impl="jax",
                   quantum=0, check=True, obs=ob)
    wall = time.perf_counter() - t0
    uploads = ob.metrics.snapshot()["counters"].get(
        "price.device_uploads", 0)
    dec = np.asarray(r.decision_seconds)
    print(f"[d] scale cold_compile_seconds={sum(compile_s)!r} "
          f"({len(compile_s)} compiles)")
    print(f"[d] scale wall_seconds={wall!r}")
    print(f"[d] scale decision_p50_seconds={float(np.percentile(dec, 50))!r}")
    print(f"[d] scale decision_p95_seconds={float(np.percentile(dec, 95))!r}")
    print(f"[d] scale accepted={r.accepted} of {n}")
    print(f"[d] scale utility={r.total_utility!r}")
    print(f"[d] scale device_uploads={uploads!r}")
    _check(uploads == 1, f"device_uploads={uploads}, expected 1")


def phase_serving() -> None:
    """Rolling-window stream (50+50, window 64, rate 0.2) for 2000 slots;
    ``run_serving`` checks ``window_bytes`` against its formula."""
    from repro.sim.scenarios import SERVING_DIMS, run_serving
    row, = run_serving(schedulers=("oasis",), slots=2000)
    expect = SERVING_DIMS["window"] * (SERVING_DIMS["H"]
                                       + SERVING_DIMS["K"]) * 5 * 8
    print(f"[e] serving jobs={row.n_jobs} accepted={row.accepted} "
          f"utility={row.utility!r} window_bytes={row.window_bytes} "
          f"(formula {expect})")
    _check(row.window_bytes == expect, "window_bytes off its formula")


def main() -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--seed", type=int, default=0,
                    help="seed of the kernel phase's random rows")
    args = ap.parse_args()
    from repro.compile_cache import enable_compile_cache
    cache = enable_compile_cache()
    warm = os.path.isdir(cache) and bool(os.listdir(cache))
    print(f"compile cache: {cache} ({'warm' if warm else 'cold'})")
    t_start = time.perf_counter()
    device = phase_device()
    for name, fn in (("kernel", lambda: phase_kernel(args.seed)),
                     ("agreement", phase_agreement),
                     ("scale", phase_scale),
                     ("serving", phase_serving)):
        t0 = time.perf_counter()
        fn()
        print(f"phase {name} passed in {time.perf_counter() - t0!r} s")
    print(f"all phases passed in {time.perf_counter() - t_start!r} s")
    print(json.dumps({"ok": True, "device": device}))
    return 0


if __name__ == "__main__":
    try:
        sys.exit(main())
    except SmokeFailure as e:
        print(f"FAILED: {e}", file=sys.stderr)
        sys.exit(1)
