"""Equivalence + edge-case suite for the fused jit Alg. 2 engine.

The fused engine (``best_schedule_fused`` / ``best_schedule_fused_batch``,
reached via ``impl="jax"``) must make identical accept/reject decisions and
produce the same utilities (within 1e-6) as ``best_schedule_ref``, the
paper-faithful oracle — including on degenerate inputs: empty server pools,
worker-only jobs (zero PS demand), and jobs whose dcap is 0.
"""
import dataclasses

import numpy as np
import pytest

from repro.core import (OASiS, best_schedule, best_schedule_ref,
                        price_params_from_jobs)
from repro.core.pricing import PriceState
from repro.core.schedule_jax import (best_schedule_fused,
                                     best_schedule_fused_batch, dp_sweep_jax)
from repro.core.subroutine import (_greedy_cost_for_counts, cost_t_rows,
                                   cost_t_rows_loop, minplus_band)
from repro.core.types import ClusterSpec, Job, SigmoidUtility
from repro.sim import make_cluster, make_jobs, simulate

from random_ops import apply_random_ops


def mk_job(jid=0, a=0, E=2, N=3, M=10, tau=0.02, e=0.05, b=1.0, B=4.0,
           g=(50.0, 1.0, 3.0), w=None, s=None):
    return Job(jid=jid, arrival=a, epochs=E, num_chunks=N,
               minibatches_per_chunk=M, tau=tau, grad_size=e, worker_bw=b,
               ps_bw=B,
               worker_res=np.array([1.0, 2.0, 2.0, 1.0, b]) if w is None else w,
               ps_res=np.array([0.0, 2.0, 2.0, 1.0, B]) if s is None else s,
               utility=SigmoidUtility(*g))


def assert_same_decision(job, state, ref, got):
    assert (ref is None) == (got is None), f"accept/reject differ jid={job.jid}"
    if ref is not None:
        assert got.finish == ref.finish, job.jid
        assert got.payoff == pytest.approx(ref.payoff, rel=1e-6, abs=1e-9)
        assert got.cost == pytest.approx(ref.cost, rel=1e-6, abs=1e-9)
        assert got.utility == pytest.approx(ref.utility, rel=1e-6)
        # placements fulfil the same per-slot worker counts
        for t, y in got.workers.items():
            assert y.sum() == ref.workers[t].sum(), (job.jid, t)


@pytest.mark.parametrize("seed,T,H,K", [(0, 12, 4, 4), (7, 16, 5, 5),
                                        (21, 10, 3, 2)])
def test_fused_equals_ref_randomized(seed, T, H, K):
    """Randomized clusters/jobs, prices evolving via ref commits."""
    cluster = make_cluster(T=T, H=H, K=K)
    jobs = make_jobs(10, T=T, seed=seed, small=True)
    params = price_params_from_jobs(jobs, cluster)
    state = PriceState(cluster, params)
    for job in jobs:
        ref = best_schedule_ref(job, state)
        got = best_schedule_fused(job, state)
        assert_same_decision(job, state, ref, got)
        if ref is not None:
            state.commit(job, ref.workers, ref.ps)


@pytest.mark.parametrize("seed", range(3))
@pytest.mark.parametrize("window", [None, 16])
def test_resolve_after_interleaved_ops_equals_ref(seed, window):
    """Decisions after rounds of random commits, releases and window
    slides: the fused engine reads the padded price state that
    ``_padded_state`` patches span by span (``_pad_patch``) or re-pads,
    and must keep agreeing with the ref oracle round after round."""
    T = 24
    cluster = make_cluster(T=T, H=3, K=3)
    jobs = make_jobs(6, T=T, seed=100 + seed, small=True)
    params = price_params_from_jobs(jobs, cluster)
    state = PriceState(cluster, params, window=window)
    rng = np.random.default_rng(seed)
    committed = []
    for _ in range(5):
        for job in jobs:
            job = dataclasses.replace(job,
                                      arrival=job.arrival % state.horizon)
            ref = best_schedule_ref(job, state)
            got = best_schedule_fused(job, state)
            assert_same_decision(job, state, ref, got)
        apply_random_ops(rng, state, jobs, committed, n_ops=3,
                         allow_advance=window is not None)


def test_fused_pallas_sweep_path_equals_ref():
    """use_pallas=True (single-launch sweep kernel, interpret mode on CPU):
    decisions must match ref; f32 kernel => looser payoff tolerance.  The
    d_left == 0 backtrack guard in the wrapper protects the mixed-precision
    (f64 rows / f32 cost table) argmin recovery."""
    cluster = make_cluster(T=10, H=3, K=3)
    jobs = make_jobs(6, T=10, seed=1, small=True)
    params = price_params_from_jobs(jobs, cluster)
    state = PriceState(cluster, params)
    for job in jobs:
        ref = best_schedule_ref(job, state)
        got = best_schedule_fused(job, state, use_pallas=True)
        assert (ref is None) == (got is None), job.jid
        if ref is not None:
            assert got.finish == ref.finish
            assert got.payoff == pytest.approx(ref.payoff, rel=1e-4, abs=1e-6)
            state.commit(job, ref.workers, ref.ps)


def test_fused_batch_equals_ref_at_fixed_state():
    cluster = make_cluster(T=14, H=4, K=4)
    jobs = make_jobs(8, T=14, seed=5, small=True)
    params = price_params_from_jobs(jobs, cluster)
    state = PriceState(cluster, params)
    cands = best_schedule_fused_batch(jobs, state)
    for job, got in zip(jobs, cands):
        ref = best_schedule_ref(job, state)
        assert_same_decision(job, state, ref, got)


def test_fused_engine_empty_ps_pool():
    """K = 0: every job needing PS bandwidth must be rejected, not crash."""
    cluster = ClusterSpec(T=8, worker_caps=np.full((3, 5), 16.0),
                          ps_caps=np.zeros((0, 5)))
    job = mk_job()
    params = price_params_from_jobs([job], cluster)
    state = PriceState(cluster, params)
    assert best_schedule_ref(job, state) is None
    assert best_schedule_fused(job, state) is None
    assert best_schedule(job, state) is None


def test_fused_engine_empty_worker_pool():
    cluster = ClusterSpec(T=8, worker_caps=np.zeros((0, 5)),
                          ps_caps=np.full((3, 5), 16.0))
    job = mk_job()
    params = price_params_from_jobs([job], cluster)
    state = PriceState(cluster, params)
    assert best_schedule_ref(job, state) is None
    assert best_schedule_fused(job, state) is None
    assert best_schedule(job, state) is None


def test_fused_engine_zero_ps_demand():
    """Worker-only jobs (all-zero ps_res) are legal: pricing must not divide
    by zero and all three backends must agree."""
    cluster = make_cluster(T=10, H=3, K=3)
    job = mk_job(s=np.zeros(5))
    params = price_params_from_jobs([job], cluster)   # regression: ssum == 0
    state = PriceState(cluster, params)
    ref = best_schedule_ref(job, state)
    assert_same_decision(job, state, ref, best_schedule_fused(job, state))
    assert_same_decision(job, state, ref, best_schedule(job, state))
    assert ref is not None and ref.cost >= 0


def test_fused_engine_dcap_zero():
    """A job whose single-slot chunk time exceeds N can never run: dcap = 0."""
    job = mk_job(N=1, M=100, tau=0.5)
    assert min(job.max_chunks_per_slot, job.workload) == 0
    cluster = make_cluster(T=8, H=3, K=3)
    params = price_params_from_jobs([job], cluster)
    state = PriceState(cluster, params)
    assert best_schedule_ref(job, state) is None
    assert best_schedule_fused(job, state) is None
    assert best_schedule(job, state) is None


def test_greedy_cost_empty_pool_no_crash():
    """Regression: empty server pool used to index scost[-1] and crash."""
    out = _greedy_cost_for_counts(np.array([], np.int64), np.array([]),
                                  np.array([]), np.array([0, 1, 5]))
    assert out[0] == 0.0 and np.isinf(out[1]) and np.isinf(out[2])


def test_vectorized_rows_match_seed_loop():
    """The whole-array COST-row builder == the seed per-slot-loop builder."""
    cluster = make_cluster(T=12, H=4, K=4)
    jobs = make_jobs(6, T=12, seed=9, small=True)
    params = price_params_from_jobs(jobs, cluster)
    state = PriceState(cluster, params)
    rng = np.random.default_rng(0)
    # random occupancy, but only on resources the pool actually has —
    # allocations on zero-capacity resources are unreachable via commit()
    state.g = rng.uniform(0, 3, state.g.shape) * (cluster.worker_caps[None] > 0)
    state.v = rng.uniform(0, 3, state.v.shape) * (cluster.ps_caps[None] > 0)
    p, q = state.worker_prices(), state.ps_prices()
    for job in jobs:
        dcap = min(job.max_chunks_per_slot, job.workload)
        if dcap == 0:
            continue
        fast = cost_t_rows(job, state, p, q, dcap)
        loop = cost_t_rows_loop(job, state, p, q, dcap)
        both_inf = np.isinf(fast) & np.isinf(loop)
        assert np.all(both_inf | (np.abs(fast - loop) < 1e-9)), job.jid


@pytest.mark.parametrize("T,H,K", [(18, 5, 5), (40, 8, 8), (64, 6, 10)])
@pytest.mark.parametrize("seed", [13, 14])
def test_on_arrivals_equals_sequential_on_arrival(T, H, K, seed):
    """Batched admission == sequential Alg. 1, job for job."""
    cluster = make_cluster(T=T, H=H, K=K)
    jobs = make_jobs(20, T=T, seed=seed, small=True)
    params = price_params_from_jobs(jobs, cluster)
    seq = OASiS(cluster, params, impl="jax")
    for j in sorted(jobs, key=lambda x: (x.arrival, x.jid)):
        seq.on_arrival(j)
    bat = OASiS(cluster, params, impl="jax")
    by_slot = {}
    for j in jobs:
        by_slot.setdefault(j.arrival, []).append(j)
    for t in range(cluster.T):
        bat.on_arrivals(by_slot.get(t, []))
    assert set(seq.accepted) == set(bat.accepted)
    assert bat.total_utility == pytest.approx(seq.total_utility, rel=1e-9)
    for jid, s in seq.accepted.items():
        assert bat.accepted[jid].finish == s.finish


def _slotted_run(cluster, jobs):
    """``on_arrivals`` over each slot's arrivals, in jid order."""
    bat = OASiS(cluster, price_params_from_jobs(jobs, cluster), impl="jax")
    by_slot = {}
    for j in jobs:
        by_slot.setdefault(j.arrival, []).append(j)
    for t in range(cluster.T):
        bat.on_arrivals(sorted(by_slot.get(t, []), key=lambda x: x.jid))
    return bat


@pytest.mark.parametrize("T,H,K", [(18, 5, 5), (64, 6, 10)])
def test_eight_lane_burst_equals_single_lane(monkeypatch, T, H, K):
    """A burst decided in one 8-lane launch (the TPU's lane count) makes
    the same decisions and placements, bit for bit, as the same burst
    decided lane by lane (the CPU's)."""
    from repro.core import schedule_jax
    cluster = make_cluster(T=T, H=H, K=K)
    jobs = make_jobs(2 * T, T=T, seed=13, small=True)
    monkeypatch.setattr(schedule_jax, "_MAX_LANES", 1)
    one = _slotted_run(cluster, jobs)
    monkeypatch.setattr(schedule_jax, "_MAX_LANES", 8)
    eight = _slotted_run(cluster, jobs)
    assert len(one.accepted) > 0
    assert set(one.accepted) == set(eight.accepted)
    assert sorted(one.rejected) == sorted(eight.rejected)
    assert eight.total_utility == one.total_utility       # exact
    for jid, s in one.accepted.items():
        b = eight.accepted[jid]
        assert (b.finish, b.cost) == (s.finish, s.cost), jid
        assert set(b.workers) == set(s.workers), jid
        for t in s.workers:
            assert np.array_equal(b.workers[t], s.workers[t]), (jid, t)
            assert np.array_equal(b.ps[t], s.ps[t]), (jid, t)


def test_simulator_capacity_sweep_jax_impl():
    """Every allocation the fused engine commits stays within capacity at
    every slot (simulator asserts via _check_capacity), including with a
    worker-only job in the mix."""
    cluster = make_cluster(T=20, H=6, K=6)
    jobs = make_jobs(24, T=20, seed=3, small=True)
    jobs.append(dataclasses.replace(jobs[0], jid=len(jobs),
                                    ps_res=np.zeros(5)))
    r = simulate(cluster, jobs, scheduler="oasis", impl="jax", check=True)
    r2 = simulate(cluster, jobs, scheduler="oasis", impl="fast", check=True)
    assert r.accepted == r2.accepted
    assert r.total_utility == pytest.approx(r2.total_utility, rel=1e-9)


def test_jax_equals_fast_on_tie_heavy_workload():
    """Regression for the float32 downcast: identical constant-utility jobs
    on identical servers produce payoff ties across many finish slots; the
    jax engine must resolve them exactly like the float64 numpy path."""
    w = np.full((4, 5), 20.0)
    s = np.full((4, 5), 20.0)
    cluster = ClusterSpec(T=12, worker_caps=w, ps_caps=s)
    jobs = [mk_job(jid=i, a=i % 3, g=(10.0, 0.0, 1.0)) for i in range(8)]
    params = price_params_from_jobs(jobs, cluster)
    fast = OASiS(cluster, params, impl="fast")
    fz = OASiS(cluster, params, impl="jax")
    for j in jobs:
        fast.on_arrival(j)
        fz.on_arrival(j)
    assert set(fast.accepted) == set(fz.accepted)
    assert sorted(fast.rejected) == sorted(fz.rejected)
    for jid in fast.accepted:
        assert fz.accepted[jid].finish == fast.accepted[jid].finish
    assert fz.total_utility == pytest.approx(fast.total_utility, rel=1e-9)


@pytest.mark.parametrize("seed", range(5))
def test_backend_trajectories_identical_paper_scale(seed):
    """The acceptance contract: on the 5 seeded paper-scale equivalence
    instances (the same T=100 / 50+50 / 200-job setting test_sim_v2 pins
    v1-vs-v2 on), the fused jax engine — burst-batched, tiled, row-cached
    — reproduces the ref oracle's whole trajectory BIT-identically:
    accept set, completion slots, and total utility (exact float
    equality, not approx) — with the monotone min-plus dispatch active
    (the obs path counters ``decide.path_*`` must show its fast paths
    firing, i.e. the chain fallback stays below 100% on every
    instance)."""
    from repro import obs as obslib
    from repro.sim import simulate
    cluster = make_cluster(T=100, H=50, K=50)
    jobs = make_jobs(200, T=100, seed=seed, small=True)
    a = simulate(cluster, jobs, scheduler="oasis", impl="ref", quantum=0)
    ob = obslib.Obs()
    with obslib.activate(ob):
        b = simulate(cluster, jobs, scheduler="oasis", impl="jax",
                     quantum=0)
    c = ob.metrics.snapshot()["counters"]
    snap = {k: c.get(f"decide.path_{k}", 0)
            for k in ("dnc", "plateau", "chain")}
    assert sum(snap.values()) > 0, "monotone dispatch inactive"
    assert snap["chain"] < sum(snap.values()), f"fallback at 100%: {snap}"
    assert a.completion == b.completion
    assert a.accepted == b.accepted
    assert a.total_utility == b.total_utility


def test_on_arrivals_burst_equals_sequential_full_size_jobs():
    """Regression for the split-tie trajectory fork: with full-size jobs
    the DP cost sits on near-zero tie plateaus, and two launch shapes
    (lane-batched burst vs B=1 sequential) can disagree in the last ulps
    of a DP cell.  The eps-banded backtrack (_SPLIT_TOL) must keep the
    burst path's placements — not just its accept set — identical to the
    sequential path, or the committed price trajectories fork."""
    from repro.sim.engine import _with_quantum
    T, H, K = 60, 40, 40
    cluster = make_cluster(T=T, H=H, K=K)
    jobs = [_with_quantum(j, 0)
            for j in make_jobs(100, T=T, seed=0, small=False)]
    params = price_params_from_jobs(jobs, cluster)
    seq = OASiS(cluster, params, impl="jax")
    for j in sorted(jobs, key=lambda x: (x.arrival, x.jid)):
        seq.on_arrival(j)
    bat = OASiS(cluster, params, impl="jax")
    by_slot = {}
    for j in jobs:
        by_slot.setdefault(j.arrival, []).append(j)
    for t in range(T):
        bat.on_arrivals(sorted(by_slot.get(t, []), key=lambda x: x.jid))
    assert set(seq.accepted) == set(bat.accepted)
    assert bat.total_utility == seq.total_utility       # exact
    for jid, s in seq.accepted.items():
        b = bat.accepted[jid]
        assert b.finish == s.finish
        for t in s.workers:
            assert np.array_equal(b.workers[t], s.workers[t]), (jid, t)
            assert np.array_equal(b.ps[t], s.ps[t]), (jid, t)


def test_dp_sweep_jax_respects_x64():
    """dp_sweep_jax keeps float64 when jax_enable_x64 is on (the seed cast
    everything to float32, silently diverging near ties)."""
    import jax
    rng = np.random.default_rng(1)
    rows = rng.random((6, 5))
    rows[:, 0] = 0.0
    # values differing only at 1e-9 — indistinguishable in float32
    rows[2, 1] = 0.5
    rows[3, 1] = 0.5 + 1e-9
    with jax.enable_x64(True):
        cost, split = dp_sweep_jax(rows, 8)
    prev = np.full(9, np.inf)
    prev[0] = 0.0
    for i in range(6):
        want, arg = minplus_band(prev, rows[i])
        both_inf = np.isinf(want) & np.isinf(cost[i])
        assert np.all(both_inf | (np.abs(cost[i] - want) < 1e-12)), i
        assert np.array_equal(split[i], arg), i
        prev = want
