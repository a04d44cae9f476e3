"""Alg. 1 — OASiS online admission + scheduling loop.

With ``impl="jax"`` decisions stream through the persistent fused engine
(`core/schedule_jax.py`): compiled executables are keyed by shape bucket
and read dual prices directly from the device-resident ``PriceState``
(`core/pricing.py`), whose ``commit``/``release`` apply jit slot-window
adds instead of re-uploading the full allocation tables per accepted job.
"""
from __future__ import annotations

import time
from typing import Dict, List, Optional

import numpy as np

from .pricing import PriceParams, PriceState
from .subroutine import best_schedule, best_schedule_ref
from .types import ClusterSpec, Job, Schedule
from .. import obs as _obs


class OASiS:
    """Online scheduler: admit iff the best schedule has positive payoff.

    ``impl`` selects the dual-subroutine backend:
      * ``"ref"``    — loop-faithful Alg. 2 (test oracle; slow)
      * ``"fast"``   — vectorized numpy (default)
      * ``"jax"``    — fused jit engine (one XLA call per decision; Pallas
                       min-plus sweep kernel on TPU) with vmapped batching
                       via :meth:`on_arrivals`
      * ``"loop"``   — the seed's per-slot-loop numpy path (benchmark
                       baseline only)

    Example — one Alg. 1 pass over a tiny trace::

        >>> from repro.core.oasis import OASiS
        >>> from repro.core.pricing import price_params_from_jobs
        >>> from repro.sim.workload import make_cluster, make_jobs
        >>> cluster = make_cluster(T=20, H=3, K=3)
        >>> jobs = sorted(make_jobs(4, T=20, seed=0, small=True),
        ...               key=lambda j: j.arrival)
        >>> sched = OASiS(cluster, price_params_from_jobs(jobs, cluster))
        >>> plans = sched.on_arrivals(jobs)
        >>> [p is not None for p in plans]     # admission decisions
        [True, True, True, True]
        >>> sorted(sched.accepted)
        [0, 1, 2, 3]
        >>> cap = sum(j.utility.gamma1 for j in jobs)   # sigmoid sup
        >>> 0 < sched.total_utility <= cap
        True
    """

    def __init__(self, cluster: ClusterSpec, params: PriceParams,
                 impl: str = "fast", track_duality: bool = False,
                 batch_threshold: int = 2, window: Optional[int] = None):
        self.cluster = cluster
        # ``window`` bounds the price-state's resident slots for the
        # continuous serving mode (sim/engine.py ``run_stream``): decisions
        # then index window-local slots and the caller is responsible for
        # ``state.advance``-ing the origin to each arrival's slot.  The
        # default keeps the full fixed-horizon tables.
        self.state = PriceState(cluster, params, window=window)
        self.impl = impl
        # min batch size before on_arrivals uses the vmapped engine
        self.batch_threshold = max(2, batch_threshold)
        self.accepted: Dict[int, Schedule] = {}
        self.rejected: List[int] = []
        self.total_utility = 0.0
        self.decision_seconds: List[float] = []
        # Lemma-2 instrumentation: per-accepted-job primal/dual increments
        # (P_i - P_{i-1}, D_i - D_{i-1}); tests assert the allocation-cost
        # relationship  ΔP >= ΔD / alpha  that drives Theorem 4.
        self.track_duality = track_duality
        self.primal_deltas: List[float] = []
        self.dual_deltas: List[float] = []

    # -- Alg. 1 "upon arrival of job i" ------------------------------------
    def propose(self, job: Job) -> Optional[Schedule]:
        """Alg. 2 candidate at current prices (no commitment, no state
        change beyond latency accounting).  ``None`` means no schedule has
        positive payoff — Alg. 1 would reject.  Split from ``on_arrival``
        so an external decider (the rl/ env's admission gate) can veto or
        confirm the commitment."""
        t0 = time.perf_counter()
        with _obs.span("decide", jid=job.jid, impl=self.impl):
            if self.impl == "ref":
                sched = best_schedule_ref(job, self.state)
            elif self.impl == "jax":
                sched = best_schedule(job, self.state, use_jax=True)
            elif self.impl == "loop":
                sched = best_schedule(job, self.state, rows_impl="loop")
            else:
                sched = best_schedule(job, self.state)
        dt = time.perf_counter() - t0
        self.decision_seconds.append(dt)
        if _obs.ENABLED:
            _obs.inc("decide.decisions")
            _obs.observe("decide.seconds", dt)
        return sched

    def on_arrival(self, job: Job) -> Optional[Schedule]:
        return self._resolve(job, self.propose(job))

    def on_arrivals(self, jobs: List[Job]) -> List[Optional[Schedule]]:
        """Batched arrivals: decide the whole burst in one engine launch
        per shape bucket, then commit sequentially.

        Alg. 1 semantics are preserved exactly.  Candidates are speculative
        (computed at the prices in effect when the batch starts):

        * a REJECTED candidate is final — commits only raise prices and
          shrink headroom, so every schedule's payoff can only decrease and
          a non-positive maximum stays non-positive;
        * an ACCEPTED candidate is used as-is only while no other job from
          the batch has been admitted; once prices move it is re-solved
          from scratch against the updated state (``best_schedule_fused``).

        The result is identical, job for job, to calling ``on_arrival`` in
        sequence (stable arrival order).
        """
        order = sorted(range(len(jobs)), key=lambda i: jobs[i].arrival)
        out: List[Optional[Schedule]] = [None] * len(jobs)
        if self.impl != "jax" or len(jobs) < self.batch_threshold:
            for i in order:
                out[i] = self.on_arrival(jobs[i])
            return out
        import jax
        import jax.numpy as jnp
        from .schedule_jax import (_materialize, _state_arrays, _x64_context,
                                   best_schedule_fused, decide_burst)
        times: List[float] = []
        with _obs.span("decide_burst", n=len(jobs), impl=self.impl):
            pends = decide_burst([jobs[i] for i in order], self.state,
                                 timings=times)
        prices_moved = False
        with _x64_context("auto"):
            dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
            for pos, (i, pend) in enumerate(zip(order, pends)):
                if pend is None:                  # dcap == 0: trivial reject
                    self.decision_seconds.append(times[pos])
                    out[i] = self._resolve(jobs[i], None)
                elif pend.best_t < 0 or not prices_moved:
                    # speculative reject is final; speculative accept is
                    # valid while no earlier job in the burst committed
                    sched = None
                    t0 = time.perf_counter()
                    if pend.best_t >= 0:
                        with _obs.span("decide.prep", jid=jobs[i].jid):
                            sd = _state_arrays(self.state, dtype)
                        sched = _materialize(pend, self.state, sd, dtype)
                    self.decision_seconds.append(
                        times[pos] + time.perf_counter() - t0)
                    out[i] = self._resolve(jobs[i], sched)
                    prices_moved = prices_moved or out[i] is not None
                else:
                    # prices moved: re-solve at the current prices
                    t0 = time.perf_counter()
                    with _obs.span("decide.resolve", jid=jobs[i].jid):
                        sched = best_schedule_fused(jobs[i], self.state)
                    # the speculative batch share spent on this job is real
                    # per-decision cost too — don't under-report latency
                    self.decision_seconds.append(
                        time.perf_counter() - t0 + times[pos])
                    out[i] = self._resolve(jobs[i], sched)
        if _obs.ENABLED:
            _obs.inc("decide.decisions", len(jobs))
        return out

    def _resolve(self, job: Job, sched: Optional[Schedule]
                 ) -> Optional[Schedule]:
        """Alg. 1 lines 5-11: admit iff positive payoff, commit, bump prices."""
        if sched is None:                       # mu_i <= 0 -> reject
            self.rejected.append(job.jid)
            return None
        if self.track_duality:
            # prices move only inside the committed slot window, so the
            # Lemma-2 increments are computed from those slots alone
            # (elementwise prices: unchanged entries difference to exactly
            # 0.0) instead of materializing the full (T,H,R)+(T,K,R)
            # exponential tables twice per accepted job
            w_slots = np.fromiter(sched.workers.keys(), dtype=np.int64,
                                  count=len(sched.workers))
            z_slots = np.fromiter(sched.ps.keys(), dtype=np.int64,
                                  count=len(sched.ps))
            p0 = self.state.worker_prices_at(w_slots)
            q0 = self.state.ps_prices_at(z_slots)
        self.state.commit(job, sched.workers, sched.ps)
        if self.track_duality:
            p1 = self.state.worker_prices_at(w_slots)
            q1 = self.state.ps_prices_at(z_slots)
            # ΔD = mu_i + Σ (p' - p) c_h + Σ (q' - q) c_k   (Lemma 2)
            d_delta = sched.payoff
            d_delta += float(((p1 - p0) *
                              self.cluster.worker_caps[None]).sum())
            d_delta += float(((q1 - q0) * self.cluster.ps_caps[None]).sum())
            self.primal_deltas.append(sched.utility)
            self.dual_deltas.append(d_delta)
        self.accepted[job.jid] = sched
        self.total_utility += sched.utility
        return sched

    # -- views used by the simulator ---------------------------------------
    def allocation_at(self, t: int) -> Dict[int, tuple]:
        out = {}
        for jid, sched in self.accepted.items():
            if t in sched.workers:
                out[jid] = (sched.workers[t], sched.ps.get(t))
        return out
