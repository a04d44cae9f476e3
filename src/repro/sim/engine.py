"""Event-driven trace simulator (sim v2).

The v1 simulator (`sim/simulator.py`, kept as ``simulate_reference``) steps
every slot in Python and re-plans / re-accounts per job per slot.  This
engine only enters Python on *events* — arrival bursts, completions,
cancellations — and does everything between events as whole-array numpy
ops over dense per-job state:

* **Reactive baselines** (FIFO/DRF/RRH/Dorm): between two events the
  scheduler's ``step(t)`` output is constant (running jobs keep their
  placement; waiting jobs face unchanged free capacity; DRF/Dorm repack
  deterministically from an unchanged job set), so the engine replans only
  at event slots and fast-forwards work progress with one vectorized
  update: per-job completion slots are ``ceil(remaining / rate)`` over the
  whole live set, and the clock jumps to the earliest completion or the
  next event.  Even at event slots the repack is skipped when the
  scheduler's ``dirty`` flag says the event cannot change the plan (a
  completion with an empty wait queue under FIFO/RRH, a rejected RRH
  arrival): the previous allocation, pruned of departed jobs, is provably
  what ``step`` would return.  The repacks themselves run on the
  vectorized batch-round kernels of ``core/repack.py`` (placement-equal
  to the seed's greedy loops, ``tests/test_repack.py``).
* **OASiS**: schedules are committed at arrival, so arrivals are the only
  plan events; arrival bursts go through the batched (vmapped on
  ``impl="jax"``) ``on_arrivals`` path, per-slot GPU usage is read
  straight off the price-state's allocation tensor
  (``PriceState.gpu_slot_usage``), and capacity feasibility is one
  whole-state comparison (``PriceState.capacity_ok``) instead of a
  per-slot Python walk.  On ``impl="jax"`` the price state is
  device-resident with commits streamed as slot-window stores, so the whole
  run performs O(1) full host↔device syncs (``PriceState.device_uploads``).

On cancellation-free, unperturbed workloads the engine is equivalence-
tested against the v1 loop (utilities, accept/complete counts, completion
slots) in ``tests/test_sim_v2.py``.  Two scenario hooks go beyond v1:

* ``cancellations``: ``{jid: slot}`` — the job departs mid-run at
  ``slot``; its remaining allocation is released (OASiS: prices drop via
  ``PriceState.release``) and it earns no utility.  A slot at/before the
  job's arrival or at/after T is a no-op (the job runs, resp. finishes,
  normally) — identically for every scheduler.
* ``throughput``: ``fn(job, n_workers, slot) -> factor in (0, 1]`` — a
  per-(job, slot) multiplicative work-rate perturbation (e.g. stragglers,
  ``sim/scenarios.py``).  Under perturbation rates vary per slot; if the
  fn declares itself ``stateless`` and provides ``rate_matrix(job,
  n_workers, t0, h)``, the engine precomputes a ``(n_live,
  horizon_chunk)`` rate matrix per plan span and detects completions via
  row cumsums, consuming only the slots up to the earliest completion
  (the discarded suffix is recomputed after the replan — safe exactly
  because the fn is stateless).  Stateful fns (straggler detection) are
  called per (job, slot) in the original order, one slot at a time, still
  vectorized across jobs.  An OASiS job whose committed schedule
  under-delivers its total work is *not* completed and earns nothing.

Both loops are written as *decision generators*: every per-arrival
admission is a decision point that can be handed to an external decider.
``run(..., policy=None)`` consumes the generator internally with each
scheduler's own decisions — that path never yields and is the unchanged
sim-v2 semantics the equivalence suites pin.  ``run(..., policy=fn)``
(or driving :func:`decisions` step by step, as the rl/ env does) yields a
:class:`DecisionPoint` per arrival and applies the answer through the
same machinery: for ``scheduler="learned"`` the action is the per-job
(worker, PS) count or reject; for the named schedulers the action gates
admission while allocation follows the scheduler's own kernels, so a
policy replaying the expert action reproduces ``run`` exactly
(tests/test_rl_env.py).
"""
from __future__ import annotations

import dataclasses
import itertools
import math
import time
from typing import (Callable, Dict, Generator, Iterable, List, Optional,
                    Sequence, Tuple)

import numpy as np

from ..core.baselines import BASELINES, Learned, ReactiveScheduler
from ..core.oasis import OASiS
from ..core.pricing import PriceParams, price_params_from_jobs
from ..core.types import ClusterSpec, Job, Schedule, SigmoidUtility
from .fleet import DOWN_LOSSY, UP, FleetState, FleetTrace
from .. import obs as _obslib

ThroughputFn = Callable[[Job, int, int], float]

# slots of look-ahead in DecisionPoint capacity windows (rl/ observations)
DECISION_WINDOW = 8

# default checkpoint cadence for fleet churn, in slots: victims of a lossy
# failure roll back to the last multiple of this on the global clock — the
# slot-level analogue of runtime/driver.py::run_with_restarts(save_every=20)
CKPT_INTERVAL = 20


@dataclasses.dataclass
class SimResult:
    name: str
    total_utility: float
    accepted: int
    completed: int
    n_jobs: int
    completion: Dict[int, int]              # jid -> completion slot
    target_gap: List[float]                 # (t_done - a) - gamma3 per job
    decision_seconds: List[float]
    utilization: float                      # mean worker-pool GPU utilization
    canceled: int = 0                       # jobs departed mid-run (sim v2)
    # fleet churn (sim/fleet.py): preemption events suffered by admitted
    # jobs, and how many of those victims the shrunken fleet could not
    # re-admit (OASiS drops them; reactive baselines re-queue, never drop)
    preempted: int = 0
    preempt_dropped: int = 0
    # worker-pool GPU fraction still alive at the end of the run (1.0 on
    # churn-free runs — see FleetState.live_frac)
    live_frac: float = 1.0
    arrivals: Dict[int, int] = dataclasses.field(default_factory=dict)
    # streaming runs only: host bytes of the price-state's rolling window
    # (the peak-RSS proxy the serving benchmark records); None episodic,
    # 0 for the reactive baselines (they keep no slot-indexed state)
    window_bytes: Optional[int] = None

    def summary(self) -> Dict[str, object]:
        """Episode-level digest: accept/completion rates, latency
        percentiles (completion slot minus arrival), total utility.
        Shared by ``examples/cluster_sim.py`` and the rl/ env's terminal
        info dict; latency stats are ``None`` when nothing completed."""
        lat = np.array([self.completion[j] - self.arrivals[j]
                        for j in self.completion if j in self.arrivals],
                       dtype=float)
        n = max(self.n_jobs, 1)
        return {
            "scheduler": self.name,
            "n_jobs": self.n_jobs,
            "accepted": self.accepted,
            "completed": self.completed,
            "canceled": self.canceled,
            "preempted": self.preempted,
            "preempt_dropped": self.preempt_dropped,
            "live_frac": float(self.live_frac),
            "accept_rate": self.accepted / n,
            "completion_rate": self.completed / n,
            "total_utility": float(self.total_utility),
            "mean_latency": float(lat.mean()) if lat.size else None,
            "p50_latency": float(np.percentile(lat, 50)) if lat.size else None,
            "p95_latency": float(np.percentile(lat, 95)) if lat.size else None,
            "utilization": float(self.utilization),
        }


@dataclasses.dataclass
class DecisionPoint:
    """One per-arrival admission decision, yielded by :func:`decisions`.

    ``expert`` is the action that replays the wrapped scheduler's own
    decision — ``(n_workers, n_ps)`` with ``n_workers == 0`` meaning
    reject.  For plan-ahead OASiS the counts carry no meaning beyond
    admit/reject (the commitment is ``candidate``, Alg. 2's best
    schedule); for the reactive baselines the counts are informational
    (allocation follows the scheduler's own repack) and only
    ``scheduler="learned"`` consumes them literally.

    ``free_frac_workers``/``free_frac_ps`` are (DECISION_WINDOW, R)
    per-slot *free* capacity fractions of each pool over ``[t, t+W)``
    (slots at/after T read 0.0 — there is no capacity past the horizon);
    the reactive baselines' allocation is constant between events, so the
    current snapshot is tiled across the window.
    """

    job: Job
    t: int
    scheduler: str
    expert: Tuple[int, int]
    candidate: Optional[Schedule]
    utility_so_far: float
    n_running: int
    n_waiting: int
    accepted: int
    rejected: int
    free_frac_workers: np.ndarray
    free_frac_ps: np.ndarray
    # fleet churn (sim/fleet.py): fraction of the worker pool's GPU
    # capacity currently alive, and whether this decision re-admits a
    # preempted victim (its remaining work already rescaled).  Both keep
    # their defaults on churn-free runs, so the zero-churn observation
    # stream is unchanged.
    live_frac: float = 1.0
    preempted: bool = False


def _as_counts(action) -> Tuple[int, int]:
    """Normalize a decider's answer to ``(n_workers, n_ps)``; ``n_ps``
    of -1 means "derive the minimum feasible PS count"."""
    if action is None or action is False:
        return 0, -1
    if isinstance(action, (tuple, list, np.ndarray)):
        a = np.asarray(action).ravel()
        return max(int(a[0]), 0), int(a[1]) if a.size > 1 else -1
    return max(int(action), 0), -1


def _free_window(used_w: np.ndarray, used_s: np.ndarray,
                 cluster: ClusterSpec, t: int,
                 t_max: Optional[int] = ...) -> Tuple[np.ndarray, np.ndarray]:
    """(W, R) per-slot free-capacity fractions of both pools from
    per-slot pool-total usage (slots at/after ``t_max`` read 0.0 — no
    capacity past the horizon; ``t_max=None`` means open-ended, the
    streaming mode, and the default reads the episodic ``cluster.T``).
    A (R,) snapshot is tiled across the window (the reactive baselines'
    allocation is constant between events)."""
    W = DECISION_WINDOW
    cap_w = np.maximum(cluster.worker_caps.sum(axis=0), 1e-9)
    cap_s = np.maximum(cluster.ps_caps.sum(axis=0), 1e-9)
    fw = np.zeros((W, cap_w.shape[0]))
    fs = np.zeros((W, cap_s.shape[0]))
    if used_w.ndim == 1:
        used_w = np.tile(used_w, (W, 1))
        used_s = np.tile(used_s, (W, 1))
    fw[:used_w.shape[0]] = np.clip(1.0 - used_w / cap_w, 0.0, 1.0)
    fs[:used_s.shape[0]] = np.clip(1.0 - used_s / cap_s, 0.0, 1.0)
    if t_max is Ellipsis:
        t_max = cluster.T
    if t_max is not None:
        live = max(min(t_max - t, W), 0)
        fw[live:] = 0.0
        fs[live:] = 0.0
    return fw, fs


def _with_quantum(job: Job, quantum: Optional[int]) -> Job:
    """Workload quantization is a DP-granularity knob (``Job.workload``);
    it is applied uniformly here but only the OASiS subroutine reads it —
    reactive baselines schedule by ``total_work_slots``/``num_chunks``,
    which are quantum-independent (asserted in tests/test_sim_v2.py)."""
    if quantum is None:
        return job
    q = quantum if quantum > 0 else max(
        1, math.ceil(job.epochs * job.num_chunks / 1200))
    return dataclasses.replace(job, quantum=q)


def _shift_utility(u: Callable[[float], float],
                   shift: int) -> Callable[[float], float]:
    """Utility of a victim re-admitted ``shift`` slots after its original
    arrival: the engine evaluates durations from the *re-admission* slot,
    so the original ``f(d)`` becomes ``f(d + shift)`` — for the paper's
    sigmoid that is the same curve with the deadline pulled ``shift``
    slots closer.  Shifting always from the original job's utility (not
    the previous shifted copy) keeps repeated preemptions exact."""
    if not shift:
        return u
    if isinstance(u, SigmoidUtility):
        return dataclasses.replace(u, gamma3=u.gamma3 - shift)
    return lambda d, _u=u, _s=shift: _u(d + _s)


def _target_gaps(jmap: Dict[int, Job], completion: Dict[int, int]) -> List[float]:
    gaps = []
    for jid, tdone in completion.items():
        u = jmap[jid].utility
        if getattr(u, "gamma2", 0) > 0:
            gaps.append((tdone - jmap[jid].arrival) - u.gamma3)
    return gaps


def _group_events(jobs: Sequence[Job], cancellations: Optional[Dict[int, int]],
                  T: int) -> Tuple[Dict[int, List[Job]], Dict[int, List[int]]]:
    by_slot: Dict[int, List[Job]] = {}
    arrival = {}
    for j in jobs:
        if j.arrival >= T:          # v1 semantics: never seen by the sim
            continue
        by_slot.setdefault(j.arrival, []).append(j)
        arrival[j.jid] = j.arrival
    cancel_slot: Dict[int, List[int]] = {}
    for jid, c in (cancellations or {}).items():
        # a departure takes effect only for a job already admitted before
        # slot c and still inside the horizon; cancelling at/before arrival
        # or at/after T is a no-op (the job runs, resp. completes, normally)
        if jid in arrival and arrival[jid] < c < T:
            cancel_slot.setdefault(int(c), []).append(jid)
    return by_slot, cancel_slot


def _check_alloc(cluster: ClusterSpec, jmap: Dict[int, Job],
                 alloc: Dict[int, tuple],
                 worker_caps: Optional[np.ndarray] = None,
                 ps_caps: Optional[np.ndarray] = None) -> None:
    """Whole-array capacity feasibility of one allocation snapshot.

    ``worker_caps``/``ps_caps`` override the cluster's static capacities
    with the surviving fleet's effective arrays under churn — down
    servers then have 0-rows, so any placement on them trips the assert."""
    if not alloc:
        return
    wc = cluster.worker_caps if worker_caps is None else worker_caps
    pc = cluster.ps_caps if ps_caps is None else ps_caps
    ids = list(alloc)
    ys = np.stack([alloc[j][0] for j in ids]).astype(float)        # (n, H)
    wres = np.stack([jmap[j].worker_res for j in ids])             # (n, R)
    assert np.all(ys.T @ wres <= wc + 1e-6), \
        "worker capacity violated"
    zs = [(j, alloc[j][1]) for j in ids if alloc[j][1] is not None]
    if zs:
        zmat = np.stack([z for _, z in zs]).astype(float)
        sres = np.stack([jmap[j].ps_res for j, _ in zs])
        assert np.all(zmat.T @ sres <= pc + 1e-6), \
            "PS capacity violated"


def decisions(cluster: ClusterSpec, jobs: Sequence[Job],
              scheduler: str = "oasis",
              params: Optional[PriceParams] = None, impl: str = "fast",
              fixed_workers: int = 8, check: bool = True,
              quantum: Optional[int] = None,
              cancellations: Optional[Dict[int, int]] = None,
              throughput: Optional[ThroughputFn] = None,
              fleet: Optional[FleetTrace] = None,
              ckpt_interval: int = CKPT_INTERVAL
              ) -> Generator[DecisionPoint, object, SimResult]:
    """The engine as a stepwise decision process (the rl/ env's substrate).

    Yields a :class:`DecisionPoint` per arrival; the caller ``send``s the
    action — ``(n_workers, n_ps)``, a bare worker count, or ``None``/0 to
    reject — and the final :class:`SimResult` is the generator's return
    value (``StopIteration.value``).  With a non-empty ``fleet`` trace,
    victim re-admissions are decision points too (``preempted=True``).
    """
    if scheduler == "oasis":
        return _drive_oasis(cluster, jobs, params, impl, check, quantum,
                            cancellations, throughput, decide=True,
                            fleet=fleet, ckpt_interval=ckpt_interval)
    return _drive_reactive(cluster, jobs, scheduler, fixed_workers, check,
                           quantum, cancellations, throughput, decide=True,
                           fleet=fleet, ckpt_interval=ckpt_interval)


def _exhaust(gen) -> SimResult:
    try:
        next(gen)
    except StopIteration as e:
        return e.value
    raise RuntimeError("engine yielded a decision point without a policy")


def run(cluster: ClusterSpec, jobs: Sequence[Job], scheduler: str = "oasis",
        params: Optional[PriceParams] = None, impl: str = "fast",
        fixed_workers: int = 8, check: bool = True,
        quantum: Optional[int] = None,
        cancellations: Optional[Dict[int, int]] = None,
        throughput: Optional[ThroughputFn] = None,
        fleet: Optional[FleetTrace] = None,
        ckpt_interval: int = CKPT_INTERVAL,
        policy: Optional[Callable[[DecisionPoint], object]] = None,
        obs: Optional["_obslib.Obs"] = None
        ) -> SimResult:
    """Drive ``scheduler`` through the trace event-by-event.

    Same contract as the v1 ``simulate`` plus the scenario hooks
    documented in the module docstring.  ``policy`` (required for
    ``scheduler="learned"``) answers each per-arrival decision point —
    see :func:`decisions`; without one the scheduler decides for itself
    on the exact pre-existing code path (no generator yields).
    ``obs`` installs a flight recorder (``repro.obs.Obs``) for the
    duration of the run — spans and counters land in it and tracing is
    torn back down on return; ``None`` (the default) records nothing.

    Example — the same four-job trace under a reactive baseline and
    OASiS (price params derived from the trace when not given)::

        >>> from repro.sim import engine
        >>> from repro.sim.workload import make_cluster, make_jobs
        >>> cluster = make_cluster(T=20, H=3, K=3)
        >>> jobs = make_jobs(4, T=20, seed=0, small=True)
        >>> r = engine.run(cluster, jobs, scheduler="fifo")
        >>> (r.n_jobs, r.accepted, r.completed)
        (4, 4, 4)
        >>> r = engine.run(cluster, jobs, scheduler="oasis")
        >>> r.accepted, r.total_utility > 0
        (4, True)
    """
    if scheduler == "learned" and policy is None:
        raise ValueError(
            "scheduler='learned' needs a policy — pass engine.run(..., "
            "policy=...) (see repro.rl.policy.LearnedDecider) or train one "
            "via repro.rl.train")
    with _obslib.activate(obs):
        if policy is None:
            if scheduler == "oasis":
                return _exhaust(_drive_oasis(cluster, jobs, params, impl,
                                             check, quantum, cancellations,
                                             throughput, decide=False,
                                             fleet=fleet,
                                             ckpt_interval=ckpt_interval))
            return _exhaust(_drive_reactive(cluster, jobs, scheduler,
                                            fixed_workers, check, quantum,
                                            cancellations, throughput,
                                            decide=False, fleet=fleet,
                                            ckpt_interval=ckpt_interval))
        gen = decisions(cluster, jobs, scheduler=scheduler, params=params,
                        impl=impl, fixed_workers=fixed_workers, check=check,
                        quantum=quantum, cancellations=cancellations,
                        throughput=throughput, fleet=fleet,
                        ckpt_interval=ckpt_interval)
        policy_seconds: List[float] = []
        try:
            dp = next(gen)
            while True:
                t0 = time.perf_counter()
                action = policy(dp)
                policy_seconds.append(time.perf_counter() - t0)
                dp = gen.send(action)
        except StopIteration as e:
            result = e.value
            if not result.decision_seconds:  # reactive decide-paths: none
                result.decision_seconds = policy_seconds
            return result


# ---------------------------------------------------------------------------
# OASiS (plan-ahead): arrivals and cancellations are the only events.
# ---------------------------------------------------------------------------

def _oasis_decision_point(osched: OASiS, cluster: ClusterSpec, job: Job,
                          t: int, cand: Optional[Schedule],
                          utility_so_far: float, live_frac: float = 1.0,
                          preempted: bool = False) -> DecisionPoint:
    g_win, v_win = osched.state.alloc_window(t, DECISION_WINDOW)
    fw, fs = _free_window(g_win, v_win, cluster, t)
    n_running = sum(1 for s in osched.accepted.values() if s.finish >= t)
    return DecisionPoint(
        job=job, t=t, scheduler="oasis",
        expert=(1, 0) if cand is not None else (0, 0), candidate=cand,
        utility_so_far=utility_so_far, n_running=n_running, n_waiting=0,
        accepted=len(osched.accepted), rejected=len(osched.rejected),
        free_frac_workers=fw, free_frac_ps=fs,
        live_frac=live_frac, preempted=preempted)


def _x64_run(impl: str, decide: bool):
    """One x64 context held across a whole jax-engine run (CPU only).

    Every ``enable_x64`` entry/exit inside ``best_schedule_fused`` flips
    the thread-local config, and each flip knocks subsequent jit calls
    off their C fast path — milliseconds of python dispatch per
    decision.  Holding one context open makes the per-decision entries
    no-ops (``_x64_context`` short-circuits when x64 is already on)
    without changing any computed value.  Skipped in stepwise
    (``decide``) mode: those generators suspend into caller policy code
    that must not inherit the flag."""
    import contextlib
    if impl != "jax" or decide:
        return contextlib.nullcontext()
    import jax
    if jax.default_backend() != "cpu" or jax.config.jax_enable_x64:
        return contextlib.nullcontext()
    return jax.enable_x64(True)


def _drive_oasis(cluster: ClusterSpec, jobs: Sequence[Job],
                 params: Optional[PriceParams], impl: str, check: bool,
                 quantum: Optional[int],
                 cancellations: Optional[Dict[int, int]],
                 throughput: Optional[ThroughputFn], decide: bool,
                 fleet: Optional[FleetTrace] = None,
                 ckpt_interval: int = CKPT_INTERVAL
                 ) -> Generator[DecisionPoint, object, SimResult]:
    with _x64_run(impl, decide):
        result = yield from _drive_oasis_gen(
            cluster, jobs, params, impl, check, quantum, cancellations,
            throughput, decide, fleet=fleet, ckpt_interval=ckpt_interval)
    return result


def _drive_oasis_gen(cluster: ClusterSpec, jobs: Sequence[Job],
                     params: Optional[PriceParams], impl: str, check: bool,
                     quantum: Optional[int],
                     cancellations: Optional[Dict[int, int]],
                     throughput: Optional[ThroughputFn], decide: bool,
                     fleet: Optional[FleetTrace] = None,
                     ckpt_interval: int = CKPT_INTERVAL
                     ) -> Generator[DecisionPoint, object, SimResult]:
    T = cluster.T
    jmap = {j.jid: j for j in jobs}
    by_slot, cancel_slot = _group_events(jobs, cancellations, T)
    params = params or price_params_from_jobs(jobs, cluster)
    osched = OASiS(cluster, params, impl=impl)

    total_gpu = max(float(cluster.worker_caps[:, 0].sum()), 1e-9)
    canceled: set = set()
    # fleet churn: every churn branch below is gated on a non-empty trace,
    # so the empty-trace run is an exact no-op (tests/test_fleet.py pins
    # bit-identity against the pre-churn engine)
    churn = fleet is not None and bool(fleet)
    fs = FleetState(cluster, fleet) if churn else None
    # current job copy per jid: re-admitted victims are rescaled replicas
    # (work_scale < 1); identical to jmap on churn-free runs
    ljobs = dict(jmap) if churn else jmap
    ck = max(int(ckpt_interval), 1)
    forced_completion: Dict[int, int] = {}
    blocked_gpu = 0.0          # filler GPU-slot area on down servers
    n_preempted = 0
    n_dropped = 0

    slots = set(by_slot) | set(cancel_slot)
    if churn:
        slots |= set(fs.event_slots)
    for t in sorted(slots):
        if churn:
            trans = fs.step(t)
            _cs = _obslib.span("churn_step", t=t, transitions=len(trans))
            _cs.__enter__()
            # recoveries first: restored headroom is visible to this
            # slot's re-admissions and arrivals
            for pool, srv, kind in trans:
                if kind == UP:
                    blocked_gpu -= osched.state.unblock_server(pool, srv, t)
            victims: Dict[int, str] = {}
            for pool, srv, kind in trans:
                if kind == UP:
                    continue
                for jid, sched in osched.accepted.items():
                    if jid in victims or jid in canceled or sched.finish < t:
                        continue
                    alloc = sched.workers if pool == "worker" else sched.ps
                    if any(tt >= t and a[srv] > 0
                           for tt, a in alloc.items()):
                        victims[jid] = kind
            readmit: List[Job] = []
            for jid, kind in victims.items():
                sched = osched.accepted.pop(jid)
                jcur = ljobs[jid]
                tail_w = {tt: y for tt, y in sched.workers.items()
                          if tt >= t}
                tail_z = {tt: z for tt, z in sched.ps.items() if tt >= t}
                osched.state.release(jcur, tail_w, tail_z)
                osched.total_utility -= sched.utility
                n_preempted += 1
                if _obslib.ENABLED:
                    _obslib.inc("engine.preemptions")
                # checkpoint boundary: lossy failures roll back to the
                # last global ckpt_interval multiple, graceful drains
                # checkpoint at drain start (no work lost)
                cb = (t // ck) * ck if kind == DOWN_LOSSY else t
                delivered = sum(float(y.sum())
                                for tt, y in sched.workers.items()
                                if tt < cb)
                rem = jcur.total_work_slots - delivered
                if rem <= 1e-9:
                    # the checkpoint already covers all work: the job is
                    # done as of its last delivering slot, no re-admission
                    done = [tt for tt, y in sched.workers.items()
                            if tt < cb and y.sum() > 0]
                    forced_completion[jid] = max(done) if done \
                        else max(cb - 1, 0)
                    continue
                scale = jcur.work_scale * rem / jcur.total_work_slots
                orig = jmap[jid]
                readmit.append(dataclasses.replace(
                    jcur, arrival=t, work_scale=scale,
                    utility=_shift_utility(orig.utility,
                                           t - orig.arrival)))
            # block AFTER the victims' tails are released (their content
            # is then exactly the fill) and BEFORE re-admission (Alg. 2
            # must not plan onto the dead servers)
            for pool, srv, kind in trans:
                if kind != UP:
                    blocked_gpu += osched.state.block_server(pool, srv, t)
            _cs.set(victims=len(victims), readmits=len(readmit))
            _cs.__exit__(None, None, None)
            for job_r in readmit:
                ljobs[job_r.jid] = job_r
                if decide:
                    cand = osched.propose(job_r)
                    action = yield _oasis_decision_point(
                        osched, cluster, job_r, t, cand,
                        osched.total_utility, live_frac=fs.live_frac,
                        preempted=True)
                    nw, _ = _as_counts(action)
                    sched = osched._resolve(job_r,
                                            cand if nw > 0 else None)
                else:
                    sched = osched.on_arrival(job_r)
                if sched is None:
                    n_dropped += 1
                    if _obslib.ENABLED:
                        _obslib.inc("engine.preempt_dropped")
        for jid in cancel_slot.get(t, ()):
            sched = osched.accepted.get(jid)
            if sched is None or sched.finish < t or jid in canceled:
                # finished / never admitted / already departed — includes
                # victims the shrunken fleet dropped: their commitment is
                # gone, so the cancellation must be (and is) a no-op
                continue
            tail_w = {tt: y for tt, y in sched.workers.items() if tt >= t}
            tail_z = {tt: z for tt, z in sched.ps.items() if tt >= t}
            osched.state.release(ljobs[jid], tail_w, tail_z)
            canceled.add(jid)
        batch = [_with_quantum(job, quantum) for job in by_slot.get(t, ())]
        if churn:
            for job in batch:
                ljobs[job.jid] = job
        if _obslib.ENABLED and batch:
            _obslib.inc("engine.arrivals", len(batch))
        if decide:
            # stepwise: propose at current prices, let the decider gate
            # the commitment.  Sequential per-job decisions are exactly
            # the batched path's semantics (on_arrivals is equivalence-
            # tested against sequential on_arrival), with the external
            # action substituted for Alg. 1's payoff test.
            for job in sorted(batch, key=lambda j: j.arrival):
                cand = osched.propose(job)
                action = yield _oasis_decision_point(
                    osched, cluster, job, t, cand, osched.total_utility,
                    live_frac=fs.live_frac if churn else 1.0)
                nw, _ = _as_counts(action)
                osched._resolve(job, cand if nw > 0 else None)
        elif batch:
            with _obslib.span("arrival_burst", t=t, n=len(batch)):
                osched.on_arrivals(batch)
        if check:
            # whole-state comparison on the price-state's own books — no
            # per-schedule Python walk and no device→host churn on the
            # jax path (the host mirror is maintained incrementally)
            ok_w, ok_ps = osched.state.capacity_ok()
            assert ok_w, "worker capacity violated"
            assert ok_ps, "PS capacity violated"

    completion: Dict[int, int] = {}
    for jid, sched in osched.accepted.items():
        if jid in canceled:
            continue
        if throughput is None:
            completion[jid] = sched.finish
            continue
        # perturbed work accounting over the committed slots (under churn
        # the live copy carries only the post-checkpoint work, and the
        # committed schedule is exactly its final segment)
        job = ljobs[jid]
        slots = sorted(sched.workers)
        w = np.array([float(sched.workers[tt].sum()) for tt in slots])
        f = np.array([throughput(job, int(c), tt)
                      for tt, c in zip(slots, w)])
        cum = np.cumsum(w * f)
        hit = np.flatnonzero(cum >= job.total_work_slots - 1e-9)
        if hit.size:                            # else: under-delivered
            completion[jid] = slots[int(hit[0])]
    completion.update(forced_completion)

    if not canceled and throughput is None and not churn:
        total_utility = osched.total_utility    # bit-exact vs v1
    else:
        # evaluate utility at the *actual* completion slot (under
        # perturbation it can differ from the committed finish; under
        # churn from the re-admission-shifted curve), always against the
        # ORIGINAL job's utility and arrival — matching the reactive
        # path's convention
        total_utility = sum(jmap[jid].utility(tdone - jmap[jid].arrival)
                            for jid, tdone in completion.items())
    # per-slot GPU usage straight off the allocation tensor (commits add,
    # cancellation releases subtract), replacing the per-schedule dict walk
    gpu_slots = osched.state.gpu_slot_usage()
    if churn and T:
        # subtract the capacity-block filler on down servers — it is in
        # the allocation tensor (that is what starves Alg. 2 of headroom)
        # but is not real usage
        utilization = float((gpu_slots.sum() - blocked_gpu)
                            / (total_gpu * T))
    else:
        utilization = float(np.mean(gpu_slots / total_gpu)) if T else 0.0
    return SimResult(name="oasis", total_utility=total_utility,
                     accepted=len(osched.accepted) + len(forced_completion),
                     completed=len(completion),
                     n_jobs=len(jobs), completion=completion,
                     target_gap=_target_gaps(jmap, completion),
                     decision_seconds=osched.decision_seconds,
                     utilization=utilization,
                     canceled=len(canceled),
                     preempted=n_preempted, preempt_dropped=n_dropped,
                     live_frac=fs.live_frac if churn else 1.0,
                     arrivals={j.jid: j.arrival for j in jobs
                               if j.arrival < T})


# ---------------------------------------------------------------------------
# Reactive baselines: replan at events, fast-forward in between.
# ---------------------------------------------------------------------------

# horizon chunk for the stateless-throughput rate matrix (slots per block)
_RATE_BLOCK = 64


def _pool_usage(cur_alloc: Dict[int, tuple], jmap: Dict[int, Job],
                cluster: ClusterSpec) -> Tuple[np.ndarray, np.ndarray]:
    """(R,) total worker/PS-pool usage of one allocation snapshot."""
    used_w = np.zeros(cluster.worker_caps.shape[1])
    used_s = np.zeros(cluster.ps_caps.shape[1])
    for jid, (y, z) in cur_alloc.items():
        used_w += float(y.sum()) * jmap[jid].worker_res
        if z is not None:
            used_s += float(z.sum()) * jmap[jid].ps_res
    return used_w, used_s


def _reactive_decision_point(rsched: ReactiveScheduler, cluster: ClusterSpec,
                             job: Job, t: int, scheduler: str,
                             cur_alloc: Dict[int, tuple],
                             usage: Tuple[np.ndarray, np.ndarray],
                             n_admitted: int,
                             n_rejected: int, n_live: int,
                             utility_so_far: float,
                             t_max: Optional[int] = ...,
                             live_frac: float = 1.0) -> DecisionPoint:
    fw, fs = _free_window(*usage, cluster, t, t_max=t_max)
    admit = rsched.would_admit(job, t)
    nw, nps = rsched._counts(job)
    return DecisionPoint(
        job=job, t=t, scheduler=scheduler,
        expert=(nw, nps) if admit else (0, 0), candidate=None,
        utility_so_far=utility_so_far,
        n_running=len(cur_alloc), n_waiting=n_live - len(cur_alloc),
        accepted=n_admitted, rejected=n_rejected,
        free_frac_workers=fw, free_frac_ps=fs, live_frac=live_frac)


def _drive_reactive(cluster: ClusterSpec, jobs: Sequence[Job], scheduler: str,
                    fixed_workers: int, check: bool, quantum: Optional[int],
                    cancellations: Optional[Dict[int, int]],
                    throughput: Optional[ThroughputFn], decide: bool,
                    fleet: Optional[FleetTrace] = None,
                    ckpt_interval: int = CKPT_INTERVAL
                    ) -> Generator[DecisionPoint, object, SimResult]:
    T = cluster.T
    src = {j.jid: _with_quantum(j, quantum) for j in jobs}
    jmap = dict(src)
    by_slot, cancel_slot = _group_events(src.values(), cancellations, T)
    rsched: ReactiveScheduler = BASELINES[scheduler](
        cluster, fixed_workers=fixed_workers)

    total_gpu = max(float(cluster.worker_caps[:, 0].sum()), 1e-9)
    admitted: List[int] = []
    remaining: Dict[int, float] = {}
    completion: Dict[int, int] = {}
    canceled: set = set()
    total_utility = 0.0
    util_sum = 0.0
    # fleet churn (all branches gated on a non-empty trace — the empty
    # trace is an exact no-op).  ``ckpt_rem`` is each admitted job's
    # remaining work at its last checkpoint: lossy failures roll
    # ``remaining`` back to it, graceful drains refresh it first.
    churn = fleet is not None and bool(fleet)
    fs = FleetState(cluster, fleet) if churn else None
    ckpt_rem: Dict[int, float] = {}
    ck = max(int(ckpt_interval), 1)
    n_preempted = 0
    # reactive per-event replan wall clocks (the repacks) — the
    # apples-to-apples counterpart of OASiS's decision_seconds.  In
    # stepwise (decide) mode the list stays empty so ``run`` can fill it
    # with the caller policy's inference latency instead.
    decision_seconds: List[float] = []

    # ``dirty`` gating: the scheduler tells us whether the last event can
    # change its next repack (arrivals and repack-relevant completions
    # set it; a completion with an empty wait queue under FIFO/RRH or a
    # rejected RRH arrival leaves it unset).  On clean events the engine
    # reuses the previous allocation — pruned of departed jobs — instead
    # of repacking: between events capacity and the job set are unchanged
    # so a fresh ``step`` provably returns the same placements.
    cur_alloc: Dict[int, tuple] = {}
    ids: List[int] = []
    counts = np.zeros(0)
    plan_gpu = 0.0
    stale = True            # derived arrays need a rebuild (alloc changed)
    # stateless throughput fns expose a vectorized per-slot factor matrix;
    # stateful ones (e.g. straggler detection) must be called slot by slot
    use_matrix = (throughput is not None
                  and getattr(throughput, "stateless", False)
                  and callable(getattr(throughput, "rate_matrix", None)))

    event_set = set(by_slot) | set(cancel_slot)
    if churn:
        event_set |= set(fs.event_slots)
    events = sorted(event_set)
    ei = 0
    n_rejected = 0
    t = events[0] if events else T
    while t < T:
        while ei < len(events) and events[ei] <= t:
            ei += 1
        if churn:
            trans = fs.step(t)
            if trans:
                _cs = _obslib.span("churn_step", t=t,
                                   transitions=len(trans))
                _cs.__enter__()
                for pool, srv, kind in trans:
                    if kind == UP:
                        continue
                    if pool == "worker":
                        vs = [jid for jid, (y, _) in cur_alloc.items()
                              if y[srv] > 0]
                    else:
                        vs = [jid for jid, (_, z) in cur_alloc.items()
                              if z is not None and z[srv] > 0]
                    for jid in vs:
                        if kind == DOWN_LOSSY:
                            # crash: work since the last checkpoint lost
                            remaining[jid] = ckpt_rem.get(
                                jid, jmap[jid].total_work_slots)
                        else:
                            # drain: checkpoint taken at drain start
                            ckpt_rem[jid] = remaining[jid]
                        rsched.preempt(jid, t)
                        cur_alloc.pop(jid, None)
                        n_preempted += 1
                        if _obslib.ENABLED:
                            _obslib.inc("engine.preemptions")
                # repack over the survivors: victims stay enrolled, so
                # the scheduler's own queue/resume order re-places them
                rsched.set_capacity(fs.worker_caps, fs.ps_caps)
                stale = True
                _cs.__exit__(None, None, None)
        arrivals_now = by_slot.pop(t, ())
        if _obslib.ENABLED and arrivals_now:
            _obslib.inc("engine.arrivals", len(arrivals_now))
        if decide and arrivals_now:
            # one usage snapshot for the whole arrival burst: admissions
            # do not change the previous allocation until the repack,
            # and cancellations at this slot are processed afterwards
            usage = _pool_usage(cur_alloc, jmap, cluster)
        for job in arrivals_now:
            if decide:
                action = yield _reactive_decision_point(
                    rsched, cluster, job, t, scheduler, cur_alloc, usage,
                    len(admitted), n_rejected, len(remaining), total_utility,
                    live_frac=fs.live_frac if churn else 1.0)
                nw, nps = _as_counts(action)
                if nw <= 0:
                    n_rejected += 1
                    continue
                if isinstance(rsched, Learned):
                    # clamp to the job's own feasibility envelope: at most
                    # N_i concurrent workers (constraint (3)), at least
                    # the bandwidth-matched PS count (constraints (6)(7))
                    nw = min(nw, job.num_chunks)
                    nps = max(nps, job.ps_for(nw))
                    rsched.set_counts(job.jid, nw, nps)
                rsched.enroll(job, t)
                admitted.append(job.jid)
                remaining[job.jid] = job.total_work_slots
            elif rsched.on_arrival(job, t):
                admitted.append(job.jid)
                remaining[job.jid] = job.total_work_slots
            else:
                n_rejected += 1
        cancels_now = cancel_slot.get(t, ())
        for jid in cancels_now:
            if jid in remaining:                # admitted, still running
                rsched.on_completion(jid, t)    # drop from pool, no utility
                del remaining[jid]
                canceled.add(jid)
                cur_alloc.pop(jid, None)
                if churn:
                    ckpt_rem.pop(jid, None)
                stale = True
        if rsched.dirty:
            t0_rp = time.perf_counter()
            with _obslib.span("repack", t=t, scheduler=scheduler,
                              n_live=len(remaining)):
                cur_alloc = dict(rsched.step(t))
            if not decide:
                decision_seconds.append(time.perf_counter() - t0_rp)
            rsched.dirty = False
            stale = True
            if check:       # a pruned reuse stays feasible by construction
                if churn:   # ...against the surviving fleet's capacity
                    _check_alloc(cluster, jmap, cur_alloc,
                                 fs.worker_caps, fs.ps_caps)
                else:
                    _check_alloc(cluster, jmap, cur_alloc)
        elif _obslib.ENABLED and (arrivals_now or cancels_now
                                  or (churn and trans)):
            # an event landed but the scheduler proved the last repack
            # still optimal — the engine skipped a full replan
            _obslib.inc("repack.dirty_skips")
        if stale:
            ids = list(cur_alloc)
            counts = np.array([float(cur_alloc[j][0].sum()) for j in ids])
            plan_gpu = float(counts @ np.array(
                [jmap[j].worker_res[0] for j in ids])) if ids else 0.0
            stale = False
        _ff = _obslib.span("ffwd", t=t, n_live=len(ids))
        _ff.__enter__()
        next_ev = events[ei] if ei < len(events) else T
        horizon = min(next_ev, T) - t

        if throughput is None:
            rem = np.array([remaining[j] for j in ids])
            active = counts > 0
            slots_left = np.full(len(ids), np.inf)
            if active.any():
                slots_left[active] = np.maximum(
                    np.ceil((rem[active] - 1e-9) / counts[active]), 1.0)
            span = int(min(float(slots_left.min()) if ids else np.inf,
                           float(horizon)))
            span = max(span, 1)
            consumed = counts * span
        elif use_matrix and ids:
            # whole-block rate matrix: factors for every (live job, slot)
            # in one pass, completion detection via row cumsums; only the
            # slots up to the earliest completion are consumed, the rest
            # are recomputed after the replan (the fn is stateless)
            h = min(horizon, _RATE_BLOCK)
            M = np.empty((len(ids), h))
            for i, jid_ in enumerate(ids):
                M[i] = throughput.rate_matrix(jmap[jid_], int(counts[i]), t, h)
            M *= counts[:, None]
            cum = np.cumsum(M, axis=1)
            rem = np.array([remaining[j] for j in ids])
            hits = cum >= rem[:, None] - 1e-9
            first = np.where(hits.any(axis=1), hits.argmax(axis=1), h)
            k = int(first.min())
            span = k + 1 if k < h else h
            consumed = cum[:, span - 1]
        elif use_matrix:
            span = min(horizon, _RATE_BLOCK)
            consumed = counts                   # no live jobs: empty array
        else:
            # stateful fn: advance one slot, still vectorized across jobs
            consumed = counts * np.array(
                [throughput(jmap[j], int(c), t) for j, c in zip(ids, counts)]) \
                if ids else counts
            span = 1

        util_sum += (plan_gpu / total_gpu) * span
        t_end = t + span - 1                    # last slot run with this plan
        if churn and ids:
            # record the checkpoint crossed inside this span (if any):
            # work is consumed uniformly over the span under the exact
            # rate model, so the boundary's share is (cb - t) / span
            cb = ((t + span) // ck) * ck
            if cb > t:
                frac = (cb - t) / span
                for j, used in zip(ids, consumed):
                    ckpt_rem[j] = max(remaining[j] - float(used) * frac,
                                      0.0)
        done_now = []
        for j, used in zip(ids, consumed):
            remaining[j] -= used
            if remaining[j] <= 1e-9:
                done_now.append(j)
        for jid in done_now:
            completion[jid] = t_end
            total_utility += jmap[jid].utility(t_end - jmap[jid].arrival)
            rsched.on_completion(jid, t_end)
            del remaining[jid]
            cur_alloc.pop(jid, None)
            if churn:
                ckpt_rem.pop(jid, None)
            stale = True
        t += span
        _ff.set(slots=span, completed=len(done_now))
        _ff.__exit__(None, None, None)
        if _obslib.ENABLED:
            _obslib.inc("engine.ffwd_slots", span)
            if done_now:
                _obslib.inc("engine.completions", len(done_now))
    return SimResult(name=scheduler, total_utility=total_utility,
                     accepted=len(admitted), completed=len(completion),
                     n_jobs=len(jobs), completion=completion,
                     target_gap=_target_gaps(jmap, completion),
                     decision_seconds=decision_seconds,
                     utilization=util_sum / T if T else 0.0,
                     canceled=len(canceled), preempted=n_preempted,
                     live_frac=fs.live_frac if churn else 1.0,
                     arrivals={j.jid: j.arrival for j in src.values()
                               if j.arrival < T})


# ---------------------------------------------------------------------------
# Continuous serving mode: open-ended arrival streams over a rolling
# price-state window.  Total simulated time is unbounded — all state is
# O(window) + O(live jobs) + O(decided jobs) dicts; nothing allocates a
# (total-time, ...) array.
# ---------------------------------------------------------------------------

def stream_price_params(sample: Sequence[Job], cluster: ClusterSpec,
                        window: int, floor_frac: float = 0.05) -> PriceParams:
    """U/L price-bound estimates for a streamed run, from a warmup sample.

    The paper's estimator is horizon-relative (worst-case utility at
    ``f_i(T - a_i)``); in serving mode the analogue of the horizon is the
    scheduling window, so the sample is evaluated arrival-free against a
    ``T=window`` view of the cluster — "estimated from past experience"
    (Sec. IV-B), exactly the operator knob Fig. 6 sweeps."""
    view = dataclasses.replace(cluster, T=int(window))
    sample0 = [dataclasses.replace(j, arrival=0) for j in sample]
    return price_params_from_jobs(sample0, view, floor_frac=floor_frac)


def stream_decisions(cluster: ClusterSpec, jobs: Iterable[Job],
                     scheduler: str = "oasis",
                     params: Optional[PriceParams] = None,
                     impl: str = "fast", window: int = 64,
                     fixed_workers: int = 8, check: bool = False,
                     quantum: Optional[int] = None,
                     warmup_sample: int = 256,
                     fleet: Optional[FleetTrace] = None,
                     ckpt_interval: int = CKPT_INTERVAL
                     ) -> Generator[DecisionPoint, object, SimResult]:
    """Streaming analogue of :func:`decisions`.

    ``jobs`` is any iterable (typically ``sim.workload.stream_jobs``)
    yielding jobs in nondecreasing arrival order; it is consumed lazily
    and never materialised.  ``cluster.T`` is ignored as a trace bound —
    the run ends when the iterable does and every admitted job has run to
    completion or provable starvation.  For OASiS the price state keeps a
    ``window``-slot rolling horizon (``PriceState.advance``); decisions
    are made in window-local coordinates (the arriving job is translated
    to arrival 0) and committed slots are translated back to the absolute
    clock, so per-decision cost is O(window), independent of trace
    length.  When ``params`` is omitted they are estimated from the first
    ``warmup_sample`` jobs via :func:`stream_price_params` (the sample is
    replayed, not dropped)."""
    if scheduler == "oasis":
        if params is None:
            it = iter(jobs)
            sample = list(itertools.islice(it, warmup_sample))
            params = stream_price_params(sample, cluster, window)
            jobs = itertools.chain(sample, it)
        return _drive_oasis_stream(cluster, jobs, params, impl, window,
                                   check, quantum, decide=True, fleet=fleet,
                                   ckpt_interval=ckpt_interval)
    return _drive_reactive_stream(cluster, jobs, scheduler, fixed_workers,
                                  check, quantum, decide=True, fleet=fleet,
                                  ckpt_interval=ckpt_interval)


def run_stream(cluster: ClusterSpec, jobs: Iterable[Job],
               scheduler: str = "oasis",
               params: Optional[PriceParams] = None, impl: str = "fast",
               window: int = 64, fixed_workers: int = 8, check: bool = False,
               quantum: Optional[int] = None, warmup_sample: int = 256,
               fleet: Optional[FleetTrace] = None,
               ckpt_interval: int = CKPT_INTERVAL,
               policy: Optional[Callable[[DecisionPoint], object]] = None,
               obs: Optional["_obslib.Obs"] = None
               ) -> SimResult:
    """Drive ``scheduler`` over an open-ended arrival stream.

    The streaming counterpart of :func:`run` — same scheduler kernels,
    same admission semantics, no horizon: completion slots are absolute,
    ``utilization`` is a running aggregate over the elapsed clock, and
    memory stays bounded by the window (``SimResult.window_bytes``).
    ``policy`` answers each decision point as in :func:`run` (required
    for ``scheduler="learned"``).

    Example — a short bounded slice of an open-ended stream through the
    rolling 16-slot price window::

        >>> import itertools
        >>> from repro.sim import engine
        >>> from repro.sim.workload import make_cluster, stream_jobs
        >>> cluster = make_cluster(T=20, H=3, K=3)
        >>> arrivals = itertools.islice(
        ...     stream_jobs(rate=0.5, seed=1, small=True), 12)
        >>> r = engine.run_stream(cluster, arrivals, scheduler="oasis",
        ...                       window=16)
        >>> (r.n_jobs, r.accepted, r.window_bytes is not None)
        (12, 12, True)
    """
    if scheduler == "learned" and policy is None:
        raise ValueError(
            "scheduler='learned' needs a policy — pass engine.run_stream("
            "..., policy=...) (see repro.rl.policy.LearnedDecider) or "
            "train one via repro.rl.train")
    with _obslib.activate(obs):
        if policy is None:
            if scheduler == "oasis":
                if params is None:
                    it = iter(jobs)
                    sample = list(itertools.islice(it, warmup_sample))
                    params = stream_price_params(sample, cluster, window)
                    jobs = itertools.chain(sample, it)
                return _exhaust(_drive_oasis_stream(
                    cluster, jobs, params, impl, window, check, quantum,
                    decide=False, fleet=fleet,
                    ckpt_interval=ckpt_interval))
            return _exhaust(_drive_reactive_stream(
                cluster, jobs, scheduler, fixed_workers, check, quantum,
                decide=False, fleet=fleet, ckpt_interval=ckpt_interval))
        gen = stream_decisions(cluster, jobs, scheduler=scheduler,
                               params=params, impl=impl, window=window,
                               fixed_workers=fixed_workers, check=check,
                               quantum=quantum, warmup_sample=warmup_sample,
                               fleet=fleet, ckpt_interval=ckpt_interval)
        policy_seconds: List[float] = []
        try:
            dp = next(gen)
            while True:
                t0 = time.perf_counter()
                action = policy(dp)
                policy_seconds.append(time.perf_counter() - t0)
                dp = gen.send(action)
        except StopIteration as e:
            result = e.value
            if not result.decision_seconds:
                result.decision_seconds = policy_seconds
            return result


def _drive_oasis_stream(cluster: ClusterSpec, jobs: Iterable[Job],
                        params: PriceParams, impl: str, window: int,
                        check: bool, quantum: Optional[int], decide: bool,
                        fleet: Optional[FleetTrace] = None,
                        ckpt_interval: int = CKPT_INTERVAL
                        ) -> Generator[DecisionPoint, object, SimResult]:
    with _x64_run(impl, decide):
        result = yield from _drive_oasis_stream_gen(
            cluster, jobs, params, impl, window, check, quantum, decide,
            fleet=fleet, ckpt_interval=ckpt_interval)
    return result


def _drive_oasis_stream_gen(cluster: ClusterSpec, jobs: Iterable[Job],
                            params: PriceParams, impl: str, window: int,
                            check: bool, quantum: Optional[int],
                            decide: bool,
                            fleet: Optional[FleetTrace] = None,
                            ckpt_interval: int = CKPT_INTERVAL
                            ) -> Generator[DecisionPoint, object, SimResult]:
    osched = OASiS(cluster, params, impl=impl, window=window)
    state = osched.state
    jmap: Dict[int, Job] = {}
    arrivals: Dict[int, int] = {}
    completion: Dict[int, int] = {}
    # absolute finish of still-running accepted jobs; entries (and their
    # committed Schedule in osched.accepted, which holds local slots that
    # go stale as the window slides) are pruned once the clock passes
    # them, keeping live state O(window-worth of jobs)
    active: Dict[int, int] = {}
    n_accepted = 0
    n_rejected = 0
    n_jobs = 0
    t = 0
    # fleet churn: trace slots are absolute; re-blocks after every
    # advance keep down servers at zero headroom across window slides
    churn = fleet is not None and bool(fleet)
    fs = FleetState(cluster, fleet) if churn else None
    fe: List[int] = fs.event_slots if churn else []
    fi = 0
    ljobs: Dict[int, Job] = {}          # live (quantized/rescaled) copies
    admit_origin: Dict[int, int] = {}   # absolute slot of live commitment
    ck = max(int(ckpt_interval), 1)
    blocked_gpu = 0.0
    n_preempted = 0
    n_dropped = 0
    it = iter(jobs)
    nxt = next(it, None)
    while True:
        ta = int(nxt.arrival) if nxt is not None else None
        tf = fe[fi] if fi < len(fe) else None
        if ta is None and (tf is None or not active):
            break                       # fleet events can't touch anything
        t = ta if (tf is None or (ta is not None and ta <= tf)) else tf
        batch: List[Job] = []
        if ta is not None and ta == t:
            while nxt is not None and int(nxt.arrival) == t:
                batch.append(nxt)
                nxt = next(it, None)
        with _obslib.span("stream_advance", t=t):
            state.advance(t)
        for jid in [j for j, fin in active.items() if fin < t]:
            del active[jid]
            osched.accepted.pop(jid, None)
            admit_origin.pop(jid, None)
            ljobs.pop(jid, None)
        if churn:
            # slots freshly opened by the slide start at zero — refill
            # every currently-down server to caps (idempotent elsewhere)
            for pool, srv in fs.down_servers():
                blocked_gpu += state.block_server(pool, srv, 0)
        if churn and tf is not None and tf == t:
            fi += 1
            trans = fs.step(t)
            _cs = _obslib.span("churn_step", t=t, transitions=len(trans))
            _cs.__enter__()
            for pool, srv, kind in trans:
                if kind == UP:
                    blocked_gpu -= state.unblock_server(pool, srv, 0)
            victims: Dict[int, str] = {}
            for pool, srv, kind in trans:
                if kind == UP:
                    continue
                for jid in active:
                    if jid in victims:
                        continue
                    sched = osched.accepted.get(jid)
                    if sched is None:
                        continue
                    shift = t - admit_origin[jid]
                    alloc = sched.workers if pool == "worker" else sched.ps
                    if any(s >= shift and a[srv] > 0
                           for s, a in alloc.items()):
                        victims[jid] = kind
            readmit: List[Tuple[int, Job]] = []
            for jid, kind in victims.items():
                sched = osched.accepted.pop(jid)
                ao = admit_origin[jid]
                shift = t - ao
                jcur = ljobs[jid]
                # the commitment's slots are local to its admission; the
                # window has since slid by ``shift``
                tail_w = {s - shift: y for s, y in sched.workers.items()
                          if s >= shift}
                tail_z = {s - shift: z for s, z in sched.ps.items()
                          if s >= shift}
                state.release(jcur, tail_w, tail_z)
                osched.total_utility -= sched.utility
                n_preempted += 1
                if _obslib.ENABLED:
                    _obslib.inc("engine.preemptions")
                del active[jid]
                cb = (t // ck) * ck if kind == DOWN_LOSSY else t
                delivered = sum(float(y.sum())
                                for s, y in sched.workers.items()
                                if s + ao < cb)
                rem = jcur.total_work_slots - delivered
                if rem <= 1e-9:
                    done = [s + ao for s, y in sched.workers.items()
                            if s + ao < cb and y.sum() > 0]
                    completion[jid] = max(done) if done else max(cb - 1, 0)
                    admit_origin.pop(jid, None)
                    ljobs.pop(jid, None)
                    continue
                scale = jcur.work_scale * rem / jcur.total_work_slots
                orig = jmap[jid]
                readmit.append((jid, dataclasses.replace(
                    jcur, arrival=0, work_scale=scale,
                    utility=_shift_utility(orig.utility,
                                           t - int(orig.arrival)))))
            for pool, srv, kind in trans:
                if kind != UP:
                    blocked_gpu += state.block_server(pool, srv, 0)
            _cs.set(victims=len(victims), readmits=len(readmit))
            _cs.__exit__(None, None, None)
            for jid, loc in readmit:
                ljobs[jid] = loc
                if decide:
                    cand = osched.propose(loc)
                    g_win, v_win = state.alloc_window(0, DECISION_WINDOW)
                    fw, fsw = _free_window(g_win, v_win, cluster, t,
                                           t_max=None)
                    action = yield DecisionPoint(
                        job=jmap[jid], t=t, scheduler="oasis",
                        expert=(1, 0) if cand is not None else (0, 0),
                        candidate=cand,
                        utility_so_far=osched.total_utility,
                        n_running=len(active), n_waiting=0,
                        accepted=n_accepted, rejected=n_rejected,
                        free_frac_workers=fw, free_frac_ps=fsw,
                        live_frac=fs.live_frac, preempted=True)
                    nw, _ = _as_counts(action)
                    sched = osched._resolve(loc, cand if nw > 0 else None)
                else:
                    sched = osched.on_arrival(loc)
                if sched is not None:
                    active[jid] = t + sched.finish
                    completion[jid] = t + sched.finish
                    admit_origin[jid] = t
                else:
                    # the shrunken fleet can't fit it: the job departs
                    # with no utility (subtracted above)
                    n_dropped += 1
                    if _obslib.ENABLED:
                        _obslib.inc("engine.preempt_dropped")
                    n_accepted -= 1
                    n_rejected += 1
                    completion.pop(jid, None)
                    admit_origin.pop(jid, None)
                    ljobs.pop(jid, None)
        # window-local coordinates: the job arrives at local slot 0 (its
        # durations — hence utility — are translation-invariant)
        local = [dataclasses.replace(_with_quantum(j, quantum), arrival=0)
                 for j in batch]
        for j in batch:
            jmap[j.jid] = j
            arrivals[j.jid] = int(j.arrival)
        n_jobs += len(batch)
        if _obslib.ENABLED and batch:
            _obslib.inc("engine.arrivals", len(batch))
        if decide:
            for job, loc in zip(batch, local):
                cand = osched.propose(loc)
                g_win, v_win = state.alloc_window(0, DECISION_WINDOW)
                fw, fsw = _free_window(g_win, v_win, cluster, t, t_max=None)
                action = yield DecisionPoint(
                    job=job, t=t, scheduler="oasis",
                    expert=(1, 0) if cand is not None else (0, 0),
                    candidate=cand, utility_so_far=osched.total_utility,
                    n_running=len(active), n_waiting=0,
                    accepted=n_accepted, rejected=n_rejected,
                    free_frac_workers=fw, free_frac_ps=fsw,
                    live_frac=fs.live_frac if churn else 1.0)
                nw, _ = _as_counts(action)
                sched = osched._resolve(loc, cand if nw > 0 else None)
                if sched is not None:
                    n_accepted += 1
                    active[job.jid] = t + sched.finish
                    completion[job.jid] = t + sched.finish
                    if churn:
                        ljobs[job.jid] = loc
                        admit_origin[job.jid] = t
                else:
                    n_rejected += 1
        elif batch:
            with _obslib.span("arrival_burst", t=t, n=len(batch)):
                scheds = osched.on_arrivals(local)
            for job, loc, sched in zip(batch, local, scheds):
                if sched is not None:
                    n_accepted += 1
                    active[job.jid] = t + sched.finish
                    completion[job.jid] = t + sched.finish
                    if churn:
                        ljobs[job.jid] = loc
                        admit_origin[job.jid] = t
                else:
                    n_rejected += 1
        if check:
            ok_w, ok_ps = state.capacity_ok()
            assert ok_w, "worker capacity violated"
            assert ok_ps, "PS capacity violated"
    # elapsed clock: through the last committed completion (tail work
    # beyond the final arrival still occupies the cluster)
    t_end = max(max(completion.values(), default=0) + 1, t + 1, 1)
    total_gpu = max(float(cluster.worker_caps[:, 0].sum()), 1e-9)
    gpu_slots = state.retired_gpu_slots + float(state.gpu_slot_usage().sum())
    if churn:
        gpu_slots -= blocked_gpu        # capacity-block filler, not usage
    return SimResult(name="oasis", total_utility=osched.total_utility,
                     accepted=n_accepted, completed=len(completion),
                     n_jobs=n_jobs, completion=completion,
                     target_gap=_target_gaps(jmap, completion),
                     decision_seconds=osched.decision_seconds,
                     utilization=gpu_slots / (total_gpu * t_end),
                     preempted=n_preempted, preempt_dropped=n_dropped,
                     live_frac=fs.live_frac if churn else 1.0,
                     arrivals=arrivals, window_bytes=state.window_bytes)


def _drive_reactive_stream(cluster: ClusterSpec, jobs: Iterable[Job],
                           scheduler: str, fixed_workers: int, check: bool,
                           quantum: Optional[int], decide: bool,
                           fleet: Optional[FleetTrace] = None,
                           ckpt_interval: int = CKPT_INTERVAL
                           ) -> Generator[DecisionPoint, object, SimResult]:
    rsched: ReactiveScheduler = BASELINES[scheduler](
        cluster, fixed_workers=fixed_workers)
    total_gpu = max(float(cluster.worker_caps[:, 0].sum()), 1e-9)
    jmap: Dict[int, Job] = {}
    arrivals: Dict[int, int] = {}
    admitted: List[int] = []
    remaining: Dict[int, float] = {}
    completion: Dict[int, int] = {}
    total_utility = 0.0
    util_sum = 0.0
    cur_alloc: Dict[int, tuple] = {}
    ids: List[int] = []
    counts = np.zeros(0)
    plan_gpu = 0.0
    stale = True
    n_rejected = 0
    n_jobs = 0
    # fleet churn (same machinery as the episodic reactive driver)
    churn = fleet is not None and bool(fleet)
    fs = FleetState(cluster, fleet) if churn else None
    fe: List[int] = fs.event_slots if churn else []
    fi = 0
    ckpt_rem: Dict[int, float] = {}
    ck = max(int(ckpt_interval), 1)
    n_preempted = 0
    # per-event repack wall clocks (see _drive_reactive); empty in
    # stepwise mode so the policy's latency takes the slot instead
    decision_seconds: List[float] = []

    it = iter(jobs)
    nxt = next(it, None)
    t = int(nxt.arrival) if nxt is not None else 0
    while nxt is not None or remaining:
        if churn:
            changed = False
            while fi < len(fe) and fe[fi] <= t:
                with _obslib.span("churn_step", t=fe[fi]):
                    for pool, srv, kind in fs.step(fe[fi]):
                        if kind == UP:
                            continue
                        if pool == "worker":
                            vs = [jid for jid, (y, _) in cur_alloc.items()
                                  if y[srv] > 0]
                        else:
                            vs = [jid for jid, (_, z) in cur_alloc.items()
                                  if z is not None and z[srv] > 0]
                        for jid in vs:
                            if kind == DOWN_LOSSY:
                                remaining[jid] = ckpt_rem.get(
                                    jid, jmap[jid].total_work_slots)
                            else:
                                ckpt_rem[jid] = remaining[jid]
                            rsched.preempt(jid, t)
                            cur_alloc.pop(jid, None)
                            n_preempted += 1
                            if _obslib.ENABLED:
                                _obslib.inc("engine.preemptions")
                changed = True
                fi += 1
            if changed:
                rsched.set_capacity(fs.worker_caps, fs.ps_caps)
                stale = True
        burst: List[Job] = []
        while nxt is not None and int(nxt.arrival) <= t:
            burst.append(_with_quantum(nxt, quantum))
            nxt = next(it, None)
        if _obslib.ENABLED and burst:
            _obslib.inc("engine.arrivals", len(burst))
        if decide and burst:
            usage = _pool_usage(cur_alloc, jmap, cluster)
        for job in burst:
            n_jobs += 1
            jmap[job.jid] = job
            arrivals[job.jid] = int(job.arrival)
            if decide:
                action = yield _reactive_decision_point(
                    rsched, cluster, job, t, scheduler, cur_alloc, usage,
                    len(admitted), n_rejected, len(remaining), total_utility,
                    t_max=None,
                    live_frac=fs.live_frac if churn else 1.0)
                nw, nps = _as_counts(action)
                if nw <= 0:
                    n_rejected += 1
                    continue
                if isinstance(rsched, Learned):
                    nw = min(nw, job.num_chunks)
                    nps = max(nps, job.ps_for(nw))
                    rsched.set_counts(job.jid, nw, nps)
                rsched.enroll(job, t)
                admitted.append(job.jid)
                remaining[job.jid] = job.total_work_slots
            elif rsched.on_arrival(job, t):
                admitted.append(job.jid)
                remaining[job.jid] = job.total_work_slots
            else:
                n_rejected += 1
        if rsched.dirty:
            t0_rp = time.perf_counter()
            with _obslib.span("repack", t=t, scheduler=scheduler,
                              n_live=len(remaining)):
                cur_alloc = dict(rsched.step(t))
            if not decide:
                decision_seconds.append(time.perf_counter() - t0_rp)
            rsched.dirty = False
            stale = True
            if check:
                if churn:
                    _check_alloc(cluster, jmap, cur_alloc,
                                 fs.worker_caps, fs.ps_caps)
                else:
                    _check_alloc(cluster, jmap, cur_alloc)
        elif _obslib.ENABLED and (burst or (churn and changed)):
            _obslib.inc("repack.dirty_skips")
        if stale:
            ids = list(cur_alloc)
            counts = np.array([float(cur_alloc[j][0].sum()) for j in ids])
            plan_gpu = float(counts @ np.array(
                [jmap[j].worker_res[0] for j in ids])) if ids else 0.0
            stale = False

        rem = np.array([remaining[j] for j in ids])
        active = counts > 0
        slots_left = np.full(len(ids), np.inf)
        if active.any():
            slots_left[active] = np.maximum(
                np.ceil((rem[active] - 1e-9) / counts[active]), 1.0)
        earliest = float(slots_left.min()) if ids else math.inf
        horizon = (int(nxt.arrival) - t) if nxt is not None else math.inf
        if churn and fi < len(fe):
            # the next fleet event bounds the plan's validity (and can
            # un-starve a waiting queue by restoring capacity)
            horizon = min(horizon, fe[fi] - t)
        if not math.isfinite(earliest) and not math.isfinite(horizon):
            # no future arrivals and no live job is progressing: the plan
            # can never change again — the waiting jobs are starved for
            # good, so the stream is done (they simply never complete)
            break
        span = max(int(min(earliest, horizon)), 1)
        consumed = counts * span
        util_sum += (plan_gpu / total_gpu) * span
        t_end = t + span - 1
        if churn and ids:
            cb = ((t + span) // ck) * ck
            if cb > t:
                frac = (cb - t) / span
                for j, used in zip(ids, consumed):
                    ckpt_rem[j] = max(remaining[j] - float(used) * frac,
                                      0.0)
        done_now = []
        for j, used in zip(ids, consumed):
            remaining[j] -= used
            if remaining[j] <= 1e-9:
                done_now.append(j)
        for jid in done_now:
            completion[jid] = t_end
            total_utility += jmap[jid].utility(t_end - jmap[jid].arrival)
            rsched.on_completion(jid, t_end)
            del remaining[jid]
            cur_alloc.pop(jid, None)
            if churn:
                ckpt_rem.pop(jid, None)
            stale = True
        t += span
        if _obslib.ENABLED:
            _obslib.inc("engine.ffwd_slots", span)
            if done_now:
                _obslib.inc("engine.completions", len(done_now))
    return SimResult(name=scheduler, total_utility=total_utility,
                     accepted=len(admitted), completed=len(completion),
                     n_jobs=n_jobs, completion=completion,
                     target_gap=_target_gaps(jmap, completion),
                     decision_seconds=decision_seconds,
                     utilization=util_sum / max(t, 1),
                     preempted=n_preempted,
                     live_frac=fs.live_frac if churn else 1.0,
                     arrivals=arrivals, window_bytes=0)
