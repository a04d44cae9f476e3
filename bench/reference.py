"""Plain float64 reference of OASiS's admission step (Alg. 1 and 2).

Written for the benchmark's judge and independent of the program: it
imports nothing from it and takes nothing the program computed.  It holds
the dual price state of a rolling ``window``-slot horizon, prices it by
eq. (22)/(25), and finds a job's best schedule by Alg. 2: per-slot
COST_t rows from the greedy cheapest-server fill, then the DP over
workload splits, then the backtrack.  ``precision="bfloat16"`` rounds the
prices, the COST rows and every DP sum to bfloat16: that is the control,
the step below the program's float32 decisions, which the judge must
reject.

Job semantics follow the paper (arXiv:1801.00936 Sec. III): a job needs
``E N`` chunk-passes, grouped ``q`` at a time into DP units (``q`` is the
auto workload quantum, ``ceil(E N / 1200)``); ``d`` units in one slot need
``ceil(d q chunk_time)`` workers and ``ceil(W b / B)`` parameter servers.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Dict, Optional, Sequence, Tuple

import numpy as np
from numpy.lib.stride_tricks import sliding_window_view

R = 5
INF = float("inf")
PAY_EPS = 1e-12
QUANTUM_UNITS = 1200          # auto quantum: ceil(E N / 1200) passes a unit


def bf16(x: np.ndarray) -> np.ndarray:
    """Round to the nearest bfloat16 (ties to even), returned as float64;
    infinities stay infinite."""
    a = np.asarray(x, np.float64).astype(np.float32)
    bits = a.view(np.uint32).astype(np.uint64)
    rounded = (bits + 0x7FFF + ((bits >> 16) & 1)) & 0xFFFF0000
    out = rounded.astype(np.uint32).view(np.float32).astype(np.float64)
    return np.where(np.isfinite(a), out, a.astype(np.float64))


@dataclasses.dataclass(frozen=True)
class RefJob:
    """A job in window-local time (arrival 0) with its workload quantum."""

    jid: int
    epochs: int
    num_chunks: int
    minibatches_per_chunk: int
    tau: float
    grad_size: float
    worker_bw: float
    ps_bw: float
    worker_res: np.ndarray
    ps_res: np.ndarray
    gamma1: float
    gamma2: float
    gamma3: float
    quantum: int

    @classmethod
    def from_spec(cls, spec, quantum: int = 0) -> "RefJob":
        q = quantum if quantum > 0 else max(
            1, math.ceil(spec.epochs * spec.num_chunks / QUANTUM_UNITS))
        return cls(jid=spec.jid, epochs=spec.epochs,
                   num_chunks=spec.num_chunks,
                   minibatches_per_chunk=spec.minibatches_per_chunk,
                   tau=spec.tau, grad_size=spec.grad_size,
                   worker_bw=spec.worker_bw, ps_bw=spec.ps_bw,
                   worker_res=np.asarray(spec.worker_res, np.float64),
                   ps_res=np.asarray(spec.ps_res, np.float64),
                   gamma1=spec.gamma1, gamma2=spec.gamma2,
                   gamma3=spec.gamma3, quantum=q)

    def utility(self, duration: float) -> float:
        """gamma1 / (1 + exp(gamma2 (duration - gamma3)))."""
        z = self.gamma2 * (duration - self.gamma3)
        if z >= 0:
            ez = math.exp(-min(z, 50.0))
            return self.gamma1 * ez / (1.0 + ez)
        return self.gamma1 / (1.0 + math.exp(max(z, -50.0)))

    @property
    def chunk_time(self) -> float:
        return self.minibatches_per_chunk * (
            self.tau + 2.0 * self.grad_size / self.worker_bw)

    @property
    def total_work_slots(self) -> float:
        return 1.0 * self.epochs * self.num_chunks * self.chunk_time

    @property
    def min_duration(self) -> int:
        return max(1, math.ceil(1.0 * self.epochs * self.minibatches_per_chunk
                                * (self.tau + 2.0 * self.grad_size
                                   / self.worker_bw)))

    @property
    def workload(self) -> int:
        return math.ceil(1.0 * self.epochs * self.num_chunks / self.quantum)

    def workers_for(self, d: int) -> int:
        if d == 0:
            return 0
        return math.ceil(d * self.quantum * self.chunk_time - 1e-9)

    def ps_for(self, w: int) -> int:
        if w == 0:
            return 0
        return math.ceil(w * self.worker_bw / self.ps_bw - 1e-9)

    @property
    def max_units_per_slot(self) -> int:
        """Largest d with workers_for(d) <= num_chunks (constraint (3))."""
        hi = int(self.num_chunks / (self.quantum * self.chunk_time)) + 2
        for d in range(hi, -1, -1):
            if self.workers_for(d) <= self.num_chunks:
                return d
        return 0

    def units_for_workers(self, w: int) -> int:
        """Most DP units ``w`` workers fulfil in one slot."""
        if w <= 0:
            return 0
        d = int((w + 1e-9) / (self.quantum * self.chunk_time))
        while self.workers_for(d + 1) <= w:
            d += 1
        while d > 0 and self.workers_for(d) > w:
            d -= 1
        return d


@dataclasses.dataclass(frozen=True)
class Params:
    U1: np.ndarray
    U2: np.ndarray
    L1: float
    L2: float


def price_params(sample: Sequence[RefJob], worker_caps: np.ndarray,
                 ps_caps: np.ndarray, window: int,
                 floor_frac: float = 0.05) -> Params:
    """U/L bounds (eq. 23-26) estimated from a sample of jobs taken to
    arrive at 0 against a ``window``-slot horizon, with each job's worst
    utility floored at ``floor_frac`` of its best."""
    T = window
    U1 = np.zeros(R)
    U2 = np.zeros(R)
    L1n = L2n = INF
    e1 = e2 = INF
    cap_w = float(worker_caps.sum())
    cap_s = float(ps_caps.sum())
    for job in sample:
        f_max = job.utility(job.min_duration)
        f_min = max(job.utility(T), floor_frac * f_max)
        work = math.ceil(job.total_work_slots)
        for r in range(R):
            if job.worker_res[r] > 0:
                U1[r] = max(U1[r], f_max / job.worker_res[r])
            if job.ps_res[r] > 0:
                U2[r] = max(U2[r], f_max / job.ps_res[r])
        wsum = float(job.worker_res.sum())
        ssum = float(job.ps_res.sum())
        if wsum > 0:
            L1n = min(L1n, f_min / (work * wsum))
            if cap_w > 0:
                e1 = min(e1, work * wsum / (T * cap_w))
        if ssum > 0:
            L2n = min(L2n, f_min / (work * ssum))
            if cap_s > 0:
                e2 = min(e2, work * ssum / (T * cap_s))
    eta1 = max(1.0 / max(e1, 1e-12) if math.isfinite(e1) else 1.0, 1.0)
    eta2 = max(1.0 / max(e2, 1e-12) if math.isfinite(e2) else 1.0, 1.0)
    if not math.isfinite(L1n):
        L1n = L2n if math.isfinite(L2n) else 4.0
    if not math.isfinite(L2n):
        L2n = L1n
    L1 = L1n / (4.0 * eta1)
    L2 = L2n / (4.0 * eta2)
    return Params(U1=np.maximum(U1, L1 * (1.0 + 1e-9)),
                  U2=np.maximum(U2, L2 * (1.0 + 1e-9)), L1=L1, L2=L2)


def _prices(alloc, caps, U, L):
    ratio = np.maximum(U / L, 1.0 + 1e-9)
    return L * ratio ** (alloc / np.maximum(caps, 1e-12))


class RefState:
    """Allocations per (local slot, server, resource) over a rolling window
    whose local slot 0 is absolute slot ``origin``."""

    def __init__(self, worker_caps, ps_caps, params: Params, window: int):
        self.wcaps = np.asarray(worker_caps, np.float64)
        self.scaps = np.asarray(ps_caps, np.float64)
        self.params = params
        self.g = np.zeros((window, self.wcaps.shape[0], R))
        self.v = np.zeros((window, self.scaps.shape[0], R))
        self.origin = 0

    @property
    def horizon(self) -> int:
        return self.g.shape[0]

    def advance(self, now: int) -> None:
        k = int(now) - self.origin
        if k < 0:
            raise ValueError(f"advance({now}) before origin {self.origin}")
        W = self.horizon
        k = min(k, W)
        self.origin = int(now)
        for a in (self.g, self.v):
            a[:W - k] = a[k:].copy()
            a[W - k:] = 0.0

    def prices(self) -> Tuple[np.ndarray, np.ndarray]:
        p = self.params
        return (_prices(self.g, self.wcaps[None], p.U1[None, None], p.L1),
                _prices(self.v, self.scaps[None], p.U2[None, None], p.L2))

    def prices_at(self, slots) -> Tuple[Dict[int, np.ndarray],
                                        Dict[int, np.ndarray]]:
        """Price entries of the given local slots only, by slot."""
        ts = np.asarray(list(slots), np.int64)
        pp = self.params
        p = _prices(self.g[ts], self.wcaps[None], pp.U1[None, None], pp.L1)
        q = _prices(self.v[ts], self.scaps[None], pp.U2[None, None], pp.L2)
        return ({int(t): p[i] for i, t in enumerate(ts)},
                {int(t): q[i] for i, t in enumerate(ts)})

    def commit(self, job: RefJob, workers: Dict[int, np.ndarray],
               ps: Dict[int, np.ndarray]) -> float:
        """Add the schedule's demand; returns the largest excess of any
        touched entry over its capacity (<= 0 when it fits)."""
        excess = -INF
        for host, caps, alloc, res in ((self.g, self.wcaps, workers,
                                        job.worker_res),
                                       (self.v, self.scaps, ps, job.ps_res)):
            for t, cnt in alloc.items():
                host[t] += np.asarray(cnt, np.float64)[:, None] * res[None, :]
                excess = max(excess, float(np.max(host[t] - caps)))
        return excess


@dataclasses.dataclass
class RefSchedule:
    workers: Dict[int, np.ndarray]
    ps: Dict[int, np.ndarray]
    finish: int
    payoff: float


def _server_caps(headroom: np.ndarray, demand: np.ndarray) -> np.ndarray:
    """(T, S) whole instances of ``demand`` each server still holds."""
    pos = demand > 0
    if not pos.any():
        return np.full(headroom.shape[:2], 1 << 40, np.int64)
    per = np.floor(headroom[:, :, pos] / demand[pos] + 1e-9).min(axis=2)
    return np.maximum(np.minimum(per, float(1 << 40)), 0).astype(np.int64)


def _greedy_costs(unit: np.ndarray, cap: np.ndarray,
                  counts: np.ndarray) -> np.ndarray:
    """(T, M) cost of placing ``counts`` (T, M) instances on the cheapest
    servers of each slot; inf where the pool cannot hold them."""
    order = np.argsort(unit, axis=1, kind="stable")
    su = np.take_along_axis(unit, order, axis=1)
    sc = np.take_along_axis(cap, order, axis=1)
    ccap = np.cumsum(sc, axis=1)
    ccost = np.cumsum(sc * su, axis=1)
    T, S = unit.shape
    out = np.full(counts.shape, INF)
    out[counts == 0] = 0.0
    if S == 0:
        return out
    for t in range(T):
        c = counts[t]
        idx = np.minimum(np.searchsorted(ccap[t], c, side="left"), S - 1)
        prev_cap = np.where(idx > 0, ccap[t][np.maximum(idx - 1, 0)], 0)
        prev_cost = np.where(idx > 0, ccost[t][np.maximum(idx - 1, 0)], 0.0)
        vals = prev_cost + (c - prev_cap) * su[t][idx]
        ok = (c <= ccap[t, -1]) & (c > 0)
        out[t, ok] = vals[ok]
    return out


def _place(unit: np.ndarray, cap: np.ndarray, want: int,
           limit: Optional[int] = None) -> np.ndarray:
    """Greedy fill of one slot: cheapest server first (stable order)."""
    out = np.zeros(unit.shape[0], np.int64)
    left = want
    for s in np.argsort(unit, kind="stable"):
        if left <= 0:
            break
        take = min(int(cap[s]), left)
        out[s] = take
        left -= take
    return out


def _minplus(prev: np.ndarray, row: np.ndarray, rnd) -> Tuple[np.ndarray,
                                                             np.ndarray]:
    """new[d] = min_j row[j] + prev[d - j] and the first minimising j."""
    m = row.shape[0]
    pad = np.concatenate([np.full(m - 1, INF), prev])
    cand = rnd(sliding_window_view(pad, m)[:, ::-1] + row[None, :])
    arg = np.argmin(cand, axis=1)
    return cand[np.arange(cand.shape[0]), arg], arg


def alg2(job: RefJob, state: RefState, precision: str = "float64"
         ) -> Optional[RefSchedule]:
    """Best schedule of ``job`` (arriving at local slot 0) at the state's
    prices, or None when no schedule has positive payoff."""
    rnd = bf16 if precision == "bfloat16" else (lambda x: x)
    T = state.horizon
    D = job.workload
    dcap = min(job.max_units_per_slot, D)
    if dcap == 0:
        return None
    p, q = state.prices()
    wunit = rnd((rnd(p) * job.worker_res[None, None]).sum(axis=2))  # (T, H)
    sunit = rnd((rnd(q) * job.ps_res[None, None]).sum(axis=2))      # (T, K)
    wcap = _server_caps(state.wcaps[None] - state.g, job.worker_res)
    scap = _server_caps(state.scaps[None] - state.v, job.ps_res)
    ds = np.arange(dcap + 1)
    W = np.array([job.workers_for(int(d)) for d in ds], np.int64)
    Z = np.array([job.ps_for(int(w)) for w in W], np.int64)
    wcost = _greedy_costs(wunit, wcap, np.broadcast_to(W, (T, dcap + 1)))
    pool = scap.sum(axis=1)[:, None] if scap.shape[1] else np.zeros((T, 1))
    deploy = np.minimum(np.minimum(Z, W)[None, :], pool).astype(np.int64)
    feas_ps = deploy * job.ps_bw >= W[None, :] * job.worker_bw - 1e-9
    zcost = _greedy_costs(sunit, scap, deploy)
    rows = np.where((W <= job.num_chunks)[None, :] & feas_ps,
                    rnd(wcost + zcost), INF)
    rows[:, 0] = 0.0
    prev = np.full(D + 1, INF)
    prev[0] = 0.0
    splits = []
    best_pay, best_t = 0.0, -1
    for t in range(T):
        u = job.utility(t)
        if u <= best_pay + PAY_EPS:
            break               # utility never rises and costs are >= 0
        prev, arg = _minplus(prev, rows[t], rnd)
        splits.append(arg)
        if prev[D] < INF:
            pay = float(rnd(np.float64(u) - prev[D]))
            if pay > best_pay + PAY_EPS:
                best_pay, best_t = pay, t
    if best_t < 0:
        return None
    workers, ps = {}, {}
    d_rem = D
    for t in range(best_t, -1, -1):
        d = int(splits[t][d_rem])
        if d > 0:
            y = _place(wunit[t], wcap[t], int(W[d]))
            z = _place(sunit[t], scap[t], int(deploy[t, d]))
            workers[t], ps[t] = y, z
        d_rem -= d
    if d_rem != 0:
        raise AssertionError(f"backtrack left {d_rem} units of job {job.jid}")
    return RefSchedule(workers=workers, ps=ps, finish=best_t,
                       payoff=best_pay)


@dataclasses.dataclass
class Verdict:
    """A schedule judged at float64 prices."""

    payoff: float           # utility at its last active slot - f64 cost
    utility: float          # utility at its last active slot
    unit_shortfall: int     # DP units short of the job's workload (>= 0)
    breaches: int           # slots over N workers or short of PS bandwidth


def evaluate(job: RefJob, state: RefState, workers: Dict[int, np.ndarray],
             ps: Dict[int, np.ndarray]) -> Verdict:
    """Judge a schedule (local slots) against the current float64 state."""
    ts = sorted(set(workers) | set(ps))
    p, q = state.prices_at(ts)
    cost = 0.0
    units = 0
    breaches = 0
    last = -1
    for t, y in workers.items():
        y = np.asarray(y, np.float64)
        z = np.asarray(ps.get(t, np.zeros(state.scaps.shape[0])), np.float64)
        nw = int(round(float(y.sum())))
        if nw <= 0:
            continue
        last = max(last, int(t))
        units += job.units_for_workers(nw)
        cost += float(y @ (p[t] @ job.worker_res))
        cost += float(z @ (q[t] @ job.ps_res))
        if nw > job.num_chunks or (float(z.sum()) * job.ps_bw
                                   < nw * job.worker_bw - 1e-9):
            breaches += 1
    for t, z in ps.items():
        if t not in workers:
            cost += float(np.asarray(z, np.float64) @ (q[t] @ job.ps_res))
    util = job.utility(last) if last >= 0 else 0.0
    return Verdict(payoff=util - cost, utility=util,
                   unit_shortfall=max(job.workload - units, 0),
                   breaches=breaches)
