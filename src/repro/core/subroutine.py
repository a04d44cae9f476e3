"""Alg. 2 — dual subroutine deriving the best schedule for one job.

Implementations with identical outputs (tests assert so):

* ``best_schedule_ref``  — loop-faithful transcription of the paper's
  pseudocode (COST_t greedy, DP_COST recursion).  The test oracle.
* ``best_schedule``      — vectorized: COST_t rows for all (t, d) via
  sort + prefix sums (the greedy fills cheapest servers first, so its
  cost is a prefix sum), DP via banded min-plus convolution.  With
  ``use_jax=True`` the whole pipeline runs as one jit-compiled XLA
  computation (``schedule_jax.best_schedule_fused``); with
  ``rows_impl="loop"`` the seed's per-slot-loop COST-row builder is used
  (kept only as the decision-latency benchmark baseline).

All return ``None`` when no schedule has positive payoff (job rejected).
"""
from __future__ import annotations

from typing import Dict, Optional, Tuple

import numpy as np

from .pricing import PriceState
from .types import Job, R, Schedule

INF = float("inf")


# ---------------------------------------------------------------------------
# Reference (paper-faithful) implementation
# ---------------------------------------------------------------------------

def _server_capacity(headroom: np.ndarray, demand: np.ndarray) -> np.ndarray:
    """Per-server max instances: min_r floor(headroom_r / demand_r) (30)(31)."""
    servers = headroom.shape[0]
    cap = np.full(servers, np.iinfo(np.int64).max, dtype=np.int64)
    for r in range(R):
        if demand[r] > 0:
            cap = np.minimum(cap, np.floor(headroom[:, r] / demand[r] + 1e-9).astype(np.int64))
    return np.maximum(cap, 0)


def cost_t_ref(job: Job, state: PriceState, p: np.ndarray, q: np.ndarray,
               t: int, d: int) -> Tuple[float, np.ndarray, np.ndarray]:
    """COST_t(t, d): greedy optimal deployment (Alg. 2 lines 21-44)."""
    H, K = state.cluster.H, state.cluster.K
    y = np.zeros(H, dtype=np.int64)
    z = np.zeros(K, dtype=np.int64)
    if d == 0:
        return 0.0, y, z
    D = job.workers_for(d)
    if D > job.num_chunks:           # constraint (3) can never be met
        return INF, y, z
    # --- workers: cheapest server first -----------------------------------
    w_cost = (p[t] * job.worker_res[None, :]).sum(axis=1)      # (H,)
    w_cap = _server_capacity(state.headroom_workers(t), job.worker_res)
    order = np.argsort(w_cost, kind="stable")
    remaining = D
    for h in order:
        if remaining <= 0:
            break
        take = min(int(w_cap[h]), job.num_chunks - int(y.sum()), remaining)
        y[h] = take
        remaining -= take
    if remaining > 0:
        return INF, y, z
    W = int(y.sum())
    # --- parameter servers -------------------------------------------------
    target = job.ps_for(W)
    s_cost = (q[t] * job.ps_res[None, :]).sum(axis=1)          # (K,)
    s_cap = _server_capacity(state.headroom_ps(t), job.ps_res)
    order_k = np.argsort(s_cost, kind="stable")
    for k in order_k:
        deployed = int(z.sum())
        take = min(int(s_cap[k]), target - deployed, W - deployed)
        if take <= 0:
            continue
        z[k] = take
    if z.sum() * job.ps_bw < W * job.worker_bw - 1e-9:          # line 39
        return INF, y, z
    cost = float((y * w_cost).sum() + (z * s_cost).sum())
    return cost, y, z


def best_schedule_ref(job: Job, state: PriceState) -> Optional[Schedule]:
    """Alg. 2: enumerate deadlines, DP over workload splits."""
    T = state.horizon      # window-local lookahead (== cluster.T episodic)
    a = job.arrival
    Dtot = job.workload
    dcap = min(job.max_chunks_per_slot, Dtot)
    p = state.worker_prices()
    q = state.ps_prices()
    # cost_t rows
    rows = np.full((T, dcap + 1), INF)
    for t in range(a, T):
        for d in range(dcap + 1):
            rows[t, d], _, _ = cost_t_ref(job, state, p, q, t, d)
    # DP: cost[t][d] = min_{d'} rows[t][d'] + cost[t-1][d-d']
    cost = np.full((T, Dtot + 1), INF)
    split = np.zeros((T, Dtot + 1), dtype=np.int64)
    prev = np.full(Dtot + 1, INF)
    prev[0] = 0.0
    best_payoff, best_t = 0.0, -1
    for t in range(a, T):
        for d in range(Dtot + 1):
            lim = min(d, dcap)
            best_c, best_d = INF, 0
            for dp in range(lim + 1):
                c = rows[t, dp] + prev[d - dp]
                if c < best_c - 1e-12:
                    best_c, best_d = c, dp
            cost[t, d] = best_c
            split[t, d] = best_d
        prev = cost[t]
        if cost[t, Dtot] < INF:
            payoff = job.utility(t - a) - cost[t, Dtot]
            if payoff > best_payoff + 1e-12:
                best_payoff, best_t = payoff, t
    if best_t < 0:
        return None
    return _extract(job, state, p, q, split, best_t, best_payoff,
                    cost[best_t, Dtot])


def _extract(job: Job, state: PriceState, p: np.ndarray, q: np.ndarray,
             split: np.ndarray, t_hat: int, payoff: float, total_cost: float
             ) -> Schedule:
    """Backtrack the DP split table and re-run the greedy per slot."""
    workers, ps = {}, {}
    d_rem = job.workload
    for t in range(t_hat, job.arrival - 1, -1):
        d = int(split[t, d_rem])
        if d > 0:
            c, y, z = cost_t_ref(job, state, p, q, t, d)
            assert c < INF
            workers[t] = y
            ps[t] = z
        d_rem -= d
    assert d_rem == 0, f"backtrack failed: {d_rem} chunk-passes unassigned"
    return Schedule(jid=job.jid, workers=workers, ps=ps, finish=t_hat,
                    cost=total_cost, payoff=payoff,
                    utility=job.utility(t_hat - job.arrival))


# ---------------------------------------------------------------------------
# Vectorized implementation
# ---------------------------------------------------------------------------

# Per-server instance caps are clamped here before prefix-summing.  A job with
# zero demand on a pool has unbounded per-server capacity; summing int64 max
# across servers overflows and flips the pool's total capacity negative, which
# silently rejected legal worker-only jobs in the seed implementation.
_CAP_CLAMP = np.int64(1) << 40


def _prefix_tables(prices: np.ndarray, headroom: np.ndarray, demand: np.ndarray
                   ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Sorted per-slot unit costs + prefix sums of capacity and cost.

    Returns (ccap (T, S), ccost (T, S), scost (T, S)) where column j holds the
    cumulative capacity/cost over the j+1 cheapest servers at each slot.
    Whole-array over (T, S, R): no Python loop over slots or resources.
    """
    unit = (prices * demand[None, None, :]).sum(axis=2)   # (T, S)
    pos = demand > 0
    if pos.any():
        with np.errstate(divide="ignore", invalid="ignore"):
            per_r = np.floor(headroom[:, :, pos] / demand[pos][None, None, :]
                             + 1e-9)
        cap = np.minimum(per_r.min(axis=2), float(_CAP_CLAMP))
        cap = np.maximum(cap, 0).astype(np.int64)
    else:
        cap = np.full(unit.shape, _CAP_CLAMP, dtype=np.int64)
    order = np.argsort(unit, axis=1, kind="stable")
    scost = np.take_along_axis(unit, order, axis=1)
    scap = np.take_along_axis(cap, order, axis=1)
    ccap = np.cumsum(scap, axis=1)
    ccost = np.cumsum(scap * scost, axis=1)
    return ccap, ccost, scost


def _greedy_cost_for_counts(ccap: np.ndarray, ccost: np.ndarray, scost: np.ndarray,
                            counts: np.ndarray) -> np.ndarray:
    """Cost of greedily placing ``counts[j]`` instances at each slot row.

    ccap/ccost/scost: (S,) prefix tables for ONE slot; counts: (M,) wanted
    instance totals.  Returns (M,) costs (inf where counts exceed capacity).
    """
    out = np.full(counts.shape, INF)
    cz = counts == 0
    out[cz] = 0.0
    if ccap.size == 0:                      # empty server pool: only 0 fits
        return out
    total = ccap[-1]
    ok = counts <= total
    idx = np.searchsorted(ccap, counts, side="left")   # first prefix covering
    idx = np.minimum(idx, len(ccap) - 1)
    prev_cap = np.where(idx > 0, ccap[np.maximum(idx - 1, 0)], 0)
    prev_cost = np.where(idx > 0, ccost[np.maximum(idx - 1, 0)], 0.0)
    vals = prev_cost + (counts - prev_cap) * scost[idx]
    sel = ok & ~cz
    out[sel] = vals[sel]
    return out


def _greedy_cost_rows(ccap: np.ndarray, ccost: np.ndarray, scost: np.ndarray,
                      counts: np.ndarray) -> np.ndarray:
    """Batched greedy cost: all slots at once.

    ccap/ccost/scost: (T, S) prefix tables; counts: (M,) or (T, M) wanted
    totals per slot.  Returns (T, M) costs.  The per-row searchsorted is
    flattened into one global call by offsetting each row into a disjoint
    integer range (caps are clamped below the offset stride).
    """
    T, S = ccap.shape
    counts = np.broadcast_to(counts, (T, counts.shape[-1])
                             if counts.ndim == 1 else counts.shape)
    M = counts.shape[1]
    out = np.full((T, M), INF)
    out[counts == 0] = 0.0
    if S == 0:                              # empty server pool: only 0 fits
        return out
    stride = np.int64(_CAP_CLAMP) * (S + 1)   # > any row's total capacity
    base = np.arange(T, dtype=np.int64) * stride
    flat = (ccap + base[:, None]).ravel()
    idx = np.searchsorted(flat, (counts + base[:, None]).ravel(),
                          side="left").reshape(T, M)
    idx -= np.arange(T, dtype=np.int64)[:, None] * S
    # gather from zero-prepended prefixes: index i yields prefix over i servers
    pad_cap = np.concatenate([np.zeros((T, 1), np.int64), ccap], axis=1)
    pad_cost = np.concatenate([np.zeros((T, 1)), ccost], axis=1)
    prev_cap = np.take_along_axis(pad_cap, idx, axis=1)
    prev_cost = np.take_along_axis(pad_cost, idx, axis=1)
    marg = np.take_along_axis(scost, np.minimum(idx, S - 1), axis=1)
    vals = prev_cost + (counts - prev_cap) * marg
    sel = (counts <= ccap[:, -1:]) & (counts > 0)
    out[sel] = vals[sel]
    return out


def workload_tables(job: Job, dcap: int) -> Tuple[np.ndarray, np.ndarray]:
    """(W, Z): workers and PS targets for d = 0..dcap, vectorized.

    Elementwise identical to ``job.workers_for`` / ``job.ps_for``.
    """
    ds = np.arange(dcap + 1, dtype=np.float64)
    W = np.ceil(ds * job.quantum * job.chunk_time - 1e-9).astype(np.int64)
    W[0] = 0
    Z = np.ceil(W * job.worker_bw / job.ps_bw - 1e-9).astype(np.int64)
    Z[W == 0] = 0
    return W, Z


def cost_t_rows(job: Job, state: PriceState, p: np.ndarray, q: np.ndarray,
                dcap: int, slots: Optional[np.ndarray] = None) -> np.ndarray:
    """rows[t, d] = COST_t(t, d) for every slot and d in [0, dcap].

    Fully vectorized over (t, d): capacity tables, the cost sort, and the
    prefix-sum greedy costs are whole-array ops — no per-slot Python loop.

    ``slots`` (sorted 1-D slot indices) restricts the computation to those
    slots, returning ``(len(slots), dcap + 1)``, bit-identical to
    ``cost_t_rows(...)[slots]``.
    """
    a = job.arrival
    # read-only access to the host mirrors (not the mutable ``g``/``v``
    # views, which would drop the device residency and the dirty log)
    if slots is None:
        g_s, v_s, p_s, q_s = state._g_host, state._v_host, p, q
    else:
        slots = np.asarray(slots, np.int64)
        g_s, v_s = state._g_host[slots], state._v_host[slots]
        p_s, q_s = p[slots], q[slots]
    n = p_s.shape[0]
    wc_cap, wc_cost, wc_scost = _prefix_tables(
        p_s, state.cluster.worker_caps[None] - g_s, job.worker_res)
    ps_cap, ps_cost, ps_scost = _prefix_tables(
        q_s, state.cluster.ps_caps[None] - v_s, job.ps_res)
    W, Z = workload_tables(job, dcap)                        # (M,)
    feas_n = W <= job.num_chunks
    w_costs = _greedy_cost_rows(wc_cap, wc_cost, wc_scost, W)      # (n, M)
    # PS deployed = min(target, W, pool capacity); feasible iff >= (b/B) W
    pool = ps_cap[:, -1:] if ps_cap.shape[1] else np.zeros((n, 1), np.int64)
    deploy = np.minimum(np.minimum(Z, W)[None, :], pool)           # (n, M)
    feas_ps = deploy * job.ps_bw >= W[None, :] * job.worker_bw - 1e-9
    z_costs = _greedy_cost_rows(ps_cap, ps_cost, ps_scost, deploy)
    rows = np.where(feas_n[None, :] & feas_ps, w_costs + z_costs, INF)
    rows[:, 0] = 0.0
    if slots is None:
        rows[:a] = INF
    else:
        rows[slots < a] = INF
    return rows


def cost_row_flags(rows: np.ndarray, plateau_max: Optional[int] = None
                   ) -> Dict[str, np.ndarray]:
    """Structure flags for a block of COST_t rows — which min-plus path
    each row is eligible for (see ``kernels/minplus/monotone.py``).

    Returns per-row arrays: ``convex`` (exact-arithmetic convexity
    certificate — the soundness condition for the SMAWK-style D&C),
    ``runs`` (maximal bitwise-equal run count — the plateau path's cost
    measure), and ``path`` (the PATH_DNC / PATH_PLATEAU / PATH_CHAIN
    code the dispatcher would pick).  Real COST_t rows are staircases —
    greedy fill composed with ``W(d) = ceil(alpha d)`` — so ``convex``
    is almost never set and ``runs`` is what decides the fast path.
    """
    from repro.kernels.minplus.monotone import (
        PATH_CHAIN, PATH_DNC, PATH_PLATEAU, _PLATEAU_FRACTION,
        convex_certificate_np, run_count_np)
    rows = np.asarray(rows)
    if plateau_max is None:
        plateau_max = max(rows.shape[-1] // _PLATEAU_FRACTION, 1)
    convex = convex_certificate_np(rows)
    runs = run_count_np(rows)
    with np.errstate(invalid="ignore"):
        clean = np.all((rows == rows) & (rows > -np.inf), axis=-1)
    path = np.where(convex, PATH_DNC,
                    np.where(clean & (runs <= plateau_max),
                             PATH_PLATEAU, PATH_CHAIN)).astype(np.int32)
    return {"convex": convex, "runs": runs, "path": path}


# ---------------------------------------------------------------------------
# Seed baseline (per-slot Python loop) — kept verbatim for the decision-
# latency benchmark so speedups stay measurable against the original path.
# ---------------------------------------------------------------------------

def _prefix_tables_loop(prices, headroom, demand, t0):
    T = prices.shape[0]
    unit = (prices * demand[None, None, :]).sum(axis=2)   # (T, S)
    cap = np.zeros(unit.shape, dtype=np.int64)
    full = np.full(unit.shape[1], _CAP_CLAMP, dtype=np.int64)
    for t in range(t0, T):
        c = full.copy()
        for r in range(R):
            if demand[r] > 0:
                c = np.minimum(c, np.floor(headroom[t, :, r] / demand[r] + 1e-9).astype(np.int64))
        cap[t] = np.maximum(c, 0)
    order = np.argsort(unit, axis=1, kind="stable")
    scost = np.take_along_axis(unit, order, axis=1)
    scap = np.take_along_axis(cap, order, axis=1)
    ccap = np.cumsum(scap, axis=1)
    ccost = np.cumsum(scap * scost, axis=1)
    return ccap, ccost, scost


def cost_t_rows_loop(job: Job, state: PriceState, p: np.ndarray, q: np.ndarray,
                     dcap: int) -> np.ndarray:
    """Seed implementation of ``cost_t_rows``: Python loop over slots."""
    T = state.horizon      # window-local lookahead (== cluster.T episodic)
    a = job.arrival
    rows = np.full((T, dcap + 1), INF)
    wc_cap, wc_cost, wc_scost = _prefix_tables_loop(
        p, state.cluster.worker_caps[None] - state.g, job.worker_res, a)
    ps_cap, ps_cost, ps_scost = _prefix_tables_loop(
        q, state.cluster.ps_caps[None] - state.v, job.ps_res, a)
    ds = np.arange(dcap + 1)
    W = np.array([job.workers_for(int(d)) for d in ds])      # (M,)
    feas_n = W <= job.num_chunks
    Z = np.array([job.ps_for(int(w)) for w in W])
    for t in range(a, T):
        w_costs = _greedy_cost_for_counts(wc_cap[t], wc_cost[t], wc_scost[t], W)
        pool = ps_cap[t, -1] if ps_cap.shape[1] else 0
        deploy = np.minimum(np.minimum(Z, W), pool)
        feas_ps = deploy * job.ps_bw >= W * job.worker_bw - 1e-9
        z_costs = _greedy_cost_for_counts(ps_cap[t], ps_cost[t], ps_scost[t], deploy)
        row = np.where(feas_n & feas_ps, w_costs + z_costs, INF)
        row[0] = 0.0
        rows[t] = row
    return rows


def minplus_band(prev: np.ndarray, row: np.ndarray) -> Tuple[np.ndarray, np.ndarray]:
    """new[d] = min_{d'} row[d'] + prev[d - d']; returns (new, argmin)."""
    D = prev.shape[0] - 1
    dcap = row.shape[0] - 1
    ids = np.arange(D + 1)[:, None] - np.arange(dcap + 1)[None, :]   # (D+1, dcap+1)
    prev_ext = np.append(prev, INF)
    cand = row[None, :] + prev_ext[np.where(ids >= 0, ids, -1)]
    cand = np.where(ids >= 0, cand, INF)
    arg = np.argmin(cand, axis=1)
    return cand[np.arange(D + 1), arg], arg


def best_schedule(job: Job, state: PriceState, *, use_jax: bool = False,
                  rows_impl: str = "fast") -> Optional[Schedule]:
    """Vectorized Alg. 2.

    ``use_jax=True`` delegates the whole pipeline to the fused jit engine in
    ``schedule_jax`` (one XLA computation per decision).  ``rows_impl`` picks
    the COST-row builder for the numpy path: ``"fast"`` (whole-array) or
    ``"loop"`` (the seed per-slot baseline, kept for benchmarks).
    """
    if use_jax:
        from .schedule_jax import best_schedule_fused
        return best_schedule_fused(job, state)
    T = state.horizon      # window-local lookahead (== cluster.T episodic)
    a = job.arrival
    Dtot = job.workload
    dcap = min(job.max_chunks_per_slot, Dtot)
    if dcap == 0:
        return None
    p = state.worker_prices()
    q = state.ps_prices()
    rows_fn = cost_t_rows_loop if rows_impl == "loop" else cost_t_rows
    rows = rows_fn(job, state, p, q, dcap)
    cost_tab = np.full((T - a, Dtot + 1), INF)
    split = np.zeros((T - a, Dtot + 1), dtype=np.int64)
    prev = np.full(Dtot + 1, INF)
    prev[0] = 0.0
    for i, t in enumerate(range(a, T)):
        cost_tab[i], split[i] = minplus_band(prev, rows[t])
        prev = cost_tab[i]
    best_payoff, best_i = 0.0, -1
    finite = cost_tab[:, Dtot] < INF
    for i in np.nonzero(finite)[0]:
        payoff = job.utility(i) - cost_tab[i, Dtot]
        if payoff > best_payoff + 1e-12:
            best_payoff, best_i = payoff, int(i)
    if best_i < 0:
        return None
    full_split = np.zeros((T, Dtot + 1), dtype=np.int64)
    full_split[a:] = split
    return _extract(job, state, p, q, full_split, a + best_i, best_payoff,
                    float(cost_tab[best_i, Dtot]))
