"""Fused, jit-compiled JAX backend for the Alg. 2 dual subroutine.

The engine runs the WHOLE per-arrival pipeline as XLA computations: dual
prices from the allocation state, per-server capacity + sorted prefix-sum
greedy COST_t rows, the banded min-plus DP sweep over slots, the payoff
argmax with the reference tie rule, the split-table backtrack, and the
greedy placement extraction.

**Tiled decision core** (``_decide_tiled``): the horizon is walked in
``TILE``-slot blocks inside a ``lax.while_loop``, natively batched over a
lane axis so an entire arrival burst is one device launch:

* blocks before the earliest arrival in the batch are skipped outright
  (their COST rows are the DP identity ``[0, inf, ...]``);
* after each block the loop exits early once **no remaining slot can beat
  the incumbent payoff for any lane** — exact, not heuristic, because the
  suffix maximum of the utility curve bounds future payoffs from above and
  every schedule's cost is bounded below by the LIVE price-floor bound
  ``workload * min_d(workers_for(d)/d) * min over feasible slots of the
  cheapest single-worker slot cost`` at the current prices (>= the static
  ``L1 * sum(worker_res)`` floor, and far tighter once the cluster fills
  up).  The reference tie rule (``> best + 1e-12``) therefore cannot
  switch on any skipped slot and decisions stay bit-identical to
  ``best_schedule_ref``;
* COST rows can be served from a :class:`RowCache` — a commit only moves
  prices inside the committed slot window, so re-solves (the sequential
  half of ``OASiS.on_arrivals``) recompute only dirtied tiles.

Placement is extracted by a second, small jit (``_place_slots``) over
just the slots of the accepted schedule that actually deploy, so the
decision loop never materializes placement tables for slots it will
not use.

``best_schedule_fused_batch`` decides a padded batch of jobs (shared
price state) in one launch per shape bucket — the speculative half of
``OASiS.on_arrivals``.

Precision: on CPU the engine runs under ``jax.enable_x64`` by default so
its decisions match the float64 numpy/reference paths exactly; on TPU it
runs float32 (f64 is unsupported there).  Single arrivals on TPU take the
legacy monolithic core with the Pallas min-plus sweep kernel
(``use_pallas=True``), which Mosaic compiles whenever the launch is
lowered for a TPU (``kernels.minplus.ops.minplus_sweep``); bursts take
the tiled core.

``dp_sweep_jax`` (the seed's DP-only entry point) is kept for
micro-benches and backward compatibility.
"""
from __future__ import annotations

import dataclasses
import functools
import os
import time
import weakref
from typing import List, Optional, Sequence, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from ..kernels.minplus.monotone import (PATH_CHAIN, PATH_DNC, PATH_PLATEAU,
                                        convex_certificate, monotone_dnc_step,
                                        plateau_step_unrolled, run_count)
from ..kernels.minplus.ops import minplus_sweep
from ..kernels.minplus.ref import minplus_sweep_cost, minplus_sweep_ref
from ..kernels.minplus.tiled import TILE, minplus_chain_step
from .pricing import PriceState, size_bucket as _bucket
from .types import Job, R, Schedule
from .. import obs as _obs

# JAX's persistent compilation cache keys a program without its metadata
# by default, so an executable compiled from another version of this
# source is served with that version's ``op_name`` scopes, and a profile
# credits its device time to stages this source does not have.  Key on
# the metadata too: the stage scopes below are what the profiles read.
jax.config.update("jax_compilation_cache_include_metadata_in_key", True)

# Stand-in for "unbounded" per-server instance capacity (job has no demand
# on some resource): big enough to never bind, small enough that prefix sums
# of it stay exact-ish in f32 comparisons against tiny instance counts.
_BIG_CAP = 1.0e9
_PAY_EPS = 1e-12        # payoff tie epsilon — same as the reference path
# safety margin on the price-floor cost lower bound: the bound is proved
# in exact arithmetic; scale it down so float64 rounding in the engine's
# prefix sums can never push a computed cost below it
_LB_MARGIN = 0.999
# split-tie band for the backtrack argmin: XLA vectorizes the same f64
# pipeline differently per launch shape (lane count, cache path), so two
# launches over identical state can disagree in the LAST ULPS of a DP
# cell.  An exact argmin then flips between equally-optimal splits and
# the committed placements — hence the whole price trajectory — fork
# between the burst and sequential paths.  Snapping the backtrack to the
# first index within this RELATIVE band of the minimum makes the split a
# function of the (macroscopically) optimal set, not of ulp noise: costs
# are nonnegative sums of ≲1e3 rounded f64 terms, so cross-launch noise
# on an exact tie stays ≲1e-13 relative, while genuinely distinct splits
# differ by far more than 1e-12 relative.  Decisions (best_t) are
# already protected the same way by _PAY_EPS.
_SPLIT_TOL = 1e-12
# Lane cap per launch: bounds the (B, T_pad, D+1) DP table memory.  On a
# single-core CPU backend the DP sweep is memory-bandwidth bound and lane
# fusion scales SUPERLINEARLY in wall clock (8 fused lanes measured ~2.7x
# the cost of 8 singleton launches at paper-10x shapes), so bursts there
# decide lane-by-lane — still speculative, still one RowCache per job —
# while parallel backends get real fusion.  Override with REPRO_BURST_LANES.
_MAX_LANES = int(os.environ.get(
    "REPRO_BURST_LANES", "8" if jax.default_backend() == "tpu" else "1"))


# ---------------------------------------------------------------------------
# Seed-compatible DP-only entry point
# ---------------------------------------------------------------------------

@functools.partial(jax.jit, static_argnames=("d_total", "use_pallas"))
def _sweep(rows: jax.Array, d_total: int, use_pallas: bool
           ) -> Tuple[jax.Array, jax.Array]:
    if use_pallas:
        return minplus_sweep(rows, d_total)
    return minplus_sweep_ref(rows, d_total)


def dp_sweep_jax(rows: np.ndarray, d_total: int, use_pallas: bool = False
                 ) -> Tuple[np.ndarray, np.ndarray]:
    """rows: (T', dcap+1) with +inf; returns (cost (T', D+1), split (T', D+1)).

    Runs in float64 when ``jax_enable_x64`` is on (the numpy path's dtype),
    float32 otherwise.  The Pallas path is always float32 (TPU VPU kernel).
    """
    dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
    rows_j = jnp.asarray(np.nan_to_num(rows, posinf=np.inf), dtype)
    costs, args = _sweep(rows_j, int(d_total), bool(use_pallas))
    return np.asarray(costs, np.float64), np.asarray(args, np.int64)


# ---------------------------------------------------------------------------
# Shared single-lane helpers (also used by the legacy Pallas core)
# ---------------------------------------------------------------------------

def _price_pow(ratio: jax.Array, x: jax.Array) -> jax.Array:
    """``ratio ** x`` computed as ``exp(x * log(ratio))``.

    XLA's CPU backend lowers a broadcast ``pow`` with a non-constant base
    to per-element libm calls (~100 ns each), which made the per-tile
    price tables the single largest cost of a fused decision launch; the
    explicit exp/log form vectorizes.  ``ratio`` is clamped to
    ``1 + 1e-9`` upstream so the log is always finite, and ``x == 0``
    still yields exactly 1.  Every price computation in this module must
    go through this helper — mixing it with ``**`` would produce
    last-ulp price disagreements between the decision and placement
    paths.
    """
    return jnp.exp(x * jnp.log(ratio))


def _sort_servers(unit: jax.Array, cap: jax.Array, with_order: bool):
    """``(scost, scap[, order])``: per-slot unit costs and capacities in
    cheapest-first server order along the last axis, ties kept in server
    order.  One stable sort keyed on ``unit`` carries ``cap`` (and, with
    ``with_order``, the server index) beside it, with no gather after it:
    ``jnp.argsort`` is that sort with the index alone, so the order is
    the same, and a data-dependent gather is the slowest thing a TPU does
    over a 100-wide server axis."""
    ops = (unit, cap)
    if with_order:
        ops += (jax.lax.broadcasted_iota(
            jax.dtypes.canonicalize_dtype(jnp.int_), unit.shape,
            unit.ndim - 1),)
    return jax.lax.sort(ops, dimension=unit.ndim - 1, is_stable=True,
                        num_keys=1)


def _prefix_tables_jnp(prices: jax.Array, headroom: jax.Array,
                       demand: jax.Array):
    """Per-slot sorted unit costs + prefix sums (whole-array, all slots).

    Returns (order, scap, scost, ccap, ccost), each (T, S)."""
    unit = (prices * demand[None, None, :]).sum(axis=2)          # (T, S)
    safe = jnp.where(demand > 0, demand, 1.0)
    per_r = jnp.where(demand[None, None, :] > 0,
                      jnp.floor(headroom / safe[None, None, :] + 1e-9),
                      _BIG_CAP)
    cap = jnp.clip(jnp.min(per_r, axis=2), 0.0, _BIG_CAP)        # (T, S)
    scost, scap, order = _sort_servers(unit, cap, with_order=True)
    ccap = jnp.cumsum(scap, axis=1)
    ccost = jnp.cumsum(scap * scost, axis=1)
    return order, scap, scost, ccap, ccost


def _greedy_cost_search(ccap: jax.Array, ccost: jax.Array, scost: jax.Array,
                        counts: jax.Array) -> jax.Array:
    """``_greedy_cost`` by binary search and gathers (the XLA CPU form).

    On XLA CPU the dense ``(..., M, S)`` comparison of
    ``_greedy_cost_dense`` was ~10x the cost of everything else here, and
    ``searchsorted`` returns exactly its ``(ccap < counts).sum(-1)`` on
    the nondecreasing ``ccap``."""
    S = ccap.shape[-1]
    search = functools.partial(jnp.searchsorted, side="left")
    for _ in range(ccap.ndim - 1):
        search = jax.vmap(search)
    idx = search(ccap, counts)
    zcol = jnp.zeros(ccap.shape[:-1] + (1,), ccap.dtype)
    prev_cap = jnp.take_along_axis(
        jnp.concatenate([zcol, ccap], -1), idx, -1)
    prev_cost = jnp.take_along_axis(
        jnp.concatenate([zcol, ccost], -1), idx, -1)
    marg = jnp.take_along_axis(scost, jnp.minimum(idx, S - 1), -1)
    vals = prev_cost + (counts - prev_cap) * marg
    return jnp.where(counts == 0, 0.0,
                     jnp.where(counts <= ccap[..., -1:], vals, jnp.inf))


def _greedy_cost_dense(ccap: jax.Array, ccost: jax.Array, scost: jax.Array,
                       counts: jax.Array) -> jax.Array:
    """``_greedy_cost`` as dense masked reductions over the server axis
    (the TPU form): the covering prefix is a compare-and-count, and each
    table is read by a one-hot sum, one value plus zeros, so every number
    equals ``_greedy_cost_search``'s bit for bit.  A one-hot read does not
    lean on ``ccost`` being monotone, which a cumsum's summation tree
    does not promise."""
    S = ccap.shape[-1]
    s = jnp.arange(S)
    idx = (ccap[..., None, :] < counts[..., :, None]).sum(-1)

    def pick(table, i):
        return jnp.where(s == i[..., None], table[..., None, :], 0.0).sum(-1)

    prev_cap = pick(ccap, idx - 1)
    prev_cost = pick(ccost, idx - 1)
    marg = pick(scost, jnp.minimum(idx, S - 1))
    vals = prev_cost + (counts - prev_cap) * marg
    return jnp.where(counts == 0, 0.0,
                     jnp.where(counts <= ccap[..., -1:], vals, jnp.inf))


# The greedy-cost lookup each platform lowers (``_greedy_cost``'s
# ``platform_dependent`` branches; platforms not named take "default"),
# each with the obs counter of the COST-row tiles built by it
# (``_rows_lookup_counter``).  A gather loop is what costs on the TPU;
# XLA CPU runs the dense form over ten times slower than the search.
_GREEDY_COST_FORMS = {
    "tpu": (_greedy_cost_dense, "decide.rows_dense_tiles"),
    "default": (_greedy_cost_search, "decide.rows_search_tiles"),
}


def _greedy_cost(ccap: jax.Array, ccost: jax.Array, scost: jax.Array,
                 counts: jax.Array) -> jax.Array:
    """Greedy (cheapest-first) deployment cost for ``counts`` (..., M) at
    every slot, from (..., S) prefix tables.  +inf where counts exceed
    capacity.  The form is the lowering platform's in
    ``_GREEDY_COST_FORMS``."""
    return jax.lax.platform_dependent(
        ccap, ccost, scost, counts,
        **{p: fn for p, (fn, _) in _GREEDY_COST_FORMS.items()})


def _greedy_place_jnp(order: jax.Array, scap: jax.Array, ccap: jax.Array,
                      count: jax.Array) -> jax.Array:
    """Per-server instance counts for a greedy fill of ``count`` (T,) at each
    slot: cheapest servers first, each up to its capacity.  Returns (T, S)
    int32 in ORIGINAL server order."""
    prev = jnp.concatenate(
        [jnp.zeros((ccap.shape[0], 1), ccap.dtype), ccap[:, :-1]], axis=1)
    take = jnp.clip(count[:, None] - prev, 0.0, scap)            # sorted order
    inv = jnp.argsort(order, axis=1, stable=True)                # rank of h
    return jnp.round(jnp.take_along_axis(take, inv, axis=1)).astype(jnp.int32)


# ---------------------------------------------------------------------------
# Batched (lane-axis) helpers for the tiled core
# ---------------------------------------------------------------------------

def _prefix_tables_b(prices: jax.Array, headroom: jax.Array,
                     demand: jax.Array):
    """Lane-batched prefix tables for one tile.

    prices/headroom: (TILE, S, R) shared across lanes; demand: (B, R) per
    lane.  Returns (scost, ccap, ccost), each (B, TILE, S) — the greedy
    cost tables only (placement order is extracted by ``_place_slots``,
    never in the decision loop).  The sort carries each server's
    capacity beside its unit cost and no index (``_sort_servers``)."""
    unit = (prices[None] * demand[:, None, None, :]).sum(axis=3)
    safe = jnp.where(demand > 0, demand, 1.0)
    per_r = jnp.where(demand[:, None, None, :] > 0,
                      jnp.floor(headroom[None] / safe[:, None, None, :]
                                + 1e-9),
                      _BIG_CAP)
    cap = jnp.clip(jnp.min(per_r, axis=3), 0.0, _BIG_CAP)
    scost, scap = _sort_servers(unit, cap, with_order=False)
    ccap = jnp.cumsum(scap, axis=2)
    ccost = jnp.cumsum(scap * scost, axis=2)
    return scost, ccap, ccost


# ---------------------------------------------------------------------------
# Per-job sorted-order / cumsum tables (the "order cache")
# ---------------------------------------------------------------------------

@jax.jit
@jax.named_scope("decide.rows")
def _sorted_fill_lanes(p, q, g, v, wcaps, scaps, resbw):
    """Full sorted-order/cumsum table set for every lane: 6 arrays
    (B, T_pad, H|K) — ``(w_scost, w_ccap, w_ccost, s_scost, s_ccap,
    s_ccost)``.

    ``_prefix_tables_b``'s ops (reduce over R, sort + cumsum along
    the trailing server axis) touch each slot independently, so TILE
    slices of these tables are bit-identical to the per-tile tables the
    decide loop used to build inline — and the per-tile sorts leave
    the decide launch entirely."""
    wres, sres = resbw[:, :R], resbw[:, R:2 * R]
    w = _prefix_tables_b(p, wcaps[None] - g, wres)
    s = _prefix_tables_b(q, scaps[None] - v, sres)
    return w + s


@functools.partial(jax.jit, static_argnames=("span",))
@jax.named_scope("decide.rows")
def _sorted_fill(tabs, p, q, g, v, wcaps, scaps, resbw, t0, span: int):
    """Patch one dirty slot span of a single lane's (T_pad, S) table set
    in place — the exact ``_sorted_fill_lanes`` formulas on the span's
    rows, so the patched tables are bit-identical to a full rebuild at
    the new state version (per-slot sort cost O(dirty) on re-solves)."""
    zero = jnp.zeros_like(t0)
    p_s = jax.lax.dynamic_slice(p, (t0, zero, zero), (span,) + p.shape[1:])
    q_s = jax.lax.dynamic_slice(q, (t0, zero, zero), (span,) + q.shape[1:])
    g_s = jax.lax.dynamic_slice(g, (t0, zero, zero), (span,) + g.shape[1:])
    v_s = jax.lax.dynamic_slice(v, (t0, zero, zero), (span,) + v.shape[1:])
    wres, sres = resbw[None, :R], resbw[None, R:2 * R]
    w = _prefix_tables_b(p_s, wcaps[None] - g_s, wres)
    s = _prefix_tables_b(q_s, scaps[None] - v_s, sres)
    return tuple(jax.lax.dynamic_update_slice(tab, n[0], (t0, zero))
                 for tab, n in zip(tabs, w + s))


# ---------------------------------------------------------------------------
# Tiled, batched decision core
# ---------------------------------------------------------------------------

def _mono_band() -> int:
    """Band-width ceiling for the monotone min-plus dispatch (env-tunable;
    0 disables).  Re-read per launch so tests can toggle it."""
    return int(os.environ.get("REPRO_MONOTONE_BAND", "64"))


def _mono_dnc() -> bool:
    """Whether the decide loop may take the SMAWK-style divide-and-conquer
    branch (vs plateau/chain only).  Default off: on CPU XLA the D&C's
    scatter-heavy lowering loses to the unrolled chain at every shape we
    measured, and compiling it per shape bucket adds seconds of cold
    latency — the kernel stays fully exercised via ops/tests/benchmarks."""
    return os.environ.get("REPRO_MONOTONE_DNC", "") not in ("", "0")

def _table_max() -> int:
    """Order-cache footprint ceiling: full sorted-table sets are only
    built (and thereafter span-patched) when ``T_pad * max(H, K)`` is at
    most this many slot-server cells.  Above it the one-shot build costs
    more than it can ever amortize — XLA CPU's stable argsort over a
    (512, 100) table runs ~26 ms while the early-exit decide loop sorts
    only the tiles it visits — so big shapes keep the inline per-tile
    path and small re-solve-heavy shapes (serving windows) get O(dirty)
    patching.  Env-tunable for the order-cache tests."""
    return int(os.environ.get("REPRO_ORDER_CACHE_MAX", "16384"))


@functools.lru_cache(maxsize=4)
def _dummy_tabs(dtype_name: str):
    """Placeholder tabs operand for ``use_tabs=False`` launches (the
    static flag keeps them out of the compiled program entirely)."""
    z = jnp.zeros((1, 1, 1), jnp.dtype(dtype_name))
    return (z,) * 6


def _decide_tiled_core(sd, jd, tabs, rows_init, valid_tiles, *, T: int,
                       d1: int, use_cache: bool, mono: int,
                       use_tabs: bool):
    """Alg. 2 decisions for a lane batch, horizon-tiled with exact early
    exit (module docstring).

    sd: PADDED state arrays from ``_pad_state`` (g (T_pad,H,R),
        v (T_pad,K,R), wcaps (H,R), scaps (K,R), U1 (R,), U2 (R,),
        L1 (), L2 (), pmin (T_pad, R) — the per-slot minimum worker
        price for the live cost floor, precomputed per state version)
    jd: lane-batched job arrays —
        resbw (B, 2R+2) = [wres, sres, wbw, psbw],
        WZ (B, 2, M) i32, u (B, T_pad), usmax (B, T_pad) suffix-max of u,
        meta (B, 4) i32 = [a, nchunks, d_tot, dcap], lb (B,) — the
        price-free per-chunk-pass lower-bound base from
        ``_cost_lower_bound`` (a live greedy price floor over the
        cheapest feasible slots is multiplied in on device).
    tabs: per-job sorted-order/cumsum tables from ``_sorted_fill_lanes``
        — 6 arrays (B, T_pad, H|K) when ``use_tabs``; the decide loop
        then only slices them, so it runs no prices and no sorts at
        all.  When ``use_tabs`` is False (the common first-decision
        path), tabs are (1, 1, 1) dummies and the loop builds each
        visited tile's tables inline from the cached price tables —
        sorts only on visited tiles, which the early exit keeps far
        below T_pad.
    rows_init/valid_tiles: ``use_cache`` row cache — (B, T_pad, M) rows at
        the current prices plus a (B, n_tiles) tile-validity mask; a tile
        is recomputed unless it is valid for EVERY lane.  Scalars when
        ``use_cache`` is False.
    T: static — the real (unpadded) horizon.
    d1: static — DP columns (padded D_total + 1).
    mono: static — monotone min-plus dispatch level: 0 = chain only,
        1 = staircase-plateau + chain, 2 = also the divide-and-conquer
        branch (``REPRO_MONOTONE_DNC``).  Levels > 0 require a single
        lane; the branch is chosen ONCE PER TILE (per-slot dispatch costs
        more than it saves) and every branch produces bit-identical DP
        values (see ``kernels.minplus.monotone``).

    Returns (best_t i32 (-1 = reject), payoff, total_cost, d_left i32,
    d_slots (B, T_pad) i32, rows (B, T_pad, M) — the refreshed row cache —
    k0, k_end i32: the visited tile range [k0, k_end), paths (3,) i32 —
    per-branch processed-tile counts [dnc, plateau, chain]).

    Device scopes: each tile's COST rows (built or served from the row
    cache) run under ``decide.rows``; the rest of the tile loop — its
    exit test and live cost floor, the slot scan with its payoff pick,
    the buffer writes — under ``decide.dp``.
    """
    g, v, wcaps, scaps, U1, U2, L1, L2, pmin, p_pad, q_pad = sd
    resbw, WZ, u, usmax, meta, lb = jd
    B = resbw.shape[0]
    T_pad = u.shape[1]
    n_tiles = T_pad // TILE
    M = WZ.shape[2]
    dt = g.dtype
    wres, sres = resbw[:, :R], resbw[:, R:2 * R]
    wbw, psbw = resbw[:, 2 * R], resbw[:, 2 * R + 1]
    W, Z = WZ[:, 0], WZ[:, 1]                                    # (B, M) i32
    a, nchunks, d_tot = meta[:, 0], meta[:, 1], meta[:, 2]
    dcap = meta[:, 3]
    tw_scost, tw_ccap, tw_ccost, ts_scost, ts_ccap, ts_ccost = tabs
    H = g.shape[1]
    K = v.shape[1]
    if mono:
        assert B == 1, "monotone dispatch is single-lane only"
    r_max = max(16, M // 4)

    Wf = W.astype(dt)
    deploy_target = jnp.minimum(Z, W).astype(dt)                 # (B, M)
    feas_n = (W <= nchunks[:, None])[:, None, :]                 # (B, 1, M)
    ms = jnp.arange(M)

    def rows_for_tile(t0):
        """COST_t rows for slots [t0, t0+TILE), all lanes: (B, TILE, M).

        ``use_tabs``: assembled from the cached sorted tables (greedy
        prefix lookups only — the prices and sorts happened in the
        table build).  Otherwise the tile's prefix tables are built here
        from slices of the version-cached price tables, with the SAME
        ``_prefix_tables_b`` formulas — the two modes are bit-identical
        (sort + cumsum touch each slot independently)."""
        zero = jnp.zeros_like(t0)
        if use_tabs:
            w_scost = jax.lax.dynamic_slice(
                tw_scost, (zero, t0, zero), (B, TILE, H))
            w_ccap = jax.lax.dynamic_slice(
                tw_ccap, (zero, t0, zero), (B, TILE, H))
            w_ccost = jax.lax.dynamic_slice(
                tw_ccost, (zero, t0, zero), (B, TILE, H))
            s_scost = jax.lax.dynamic_slice(
                ts_scost, (zero, t0, zero), (B, TILE, K))
            s_ccap = jax.lax.dynamic_slice(
                ts_ccap, (zero, t0, zero), (B, TILE, K))
            s_ccost = jax.lax.dynamic_slice(
                ts_ccost, (zero, t0, zero), (B, TILE, K))
        else:
            nr = p_pad.shape[2]
            p_t = jax.lax.dynamic_slice(
                p_pad, (t0, zero, zero), (TILE, H, nr))
            q_t = jax.lax.dynamic_slice(
                q_pad, (t0, zero, zero), (TILE, K, nr))
            g_t = jax.lax.dynamic_slice(
                g, (t0, zero, zero), (TILE, H, nr))
            v_t = jax.lax.dynamic_slice(
                v, (t0, zero, zero), (TILE, K, nr))
            w_scost, w_ccap, w_ccost = _prefix_tables_b(
                p_t, wcaps[None] - g_t, wres)
            s_scost, s_ccap, s_ccost = _prefix_tables_b(
                q_t, scaps[None] - v_t, sres)
        Wt = jnp.broadcast_to(Wf[:, None, :], (B, TILE, M))
        w_costs = _greedy_cost(w_ccap, w_ccost, w_scost, Wt)
        pool = s_ccap[..., -1:]                                  # (B, TILE, 1)
        deploy = jnp.minimum(deploy_target[:, None, :], pool)
        feas_ps = deploy * psbw[:, None, None] >= Wt * wbw[:, None, None] - 1e-9
        z_costs = _greedy_cost(s_ccap, s_ccost, s_scost, deploy)
        rows = jnp.where(feas_n & feas_ps, w_costs + z_costs, jnp.inf)
        rows = rows.at[:, :, 0].set(0.0)
        # pre-arrival and beyond-horizon slots carry the DP unchanged
        ts = t0 + jnp.arange(TILE, dtype=jnp.int32)
        dead = (ts[None, :] < a[:, None]) | (ts >= T)[None, :]
        return jnp.where(dead[:, :, None] & (ms > 0)[None, None, :],
                         jnp.inf, rows)

    a_min = jnp.min(a)
    init_col = jnp.full((B, d1), jnp.inf, dt).at[:, 0].set(0.0)
    if use_cache:
        rows_buf0 = rows_init
    else:
        rows_buf0 = jnp.full((B, T_pad, M), jnp.inf, dt).at[:, :, 0].set(0.0)
    cost_buf0 = jnp.full((B, T_pad, d1), jnp.inf, dt)
    k0 = jnp.min(a).astype(jnp.int32) // TILE
    t_start = k0 * TILE

    # Live early-exit cost floor.  ``lb`` from the host is the price-free
    # per-chunk-pass base min_d(W(d)/d) (times _LB_MARGIN); every worker
    # a schedule deploys in slot s costs >= sum_r wres_r * min_h
    # p[s,h,r] =: wslot[s], so placing d chunk-passes in slot s costs
    # >= d * base * wslot[s].  A schedule can place at most dcap
    # chunk-passes per slot, so ANY schedule's total cost is >= base
    # times the greedy spread of d_tot over the CHEAPEST feasible slots
    # (dcap each, remainder on the last) — minimizing sum_s d_s *
    # wslot[s] subject to 0 <= d_s <= dcap, sum d_s = d_tot puts dcap on
    # the cheapest slots, so the spread is a true minimum over feasible
    # splits.  This reduces to the old single-cheapest-slot floor when
    # dcap >= d_tot and is far tighter for multi-slot workloads: rejects
    # exit the tile loop after a prefix of the horizon (often before the
    # first tile) instead of sweeping the DP to the deadline.  ``pmin``
    # (the per-slot minimum worker price, (T_pad, R)) is computed once
    # per state version in ``_pad_state``, not per launch.
    with jax.named_scope("decide.dp"):
        wslot = jnp.einsum("tr,br->bt", pmin, wres)
        ts_all = jnp.arange(T_pad, dtype=jnp.int32)
        feas_t = (ts_all[None, :] >= a[:, None]) & (ts_all < T)[None, :]
        wsort = jnp.sort(jnp.where(feas_t, wslot, jnp.inf), axis=1)
        dcap_f = jnp.maximum(dcap, 1).astype(dt)
        take = jnp.clip(d_tot[:, None].astype(dt)
                        - ts_all[None, :].astype(dt) * dcap_f[:, None],
                        0.0, dcap_f[:, None])
        # infeasible-window tail: missing slots contribute 0, keeping the
        # floor a valid (weaker) lower bound; the DP itself rejects such
        # jobs
        floor_sum = jnp.sum(
            take * jnp.where(jnp.isfinite(wsort), wsort, 0.0), axis=1)
        lb = jnp.where(lb > 0, lb * floor_sum, 0.0)

    def cond(c):
        k, _, best, _, _, _, _ = c
        t_next = jnp.clip(k * TILE, 0, T_pad - 1)
        um = jax.lax.dynamic_slice_in_dim(usmax, t_next, 1, axis=1)[:, 0]
        active = um > best + _PAY_EPS + lb
        return (k < n_tiles) & jnp.any(active)

    def body(c):
        k, prev, best, best_t, paths, cost_buf, rows_buf = c
        t0 = k * TILE
        zero = jnp.zeros_like(t0)
        with jax.named_scope("decide.rows"):
            if use_cache:
                tile_ok = jnp.all(
                    jax.lax.dynamic_slice_in_dim(valid_tiles, k, 1, axis=1))
                rows_tile = jax.lax.cond(
                    tile_ok,
                    lambda: jax.lax.dynamic_slice(
                        rows_init, (zero, t0, zero), (B, TILE, M)),
                    lambda: rows_for_tile(t0))
            else:
                rows_tile = rows_for_tile(t0)
        u_tile = jax.lax.dynamic_slice(u, (zero, t0), (B, TILE))
        ts_tile = t0 + jnp.arange(TILE, dtype=jnp.int32)

        # Monotone min-plus dispatch, decided ONCE for the whole tile:
        # every slot row in the tile must qualify, because a per-slot
        # branch costs more in dispatch than the fast path saves.  The
        # plateau gate (run_count <= r_max, no NaN / -inf) is exactly the
        # soundness condition of ``plateau_step_unrolled``; identity rows
        # of dead slots have 2 runs and never block it.
        if mono:
            rt = rows_tile[0]
            clean = jnp.all((rt == rt) & (rt > -jnp.inf))
            plat_ok = clean & jnp.all(jax.vmap(run_count)(rt) <= r_max)
            if mono >= 2:
                conv_ok = clean & jnp.all(jax.vmap(convex_certificate)(rt))
                branch = jnp.where(
                    conv_ok, PATH_DNC,
                    jnp.where(plat_ok, PATH_PLATEAU, PATH_CHAIN))
            else:
                branch = jnp.where(plat_ok, PATH_PLATEAU, PATH_CHAIN)
        else:
            branch = jnp.int32(PATH_CHAIN)
        paths = paths.at[branch].add(1)

        def slot(carry, x):
            prev, best, best_t = carry
            row, u_t, t = x

            def live(_):
                if mono >= 2:
                    def _dnc():
                        out, ovf = monotone_dnc_step(row[0], prev[0])
                        return jax.lax.cond(
                            ovf,
                            lambda: minplus_chain_step(row, prev),
                            lambda: out[None])
                    new = jax.lax.switch(branch, [
                        _dnc,
                        lambda: plateau_step_unrolled(
                            row[0], prev[0], r_max)[None],
                        lambda: minplus_chain_step(row, prev)])
                elif mono:
                    new = jax.lax.cond(
                        branch == PATH_PLATEAU,
                        lambda: plateau_step_unrolled(
                            row[0], prev[0], r_max)[None],
                        lambda: minplus_chain_step(row, prev))
                else:
                    new = minplus_chain_step(row, prev)
                costD = jnp.take_along_axis(new, d_tot[:, None],
                                            axis=1)[:, 0]
                pay = jnp.where(jnp.isfinite(costD) & (t >= a) & (t < T),
                                u_t - costD, -jnp.inf)
                switch = pay > best + _PAY_EPS
                return (new, jnp.where(switch, pay, best),
                        jnp.where(switch, t, best_t))

            def dead(_):
                # slots before every lane's arrival (or past the horizon)
                # have the identity row [0, inf, ...]: the chain step
                # would return ``prev`` bit-for-bit, so skip it at
                # runtime — with single-lane launches this skips the DP
                # for the whole pre-arrival prefix of the first tile
                return (prev, best, best_t)

            new, best, best_t = jax.lax.cond(
                (t >= a_min) & (t < T), live, dead, None)
            return (new, best, best_t), new

        (prev, best, best_t), cols = jax.lax.scan(
            slot, (prev, best, best_t),
            (jnp.swapaxes(rows_tile, 0, 1), u_tile.T, ts_tile))
        cost_buf = jax.lax.dynamic_update_slice(
            cost_buf, jnp.swapaxes(cols, 0, 1), (zero, t0, zero))
        rows_buf = jax.lax.dynamic_update_slice(
            rows_buf, rows_tile, (zero, t0, zero))
        return k + 1, prev, best, best_t, paths, cost_buf, rows_buf

    with jax.named_scope("decide.dp"):
        k_end, _, best, best_t, paths, cost_buf, rows_buf = \
            jax.lax.while_loop(
                cond, body,
                (k0, init_col, jnp.zeros((B,), dt),
                 jnp.full((B,), -1, jnp.int32), jnp.zeros((3,), jnp.int32),
                 cost_buf0, rows_buf0))
    return best_t, best, rows_buf, cost_buf, k0, k_end, paths


@functools.partial(jax.jit,
                   static_argnames=("T", "d1", "use_cache", "mono",
                                    "use_tabs"))
def _decide_tiled(sd, jd, tabs, rows_init, valid_tiles, T: int, d1: int,
                  use_cache: bool, mono: int, use_tabs: bool):
    return _decide_tiled_core(sd, jd, tabs, rows_init, valid_tiles, T=T,
                              d1=d1, use_cache=use_cache, mono=mono,
                              use_tabs=use_tabs)


@jax.jit
@jax.named_scope("decide.backtrack")
def _backtrack(rows_lane: jax.Array, cost_lane: jax.Array, best_t, d_tot,
               t_start):
    """Split recovery for ONE accepted lane, from the decision loop's
    stored row/cost tables (device-resident; rejects never pay this).

    Walks t DOWN from ``best_t`` (later slots place nothing by
    construction), recomputing each slot's split as the FIRST j with
    rows[t, j] + cost_{t-1}[d_rem - j] within ``_SPLIT_TOL`` of the
    minimum — an exact argmin would make the split (and so the committed
    placements) a function of launch-shape ulp noise; see the
    ``_SPLIT_TOL`` note.  Stops as soon as the remaining workload hits
    zero: every earlier slot's only in-band candidate is then j = 0
    (idx = -j < 0 is masked to inf for j > 0 and vals[0] = 0 + prev[0]),
    so skipping them is bit-identical to the full scan the loop
    replaces — and a typical accept backtracks a short suffix of the
    horizon instead of all T_pad slots.  ``t_start`` is the first slot
    the decision loop processed (earlier slots carry the DP identity).
    Returns (total_cost, d_left, d_slots (T_pad,) i32)."""
    T_pad, M = rows_lane.shape
    d1 = cost_lane.shape[1]
    dt = cost_lane.dtype
    init_col = jnp.full((d1,), jnp.inf, dt).at[0].set(0.0)
    js = jnp.arange(M)

    def cond(c):
        t, d_rem, _ = c
        return (t >= 0) & (d_rem > 0)

    def body(c):
        t, d_rem, d_slots = c
        row = jax.lax.dynamic_slice_in_dim(rows_lane, t, 1, axis=0)[0]
        prev = jax.lax.dynamic_slice_in_dim(
            cost_lane, jnp.maximum(t - 1, 0), 1, axis=0)[0]
        prev = jnp.where(t <= t_start, init_col, prev)
        idx = d_rem - js
        vals = jnp.where(idx >= 0, row + prev[jnp.clip(idx, 0, d1 - 1)],
                         jnp.inf)
        m = jnp.min(vals)
        band = vals <= m * (1.0 + _SPLIT_TOL)
        d_here = jnp.argmax(band).astype(jnp.int32)
        return t - 1, d_rem - d_here, d_slots.at[t].set(d_here)

    _, d_left, d_slots = jax.lax.while_loop(
        cond, body,
        (jnp.clip(best_t, -1, T_pad - 1), d_tot,
         jnp.zeros((T_pad,), jnp.int32)))
    bt = jnp.clip(best_t, 0, T_pad - 1)
    col = jax.lax.dynamic_slice_in_dim(cost_lane, bt, 1, axis=0)[0]
    total_cost = col[jnp.minimum(d_tot, d1 - 1)]
    return total_cost, d_left, d_slots


@functools.partial(jax.jit, static_argnames=("wa",))
@jax.named_scope("decide.placement")
def _place_slots(sd, resbw, Wc, Zc, ts, wa: int):
    """Greedy placements for the ACTIVE slots of an accepted schedule.

    ``ts``: (wa,) i32 slot indices with a nonzero split (padded by
    repeating the last index; padding lanes carry ``Wc = 0`` and are
    discarded by the caller).  ``Wc``/``Zc``: per-slot worker / PS-target
    counts (wa,) from the decided split.  Returns (y (wa, H'), z (wa, K'))
    int32 — the same cheapest-first fills the reference ``cost_t_ref``
    greedy produces.  Each slot's fill depends only on that slot's state
    column, so gathering the active subset is bit-identical to slicing
    the whole [arrival, finish] window and discarding the idle slots."""
    g, v, wcaps, scaps, U1, U2, L1, L2 = sd
    g_w = jnp.take(g, ts, axis=0)
    v_w = jnp.take(v, ts, axis=0)
    wres, sres = resbw[:R], resbw[R:2 * R]
    p = L1 * _price_pow(jnp.maximum(U1 / L1, 1.0 + 1e-9)[None, None, :],
                        g_w / jnp.maximum(wcaps, 1e-12)[None])
    q = L2 * _price_pow(jnp.maximum(U2 / L2, 1.0 + 1e-9)[None, None, :],
                        v_w / jnp.maximum(scaps, 1e-12)[None])
    w_order, w_scap, _, w_ccap, _ = _prefix_tables_jnp(
        p, wcaps[None] - g_w, wres)
    s_order, s_scap, _, s_ccap, _ = _prefix_tables_jnp(
        q, scaps[None] - v_w, sres)
    y = _greedy_place_jnp(w_order, w_scap, w_ccap, Wc)
    pool = s_ccap[:, -1]
    deploy = jnp.minimum(jnp.minimum(Zc, Wc), pool)
    z = _greedy_place_jnp(s_order, s_scap, s_ccap, deploy)
    return y, z


# ---------------------------------------------------------------------------
# Legacy monolithic core — kept for the TPU/Pallas path (use_pallas=True)
# ---------------------------------------------------------------------------

def _decide_core(sd, jd, *, d1: int, use_pallas: bool):
    """One Alg. 2 decision, fully fused, whole horizon in one block.

    sd: state arrays (g (T,H,R), v (T,K,R), wcaps (H,R), scaps (K,R),
        U1 (R,), U2 (R,), L1 (), L2 ())
    jd: bundled job arrays (resbw (2R+2,) = [wres, sres, wbw, psbw],
        WZ (2, M) i32, u (T,), meta (3,) i32 = [a, nchunks, workload])
    d1: static — DP columns (padded D_total + 1).

    Returns (best_t i32 (-1 = reject), payoff, total_cost, d_left i32 —
    workload still unassigned after the backtrack, 0 for any sound accept —
    d_slots (T,) i32, y (T, H) i32, z (T, K) i32).  Its four stages run
    under the device scopes ``decide.rows``, ``decide.dp``,
    ``decide.backtrack`` and ``decide.placement``.
    """
    g, v, wcaps, scaps, U1, U2, L1, L2 = sd
    resbw, WZ, u, meta = jd
    wres, sres = resbw[:R], resbw[R:2 * R]
    wbw, psbw = resbw[2 * R], resbw[2 * R + 1]
    W, Z = WZ[0], WZ[1]
    a, nchunks, d_tot = meta[0], meta[1], meta[2]
    T = g.shape[0]
    M = W.shape[0]
    dt = g.dtype

    with jax.named_scope("decide.rows"):
        # dual prices p = L1 (U1/L1)^(g/c), q = L2 (U2/L2)^(v/c) (eq. 22, 25)
        p = L1 * _price_pow(jnp.maximum(U1 / L1, 1.0 + 1e-9)[None, None, :],
                            g / jnp.maximum(wcaps, 1e-12)[None])
        q = L2 * _price_pow(jnp.maximum(U2 / L2, 1.0 + 1e-9)[None, None, :],
                            v / jnp.maximum(scaps, 1e-12)[None])

        w_order, w_scap, w_scost, w_ccap, w_ccost = _prefix_tables_jnp(
            p, wcaps[None] - g, wres)
        s_order, s_scap, s_scost, s_ccap, s_ccost = _prefix_tables_jnp(
            q, scaps[None] - v, sres)

        # COST_t rows for all (t, d)
        Wt = jnp.broadcast_to(W.astype(dt)[None, :], (T, M))
        w_costs = _greedy_cost(w_ccap, w_ccost, w_scost, Wt)
        pool = s_ccap[:, -1:]                                    # (T, 1)
        deploy = jnp.minimum(jnp.minimum(Z, W).astype(dt)[None, :], pool)
        feas_n = (W <= nchunks)[None, :]
        feas_ps = deploy * psbw >= Wt * wbw - 1e-9
        z_costs = _greedy_cost(s_ccap, s_ccost, s_scost, deploy)
        rows = jnp.where(feas_n & feas_ps, w_costs + z_costs, jnp.inf)
        rows = rows.at[:, 0].set(0.0)
        # slots before arrival carry the DP unchanged: row = [0, inf, ...]
        ts = jnp.arange(T, dtype=jnp.int32)
        pre = (ts[:, None] < a) & (jnp.arange(M)[None, :] > 0)
        rows = jnp.where(pre, jnp.inf, rows)

    with jax.named_scope("decide.dp"):
        # banded min-plus DP over slots (cost only; splits recovered below)
        if use_pallas:
            cost_tab = minplus_sweep(rows, d1 - 1)[0].astype(dt)
        else:
            cost_tab = minplus_sweep_cost(rows, d1 - 1)

        # payoff argmax with the reference tie rule (> best + eps switches)
        costD = jnp.take(cost_tab, d_tot, axis=1)                # (T,)
        payoff_t = jnp.where(jnp.isfinite(costD) & (ts >= a), u - costD,
                             -jnp.inf)

        def _pick(carry, x):
            best, best_t = carry
            pt, t = x
            switch = pt > best + _PAY_EPS
            return (jnp.where(switch, pt, best),
                    jnp.where(switch, t, best_t)), None

        (best_payoff, best_t), _ = jax.lax.scan(
            _pick, (jnp.asarray(0.0, dt), jnp.int32(-1)), (payoff_t, ts))

    with jax.named_scope("decide.backtrack"):
        # backtrack from best_t down to arrival, recomputing each slot's
        # split as argmin_j rows[t, j] + cost_{t-1}[d_rem - j] over the
        # stored table — the same first-minimum the carried DP argmin
        # would have produced
        init_row = jnp.full((d1,), jnp.inf, dt).at[0].set(0.0)
        prev_tab = jnp.concatenate([init_row[None, :], cost_tab[:-1]],
                                   axis=0)
        js = jnp.arange(M)

        def _back(d_rem, x):
            row, prev, t = x
            idx = d_rem - js
            vals = jnp.where(idx >= 0,
                             row + prev[jnp.clip(idx, 0, d1 - 1)], jnp.inf)
            d_here = jnp.where(t <= best_t,
                               jnp.argmin(vals).astype(jnp.int32), 0)
            return d_rem - d_here, d_here

        d_left, d_slots = jax.lax.scan(_back, d_tot, (rows, prev_tab, ts),
                                       reverse=True)

    with jax.named_scope("decide.placement"):
        # greedy placements for the chosen per-slot counts
        W_slots = jnp.take(W, d_slots)
        Z_slots = jnp.take(Z, d_slots)
        deploy_slots = jnp.minimum(
            jnp.minimum(Z_slots, W_slots).astype(dt), pool[:, 0])
        y = _greedy_place_jnp(w_order, w_scap, w_ccap, W_slots.astype(dt))
        z = _greedy_place_jnp(s_order, s_scap, s_ccap, deploy_slots)

    total_cost = jnp.take(costD, jnp.maximum(best_t, 0))
    return best_t, best_payoff, total_cost, d_left, d_slots, y, z


@functools.partial(jax.jit, static_argnames=("d1", "use_pallas"))
def _decide_one(sd, jd, d1: int, use_pallas: bool):
    return _decide_core(sd, jd, d1=d1, use_pallas=use_pallas)


# ---------------------------------------------------------------------------
# Row cache (incremental COST-row maintenance)
# ---------------------------------------------------------------------------

@dataclasses.dataclass
class RowCache:
    """Per-job COST-row cache across price-state versions.

    ``rows`` holds the (T_pad, m_pad) COST_t table the engine computed
    at ``version``; ``valid`` marks which ``TILE``-slot blocks of it are
    both *visited* (actually computed, not the identity placeholder) and
    *fresh* (no commit/release has moved prices inside them since).  The
    engine recomputes exactly the invalid tiles (``use_cache`` path of
    ``_decide_tiled``); :meth:`sync` invalidates against the price
    state's dirty-slot log (``PriceState.dirty_spans_since``).

    ``tables`` is the job's sorted-order/cumsum table set (6 arrays
    (T_pad, H|K) from ``_sorted_fill_lanes``) at ``tables_version``.  It
    is NOT maintained by :meth:`sync`: ``_decide_jobs`` patches exactly
    the slots ``PriceState.patch_spans(tables_version)`` reports dirty
    (``_sorted_fill``) right before each launch, so re-solves pay an
    O(dirty) sort bill instead of re-sorting the horizon."""
    rows: Optional[jax.Array]       # (T_pad, m_pad) device-resident
    valid: np.ndarray               # (n_tiles,) bool, host
    version: int
    m_pad: int
    d1: int
    # 6 x (T_pad, S) device-resident, or a lazy ``_LaneTabs`` view into
    # the stacked launch build (materialized via ``_tabs_get`` on reuse)
    tables: Optional[object] = None
    tables_version: int = -1

    @classmethod
    def empty(cls, state: PriceState, job: Job) -> Optional["RowCache"]:
        """A cache with no valid tiles (first decision fills it).  None
        for dcap-0 jobs (the engine rejects those without solving)."""
        key = _shape_bucket(job)
        if key is None:
            return None
        m_pad, d1 = key
        n_tiles = _pad_tiles(state.horizon) // TILE
        return cls(rows=None, valid=np.zeros(n_tiles, bool),
                   version=state.version, m_pad=m_pad, d1=d1)

    def invalidate_spans(self, spans) -> None:
        """Mark every tile overlapping a dirtied [t0, t1) slot span stale."""
        for t0, t1 in spans:
            k0 = max(int(t0) // TILE, 0)
            k1 = min((int(t1) - 1) // TILE + 1, len(self.valid))
            self.valid[k0:k1] = False

    def invalidate_all(self) -> None:
        self.valid[:] = False

    def sync(self, state: PriceState) -> "RowCache":
        """Invalidate whatever ``state`` has dirtied since ``version``.

        Uses the commit/release dirty-slot log; an unknown delta (window
        slide, log trimmed) invalidates everything.  Returns self."""
        if state.version != self.version:
            spans = state.dirty_spans_since(self.version)
            if spans is None:
                self.invalidate_all()
                if _obs.ENABLED:
                    _obs.inc("decide.row_cache_full_invalidations")
            else:
                self.invalidate_spans(spans)
            self.version = state.version
            if _obs.ENABLED:
                _obs.inc("decide.row_cache_syncs")
        return self


# ---------------------------------------------------------------------------
# Python wrappers: padding, bucketing, Schedule construction
# ---------------------------------------------------------------------------

def _state_arrays(state: PriceState, dtype):
    """Engine view of the price state: the device-resident allocation
    tensors plus static caps/params (``PriceState.device_state``).

    The first call per state uploads the full tensors once; afterwards
    ``commit``/``release`` maintain the residency with streamed slot-window
    adds, so a sequential simulation performs O(1) full uploads instead of
    re-uploading (T,H,R)+(T,K,R) after every accepted job."""
    return state.device_state(dtype)


def _price_tables(g, v, wcaps, scaps, U1, U2, L1, L2):
    """Job-independent dual price tables p (T', H, R), q (T', K, R) —
    the exact per-tile formula ``rows_for_tile`` used to evaluate inline
    (same elementwise ops, so slices of these are bit-identical)."""
    ratio1 = jnp.maximum(U1 / L1, 1.0 + 1e-9)
    ratio2 = jnp.maximum(U2 / L2, 1.0 + 1e-9)
    p = L1 * _price_pow(ratio1[None, None, :],
                        g / jnp.maximum(wcaps, 1e-12)[None])
    q = L2 * _price_pow(ratio2[None, None, :],
                        v / jnp.maximum(scaps, 1e-12)[None])
    return p, q


@functools.partial(jax.jit, static_argnames=("T_pad",))
@jax.named_scope("price.pad")
def _pad_state(g, v, wcaps, scaps, U1, U2, L1, L2, T_pad: int):
    """Tile-pad the allocation tensors and precompute everything about
    the state the decide launch re-derived per tile: the live-floor
    minimum worker price ``pmin`` (module docstring: every deployed
    worker in slot s costs >= sum_r wres_r * min_h p[s,h,r]; with
    ratio >= 1, min_h ratio^(g/c) == ratio^(min_h g/c), so the floor
    needs only (T_pad, R) pows) and the full job-independent price
    tables ``p``/``q`` — the exp/log transcendentals that used to
    dominate the row-build stage now run once per state version instead
    of once per visited tile per decision."""
    T = g.shape[0]
    g = jnp.pad(g, ((0, T_pad - T), (0, 0), (0, 0)))
    v = jnp.pad(v, ((0, T_pad - T), (0, 0), (0, 0)))
    ratio1 = jnp.maximum(U1 / L1, 1.0 + 1e-9)
    umin = jnp.min(g / jnp.maximum(wcaps, 1e-12)[None], axis=1)
    pmin = L1 * _price_pow(ratio1[None, :], umin)
    p, q = _price_tables(g, v, wcaps, scaps, U1, U2, L1, L2)
    return g, v, pmin, p, q


@functools.partial(jax.jit, static_argnames=("span",))
@jax.named_scope("price.pad")
def _pad_patch(g_pad, v_pad, pmin, p_pad, q_pad, g, v, wcaps, scaps,
               U1, U2, L1, L2, t0, span: int):
    """Refresh one dirty slot span of the padded-state cache in place:
    re-slice ``g``/``v`` and recompute the ``pmin`` floor and price-table
    rows with the exact ``_pad_state`` formulas, so the patched tensors
    are bit-identical to a from-scratch pad at the new state version."""
    zero = jnp.zeros_like(t0)
    g_s = jax.lax.dynamic_slice(g, (t0, zero, zero), (span,) + g.shape[1:])
    v_s = jax.lax.dynamic_slice(v, (t0, zero, zero), (span,) + v.shape[1:])
    ratio1 = jnp.maximum(U1 / L1, 1.0 + 1e-9)
    umin = jnp.min(g_s / jnp.maximum(wcaps, 1e-12)[None], axis=1)
    pmin_s = L1 * _price_pow(ratio1[None, :], umin)
    p_s, q_s = _price_tables(g_s, v_s, wcaps, scaps, U1, U2, L1, L2)
    g_pad = jax.lax.dynamic_update_slice(g_pad, g_s, (t0, zero, zero))
    v_pad = jax.lax.dynamic_update_slice(v_pad, v_s, (t0, zero, zero))
    pmin = jax.lax.dynamic_update_slice(pmin, pmin_s, (t0, zero))
    p_pad = jax.lax.dynamic_update_slice(p_pad, p_s, (t0, zero, zero))
    q_pad = jax.lax.dynamic_update_slice(q_pad, q_s, (t0, zero, zero))
    return g_pad, v_pad, pmin, p_pad, q_pad


_pad_cache: "weakref.WeakKeyDictionary" = weakref.WeakKeyDictionary()

# full-repad fallback threshold: more dirty spans than this and the
# span-by-span patching would launch more kernels than one full pad
_PATCH_MAX_SPANS = 8


def _padded_state(state: PriceState, dtype, T_pad: int):
    """``_state_arrays`` extended with the decide core's per-launch
    prologue — tile padding + the live-floor price ``pmin`` — computed
    once per (state version, dtype) and reused across every decision
    launch until the next commit/release, instead of inside each one.

    Between consecutive versions the cache is patched incrementally:
    ``PriceState.dirty_spans_since`` names the slots the commits touched
    and ``_pad_patch`` refreshes just those rows (the same maintenance
    contract ``RowCache`` uses).  Falls back to a full re-pad when the
    delta is unknowable or fragmented."""
    g, v, wcaps, scaps, U1, U2, L1, L2 = _state_arrays(state, dtype)
    key = (state.version, T_pad, jnp.dtype(dtype).name)
    hit = _pad_cache.get(state)
    if hit is not None and hit[0] == key:
        if _obs.ENABLED:
            _obs.inc("decide.pad_hit")
        return hit[1]
    T = g.shape[0]
    if hit is not None and hit[0][1:] == key[1:]:
        spans = state.dirty_spans_since(hit[0][0])
        if spans is not None and len(spans) <= _PATCH_MAX_SPANS:
            g_pad, v_pad, pmin = hit[1][0], hit[1][1], hit[1][8]
            p_pad, q_pad = hit[1][9], hit[1][10]
            for s0, s1 in spans:
                span = _bucket(max(s1 - s0, 1), floor=8, step=64)
                if span > T:
                    break
                start = min(max(int(s0), 0), T - span)
                with _obs.dispatch("_pad_patch"):
                    g_pad, v_pad, pmin, p_pad, q_pad = _pad_patch(
                        g_pad, v_pad, pmin, p_pad, q_pad, g, v, wcaps,
                        scaps, U1, U2, L1, L2, jnp.int32(start), span)
            else:
                hit = (key, (g_pad, v_pad, wcaps, scaps, U1, U2, L1, L2,
                             pmin, p_pad, q_pad))
                _pad_cache[state] = hit
                if _obs.ENABLED:
                    _obs.inc("decide.pad_patch")
                return hit[1]
    if _obs.ENABLED:
        _obs.inc("decide.pad_full")
    with _obs.dispatch("_pad_state"):
        g_pad, v_pad, pmin, p_pad, q_pad = _pad_state(
            g, v, wcaps, scaps, U1, U2, L1, L2, T_pad=T_pad)
    hit = (key, (g_pad, v_pad, wcaps, scaps, U1, U2, L1, L2, pmin,
                 p_pad, q_pad))
    _pad_cache[state] = hit
    return hit[1]


def _pad_tiles(T: int) -> int:
    return ((T + TILE - 1) // TILE) * TILE


def _utility_curve(job: Job, T: int, T_pad: int) -> np.ndarray:
    u = np.zeros(T_pad)
    a = job.arrival
    u[a:T] = [job.utility(t - a) for t in range(a, T)]
    return u


def _cost_lower_bound(job: Job, state: PriceState, W: np.ndarray) -> float:
    """Price-free per-chunk-pass base of the cost lower bound:
    min_d W(d)/d.

    Any split's worker-slots for d chunk-passes in one slot is
    >= d * min_d W(d)/d, so ANY schedule's cost is >= this base times a
    workload-weighted sum of live per-slot price floors — the device
    side of ``_decide_tiled_core`` multiplies in a greedy spread over
    the cheapest feasible slots (each capped at dcap chunk-passes),
    which is >= the old single-cheapest-slot floor and reduces to it
    when one slot can hold the whole workload.  Scaled by ``_LB_MARGIN``
    so engine float64 rounding stays above the bound."""
    if len(W) < 2:
        return 0.0
    per_unit = float(np.min(W[1:] / np.arange(1, len(W), dtype=np.float64)))
    return _LB_MARGIN * per_unit


def _job_arrays_tiled(job: Job, state: PriceState, T: int, T_pad: int,
                      m_pad: int, dtype):
    """Lane arrays for the tiled core.  Padded d entries get a sentinel
    worker count larger than any N so they are infeasible."""
    from .subroutine import workload_tables
    dcap = min(job.max_chunks_per_slot, job.workload)
    W, Z = workload_tables(job, dcap)
    WZ = np.zeros((2, m_pad), np.int32)
    WZ[0] = np.int32(1) << 30
    WZ[0, :dcap + 1] = W
    WZ[1, :dcap + 1] = Z
    u = _utility_curve(job, T, T_pad)
    usmax = np.maximum.accumulate(u[::-1])[::-1].copy()
    lb = _cost_lower_bound(job, state, W)
    resbw = np.concatenate([job.worker_res, job.ps_res,
                            [job.worker_bw, job.ps_bw]])
    meta = np.array([job.arrival, job.num_chunks, job.workload, dcap],
                    np.int32)
    return (resbw.astype(np.float64), WZ, u, usmax, meta, np.float64(lb)), (W, Z)


def _reject_lane(T: int, T_pad: int, m_pad: int):
    """A batch-padding dummy: infeasible everywhere (nchunks = -1), arrival
    at T so it never drags the start tile down, zero utility so it never
    keeps the early-exit loop alive."""
    resbw = np.zeros(2 * R + 2)
    resbw[-2:] = 1.0
    WZ = np.zeros((2, m_pad), np.int32)
    WZ[0] = np.int32(1) << 30
    meta = np.array([T, -1, 1, 1], np.int32)
    z = np.zeros(T_pad)
    return (resbw, WZ, z, z, meta, np.float64(0.0)), (WZ[0, :1], WZ[1, :1])


def _stack_lanes(lanes, dtype):
    cols = list(zip(*lanes))
    return (jnp.asarray(np.stack(cols[0]), dtype),      # resbw
            jnp.asarray(np.stack(cols[1])),             # WZ
            jnp.asarray(np.stack(cols[2]), dtype),      # u
            jnp.asarray(np.stack(cols[3]), dtype),      # usmax
            jnp.asarray(np.stack(cols[4])),             # meta
            jnp.asarray(np.stack(cols[5]), dtype))      # lb


def _job_arrays(job: Job, T: int, m_pad: int, dtype):
    """Legacy bundling for the monolithic (Pallas) core."""
    from .subroutine import workload_tables
    dcap = min(job.max_chunks_per_slot, job.workload)
    W, Z = workload_tables(job, dcap)
    WZ = np.zeros((2, m_pad), np.int32)
    WZ[0] = np.int32(1) << 30
    WZ[0, :dcap + 1] = W
    WZ[1, :dcap + 1] = Z
    a = job.arrival
    u = np.array([job.utility(t - a) if t >= a else 0.0 for t in range(T)])
    resbw = np.concatenate([job.worker_res, job.ps_res,
                            [job.worker_bw, job.ps_bw]])
    meta = np.array([a, job.num_chunks, job.workload], np.int32)
    return (jnp.asarray(resbw, dtype), jnp.asarray(WZ), jnp.asarray(u, dtype),
            jnp.asarray(meta))


def _x64_context(precision: str):
    """Engine precision policy.  "auto": float64 on CPU (exact agreement with
    the numpy paths), float32 on TPU.  An ambient jax_enable_x64 always wins.
    """
    import contextlib
    if precision == "x64" or (precision == "auto"
                              and jax.default_backend() == "cpu"):
        # already-enabled is a no-op: entering jax.enable_x64 flips the
        # thread-local config even when the value is unchanged, and every
        # flip knocks jit calls off the C fast path (~ms of python
        # dispatch per call).  The sim drivers hold one x64 context open
        # across the whole run so per-decision entries land here.
        if jax.config.jax_enable_x64:
            return contextlib.nullcontext()
        return jax.enable_x64(True)
    return contextlib.nullcontext()


@dataclasses.dataclass
class _Pending:
    """A decided-but-unplaced candidate from the tiled core.

    Holds the launch's device-resident row/cost tables (shared across the
    lanes of one launch) so the split backtrack — and the placement — run
    only if the candidate is actually accepted AND survives the commit
    pass.  Rejects never pay for either."""
    job: Job
    best_t: int
    payoff: float
    rows_full: jax.Array            # (B, T_pad, M) device, shared
    cost_full: jax.Array            # (B, T_pad, d1) device, shared
    lane: int                       # this job's lane in the launch
    t_start: int                    # first slot the decision loop visited
    W: np.ndarray                   # (dcap+1,) workload tables
    Z: np.ndarray
    cache: RowCache
    cost: float = float("nan")      # filled by _materialize for accepts


def _materialize(pend: _Pending, state: PriceState, sd, dtype
                 ) -> Optional[Schedule]:
    """Extract the split + placement for an accepted candidate (None =
    reject).

    Runs ``_backtrack`` over the stored lane tables and ``_place_slots``
    over just the deploying slots — MUST be called at the same price
    state the decision was made at."""
    job, best_t = pend.job, pend.best_t
    if best_t < 0:
        return None
    with _obs.span("decide.backtrack", jid=job.jid):
        with _obs.dispatch("_backtrack"):
            out = _backtrack(
                pend.rows_full[pend.lane], pend.cost_full[pend.lane],
                jnp.int32(best_t), jnp.int32(job.workload),
                jnp.int32(pend.t_start))
        with _obs.span("launch.fetch", program="_backtrack"):
            total_cost, d_left, d_slots = jax.device_get(out)
    pend.cost = float(total_cost)
    # mirrors _extract's backtrack assert: an accepted schedule must place
    # the whole workload (guards e.g. mixed-precision runs)
    assert int(d_left) == 0, \
        f"fused backtrack failed: {int(d_left)} chunk-passes unassigned"
    a = job.arrival
    # place only the slots that actually deploy (typically well under
    # half the [arrival, finish] window): each slot's greedy fill reads
    # its own state column only, so the gather changes nothing bit-wise
    ts_active = np.nonzero(d_slots[a:best_t + 1])[0] + a
    if len(ts_active) == 0:        # degenerate zero-workload accept
        utility = job.utility(best_t - a)
        return Schedule(jid=job.jid, workers={}, ps={}, finish=int(best_t),
                        cost=float(pend.cost),
                        payoff=utility - float(pend.cost), utility=utility)
    with _obs.span("decide.placement", jid=job.jid, slots=len(ts_active)):
        with _obs.span("decide.prep", jid=job.jid):
            wa = _bucket(len(ts_active), floor=8, step=32)
            ts = np.full(wa, ts_active[-1], np.int32)
            ts[:len(ts_active)] = ts_active
            d_act = np.zeros(wa, d_slots.dtype)
            d_act[:len(ts_active)] = d_slots[ts_active]
            Wc = pend.W[d_act].astype(np.float64)
            Zc = pend.Z[d_act].astype(np.float64)
            Wc[len(ts_active):] = 0.0
            Zc[len(ts_active):] = 0.0
            args = (jnp.asarray(np.concatenate(
                        [job.worker_res, job.ps_res,
                         [job.worker_bw, job.ps_bw]]), dtype),
                    jnp.asarray(Wc, dtype), jnp.asarray(Zc, dtype),
                    jnp.asarray(ts))
        with _obs.dispatch("_place_slots"):
            out = _place_slots(sd, *args, wa)
        with _obs.span("launch.fetch", program="_place_slots"):
            y, z = jax.device_get(out)
    H, K = state.cluster.H, state.cluster.K
    workers, ps = {}, {}
    for k, t in enumerate(ts_active):
        workers[int(t)] = y[k, :H].astype(np.int64)
        ps[int(t)] = z[k, :K].astype(np.int64)
    utility = job.utility(best_t - a)
    return Schedule(jid=job.jid, workers=workers, ps=ps, finish=int(best_t),
                    cost=float(pend.cost), payoff=utility - float(pend.cost),
                    utility=utility)


def _schedule_from_outputs(job: Job, state: PriceState, best_t: int,
                           cost: float, d_left: int, d_slots: np.ndarray,
                           y: np.ndarray, z: np.ndarray
                           ) -> Optional[Schedule]:
    """Schedule assembly for the legacy monolithic core's outputs."""
    if best_t < 0:
        return None
    assert d_left == 0, \
        f"fused backtrack failed: {d_left} chunk-passes unassigned"
    H, K = state.cluster.H, state.cluster.K
    workers, ps = {}, {}
    for t in range(job.arrival, best_t + 1):
        if d_slots[t] > 0:
            workers[t] = y[t, :H].astype(np.int64)
            ps[t] = z[t, :K].astype(np.int64)
    utility = job.utility(best_t - job.arrival)
    return Schedule(jid=job.jid, workers=workers, ps=ps, finish=int(best_t),
                    cost=float(cost), payoff=utility - float(cost),
                    utility=utility)


@functools.lru_cache(maxsize=32)
def _empty_cache(b_pad: int, T_pad: int, n_tiles: int, m_pad: int,
                 dtype_name: str):
    """Device-resident all-invalid row cache, one per launch shape: lets
    the cache-less decision path run the ``use_cache=True`` compiled
    variant without uploading a fresh buffer per launch."""
    rows0 = np.zeros((b_pad, T_pad, m_pad))
    rows0[:, :, 1:] = np.inf
    return (jnp.asarray(rows0, jnp.dtype(dtype_name)),
            jnp.zeros((b_pad, n_tiles), bool))


class _LaneTabs:
    """Deferred per-lane view into a stacked table set.

    A fresh ``_sorted_fill_lanes`` launch returns six ``(B, T_pad, S)``
    arrays; slicing every lane's 6-tuple out of them eagerly costs six
    device ``__getitem__`` dispatches per lane, and in the streaming
    engine nearly every launch is fresh while the slices are consumed
    only if that job is later re-solved.  This holds (stack, lane) and
    materializes the 6-tuple on first :meth:`get`."""

    __slots__ = ("stack", "lane", "_tabs")

    def __init__(self, stack: tuple, lane: int):
        self.stack = stack
        self.lane = lane
        self._tabs: Optional[tuple] = None

    def get(self) -> tuple:
        if self._tabs is None:
            bi = self.lane
            self._tabs = tuple(t[bi] for t in self.stack)
            self.stack = None
        return self._tabs


def _tabs_get(tabs) -> tuple:
    """Materialize a RowCache ``tables`` entry (concrete or _LaneTabs)."""
    return tabs.get() if isinstance(tabs, _LaneTabs) else tabs


def _lane_tables(chunk, caches, state, psd, lanes, b_pad, T, dtype):
    """Sorted-order/cumsum tables for every lane of one launch.

    Serves each lane from its RowCache when fresh, patches it through
    ``_sorted_fill`` when ``PriceState.patch_spans`` can name the dirty
    slots (O(dirty) sort cost on the re-solve path), and rebuilds from
    the cached price tables otherwise (one fused ``_sorted_fill_lanes``
    launch).  Tables only exist at all below the ``_table_max``
    footprint gate — above it the launch keeps the inline per-tile path
    and this returns dummies.  Returns (tabs — 6 launch operands,
    lane_tabs — per-lane entries (6-tuple, ``_LaneTabs``, or None) for
    cache write-back, use_tabs — whether the launch slices ``tabs``)."""
    g_pad, v_pad, wcaps, scaps = psd[0], psd[1], psd[2], psd[3]
    p_pad, q_pad = psd[9], psd[10]
    T_pad = g_pad.shape[0]
    if T_pad * max(g_pad.shape[1], v_pad.shape[1]) > _table_max():
        return _dummy_tabs(jnp.dtype(dtype).name), [None] * b_pad, False
    lane_tabs: List[Optional[object]] = [None] * b_pad
    for bi, (i, _) in enumerate(chunk):
        cache = caches.get(i) if caches else None
        if cache is None or cache.tables is None:
            continue
        if cache.tables_version == state.version:
            lane_tabs[bi] = cache.tables
            continue
        spans = state.patch_spans(cache.tables_version,
                                  limit=_PATCH_MAX_SPANS)
        if spans is None:
            continue
        tabs_l = _tabs_get(cache.tables)
        resbw = jnp.asarray(lanes[bi][0], dtype)
        for s0, s1 in spans:
            span = _bucket(max(s1 - s0, 1), floor=8, step=64)
            if span > T:
                tabs_l = None
                break
            start = min(max(int(s0), 0), T - span)
            with _obs.dispatch("_sorted_fill"):
                tabs_l = _sorted_fill(tabs_l, p_pad, q_pad, g_pad, v_pad,
                                      wcaps, scaps, resbw,
                                      jnp.int32(start), span)
        lane_tabs[bi] = tabs_l
    if all(t is None for t in lane_tabs):
        resbw_all = jnp.asarray(np.stack([la[0] for la in lanes]), dtype)
        with _obs.dispatch("_sorted_fill_lanes"):
            full = _sorted_fill_lanes(p_pad, q_pad, g_pad, v_pad, wcaps,
                                      scaps, resbw_all)
        return full, [_LaneTabs(full, bi) for bi in range(b_pad)], True
    for bi in range(b_pad):
        if lane_tabs[bi] is None:
            resbw = jnp.asarray(lanes[bi][0], dtype)
            with _obs.dispatch("_sorted_fill_lanes"):
                one = _sorted_fill_lanes(p_pad, q_pad, g_pad, v_pad, wcaps,
                                         scaps, resbw[None])
            lane_tabs[bi] = _LaneTabs(one, 0)
    if b_pad == 1:
        lt = lane_tabs[0]
        if isinstance(lt, _LaneTabs) and lt.stack is not None \
                and lt.lane == 0 and lt.stack[0].shape[0] == 1:
            tabs = lt.stack       # reuse the stacked build directly
        else:
            tabs = tuple(t[None] for t in _tabs_get(lt))
    else:
        mats = [_tabs_get(lt) for lt in lane_tabs]
        tabs = tuple(jnp.stack([m[k] for m in mats]) for k in range(6))
    return tabs, lane_tabs, True


def _rows_lookup_counter(x: jax.Array) -> str:
    """The obs counter of the COST-row tiles a launch built: that of the
    form ``_GREEDY_COST_FORMS`` gives the platform of ``x``'s device."""
    platform = next(iter(x.devices())).platform
    forms = _GREEDY_COST_FORMS
    return forms.get(platform, forms["default"])[1]


def _decide_jobs(jobs: Sequence[Tuple[int, Job]], state: PriceState, dtype,
                 m_pad: int, d1: int,
                 caches: Optional[dict] = None) -> List[_Pending]:
    """Run the tiled core over one shape-bucket group (<= _MAX_LANES jobs
    per launch).  ``caches``: optional {index: RowCache} serving lanes."""
    T = state.horizon
    T_pad = _pad_tiles(T)
    n_tiles = T_pad // TILE
    with _obs.span("decide.prep"):
        sd = _padded_state(state, dtype, T_pad)
    out: List[_Pending] = []
    for c0 in range(0, len(jobs), _MAX_LANES):
        chunk = jobs[c0:c0 + _MAX_LANES]
        b_pad = _bucket(len(chunk), floor=1, step=_MAX_LANES)
        with _obs.span("decide.prep", lanes=len(chunk)):
            lanes, tables = [], []
            for _, j in chunk:
                la, wz = _job_arrays_tiled(j, state, T, T_pad, m_pad, dtype)
                lanes.append(la)
                tables.append(wz)
            for _ in range(b_pad - len(chunk)):
                la, wz = _reject_lane(T, T_pad, m_pad)
                lanes.append(la)
                tables.append(wz)
            jd = _stack_lanes(lanes, dtype)
            # the no-cache case runs the SAME compiled variant with an
            # all-invalid (device-cached) empty cache: every distinct
            # (shape, use_cache) pair is a separate multi-second XLA
            # compilation, and the cond-per-tile overhead of the cached
            # variant is microseconds
            use_cache = caches is not None and any(
                caches.get(i) is not None for i, _ in chunk)
            if use_cache:
                rows0 = np.zeros((b_pad, T_pad, m_pad))
                rows0[:, :, 1:] = np.inf
                valid0 = np.zeros((b_pad, n_tiles), bool)
                rows_list = [None] * b_pad
                for bi, (i, _) in enumerate(chunk):
                    cache = caches.get(i)
                    if cache is not None and cache.rows is not None:
                        rows_list[bi] = cache.rows
                        valid0[bi] = cache.valid
                base = jnp.asarray(rows0, dtype)
                stackable = [rows_list[bi] if rows_list[bi] is not None
                             else base[bi] for bi in range(b_pad)]
                rows_init = jnp.stack(stackable)
                valid_tiles = jnp.asarray(valid0)
                if _obs.ENABLED:
                    _obs.inc("decide.cache_tiles_valid",
                             int(valid0[:len(chunk)].sum()))
                    _obs.inc("decide.cache_tiles_total",
                             len(chunk) * n_tiles)
            else:
                # cache-less launches stay out of the cache_tiles_*
                # counters: the tracked hit rate measures how much of a
                # RE-SOLVE the row cache saved, not how often the cache
                # path ran at all
                rows_init, valid_tiles = _empty_cache(
                    b_pad, T_pad, n_tiles, m_pad, jnp.dtype(dtype).name)
            tabs, lane_tabs, use_tabs = _lane_tables(chunk, caches, state,
                                                     sd, lanes, b_pad, T,
                                                     dtype)
        mono = 0
        if b_pad == 1 and m_pad <= _mono_band():
            mono = 2 if _mono_dnc() else 1
        with _obs.span("decide.dp_sweep", lanes=len(chunk), T_pad=T_pad,
                       m_pad=m_pad) as dp_span:
            with _obs.dispatch("_decide_tiled"):
                best_t, payoff, rows_buf, cost_buf, k0, k_end, paths = \
                    _decide_tiled(sd, jd, tabs, rows_init, valid_tiles,
                                  T=T, d1=d1, use_cache=True, mono=mono,
                                  use_tabs=use_tabs)
            with _obs.span("launch.fetch", program="_decide_tiled"):
                best_t, payoff, k0, k_end, pth = jax.device_get(
                    (best_t, payoff, k0, k_end, paths))
            k0, k_end = int(k0), int(k_end)
            dp_span.set(tiles_visited=k_end - k0, n_tiles=n_tiles)
        if _obs.ENABLED:
            _obs.inc("decide.tiles_visited", k_end - k0)
            _obs.inc("decide.tiles_horizon", n_tiles)
            # a visited tile's rows are served from the row cache only
            # where every lane holds it (``_decide_tiled_core``)
            served = (int(valid0[:, k0:k_end].all(axis=0).sum())
                      if use_cache else 0)
            _obs.inc(_rows_lookup_counter(rows_buf), k_end - k0 - served)
            _obs.observe("decide.early_exit_frac",
                         (k_end - k0) / max(n_tiles, 1))
            # tiles per min-plus branch (bit-identical paths): how often
            # the monotone fast paths fired against the chain fallback
            _obs.inc("decide.path_dnc", int(pth[0]))
            _obs.inc("decide.path_plateau", int(pth[1]))
            _obs.inc("decide.path_chain", int(pth[2]))
        # each lane's row cache: the inputs of its later re-solves
        with _obs.span("decide.prep", lanes=len(chunk)):
            for bi, (i, job) in enumerate(chunk):
                valid = np.zeros(n_tiles, bool)
                if use_cache and caches.get(i) is not None:
                    valid |= caches[i].valid
                valid[k0:k_end] = True
                cache = RowCache(rows=rows_buf[bi], valid=valid,
                                 version=state.version, m_pad=m_pad, d1=d1,
                                 tables=lane_tabs[bi],
                                 tables_version=(state.version
                                                 if lane_tabs[bi] is not None
                                                 else -1))
                out.append(_Pending(
                    job=job, best_t=int(best_t[bi]),
                    payoff=float(payoff[bi]), rows_full=rows_buf,
                    cost_full=cost_buf, lane=bi, t_start=k0 * TILE,
                    W=tables[bi][0], Z=tables[bi][1], cache=cache))
    return out


def _pow2_bucket(n: int, floor: int) -> int:
    b = floor
    while b < n:
        b *= 2
    return b


def _band_bucket(n: int) -> int:
    """Band-width (m_pad) compile bucket: 64, 128, then multiples of 128.

    The DP slot scan is O(m_pad) per column, so the old power-of-two
    buckets made a dcap-296 job sweep a 512-wide band — 1.7x the work —
    where 384 suffices.  Padded columns carry the infeasible sentinel
    (W = 2^30 -> +inf rows), so narrowing the pad only removes all-inf
    min-plus candidates and DP values are bit-identical across buckets.
    128-steps above 128 keep the bucket count (and XLA compile count) as
    coarse as the pow2 scheme at the shapes the benchmarks see."""
    if n <= 64:
        return 64
    if n <= 128:
        return 128
    return ((n + 127) // 128) * 128


def _shape_bucket(job: Job) -> Optional[Tuple[int, int]]:
    """Padded (m_pad, d1) compile bucket for a job's DP tables.

    Deliberately coarse — band buckets with high floors — because every
    distinct (m_pad, d1, lanes) triple is a separate XLA compilation of
    the decision loop, and compile time dominates wall clock at scale.
    The d1 floor covers the auto-quantized workload range (engine quantum
    targets <= 1200 chunk-passes) so scale runs see a SINGLE d1."""
    dcap = min(job.max_chunks_per_slot, job.workload)
    if dcap == 0:
        return None
    return (_band_bucket(dcap + 1), _pow2_bucket(job.workload + 1, 1280))


def best_schedule_fused(job: Job, state: PriceState, *,
                        use_pallas: Optional[bool] = None,
                        precision: str = "auto",
                        row_cache: Optional[RowCache] = None
                        ) -> Optional[Schedule]:
    """Alg. 2 for one job through the fused jit engine.

    The default path is the tiled early-exit core; ``row_cache`` (from a
    previous decision for the SAME job, ``sync``-ed against the state)
    lets it recompute only dirtied tiles.  ``use_pallas=True`` routes
    through the legacy monolithic core with the Pallas sweep kernel (the
    TPU path)."""
    key = _shape_bucket(job)
    if key is None:
        return None
    m_pad, d1 = key
    if use_pallas is None:
        use_pallas = jax.default_backend() == "tpu"
    T = state.horizon      # window-local lookahead (== cluster.T episodic)
    with _x64_context(precision):
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        if use_pallas:
            with _obs.span("decide.prep", jid=job.jid):
                sd = _state_arrays(state, dtype)
                jd = _job_arrays(job, T, m_pad, dtype)
            with _obs.dispatch("_decide_one"):
                out = _decide_one(sd, jd, d1=d1, use_pallas=True)
            with _obs.span("launch.fetch", program="_decide_one"):
                best_t, _, cost, d_left, d_slots, y, z = jax.device_get(out)
            if _obs.ENABLED:
                _obs.inc(_rows_lookup_counter(out[0]), _pad_tiles(T) // TILE)
            return _schedule_from_outputs(
                job, state, int(best_t), float(cost), int(d_left),
                d_slots, y, z)
        caches = {0: row_cache} if row_cache is not None else None
        pend = _decide_jobs([(0, job)], state, dtype, m_pad, d1,
                            caches=caches)[0]
        if row_cache is not None:
            row_cache.rows = pend.cache.rows
            row_cache.valid = pend.cache.valid
            row_cache.version = pend.cache.version
            row_cache.tables = pend.cache.tables
            row_cache.tables_version = pend.cache.tables_version
        with _obs.span("decide.prep", jid=job.jid):
            sd = _state_arrays(state, dtype)
        return _materialize(pend, state, sd, dtype)


def decide_burst(jobs: Sequence[Job], state: PriceState, *,
                 precision: str = "auto",
                 timings: Optional[List[float]] = None) -> List[_Pending]:
    """Speculative batched Alg. 2: the whole burst decided at the CURRENT
    prices, one tiled launch per shape bucket (jobs are grouped by
    (dcap, workload) bucket so a small job is never padded up to the
    burst's largest DP table).  Returns per-job ``_Pending`` candidates —
    decision + split + row cache, placement deferred to
    ``_materialize`` — in input order (None for dcap-0 jobs).  Commit
    order / price updates are the caller's job (``OASiS.on_arrivals``
    re-solves any job whose prices moved).

    ``timings``, when given, is filled in place with each job's share of
    its own shape group's wall time."""
    out: List[Optional[_Pending]] = [None] * len(jobs)
    if timings is not None:
        timings[:] = [0.0] * len(jobs)
    groups = {}
    for i, j in enumerate(jobs):
        key = _shape_bucket(j)
        if key is None:
            continue
        groups.setdefault(key, []).append((i, j))
    if not groups:
        return out
    with _x64_context(precision):
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        for (m_pad, d1), live in groups.items():
            t0 = time.perf_counter()
            pends = _decide_jobs(live, state, dtype, m_pad, d1)
            for (i, _), pend in zip(live, pends):
                out[i] = pend
            if timings is not None:
                share = (time.perf_counter() - t0) / len(live)
                for i, _ in live:
                    timings[i] = share
    return out


def best_schedule_fused_batch(jobs: Sequence[Job], state: PriceState, *,
                              precision: str = "auto",
                              timings: Optional[List[float]] = None
                              ) -> List[Optional[Schedule]]:
    """Speculative batched Alg. 2 with placements materialized for every
    accepted candidate (all at the CURRENT prices — the caller must not
    commit between the call and using the results)."""
    pends = decide_burst(jobs, state, precision=precision, timings=timings)
    out: List[Optional[Schedule]] = [None] * len(jobs)
    with _x64_context(precision):
        dtype = jnp.float64 if jax.config.jax_enable_x64 else jnp.float32
        with _obs.span("decide.prep"):
            sd = _state_arrays(state, dtype)
        for i, pend in enumerate(pends):
            if pend is not None:
                out[i] = _materialize(pend, state, sd, dtype)
    return out
