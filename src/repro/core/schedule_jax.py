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
* each visited tile's COST rows are built inside the launch from slices
  of the padded price tables (``_padded_state``, cached per state
  version): a launch's only operands are that state and the lanes'
  job arrays.

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
the tiled core, whose slot scan runs on a TPU as one Pallas kernel call
per visited tile (``kernels.minplus.dp_tile``, ``_SLOT_SCAN_FORMS``).

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

from ..kernels.minplus.dp_tile import dp_tile_pallas
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
# pipeline differently per launch shape (lane count), so two
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
# decide lane-by-lane — still speculative — while parallel backends get
# real fusion.  Override with REPRO_BURST_LANES.
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
# (``_form_counter``).  A gather loop is what costs on the TPU;
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


def _slot_scan(rows_tile, u_tile, prev, best, best_t, paths, t0, a, d_tot,
               *, T: int, mono: int):
    """One tile's slot scan in XLA: a ``lax.scan`` of
    ``minplus_chain_step`` (or a monotone branch, chosen once for the
    tile) and the payoff pick, for every platform but the TPU.

    rows_tile (B, TILE, M), u_tile (B, TILE), prev (B, d1) the carry,
    best/best_t (B,) the pick so far, paths (3,) the tiles per branch,
    t0 the tile's first slot, a/d_tot (B,) i32.  Returns ``(prev, best,
    best_t, cols (B, TILE, d1), paths)``."""
    n, M = rows_tile.shape[1:]
    r_max = max(16, M // 4)
    ts_tile = t0 + jnp.arange(n, dtype=jnp.int32)
    a_min = jnp.min(a)

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
            costD = jnp.take_along_axis(new, d_tot[:, None], axis=1)[:, 0]
            pay = jnp.where(jnp.isfinite(costD) & (t >= a) & (t < T),
                            u_t - costD, -jnp.inf)
            switch = pay > best + _PAY_EPS
            return (new, jnp.where(switch, pay, best),
                    jnp.where(switch, t, best_t))

        def dead(_):
            # slots before every lane's arrival (or past the horizon)
            # have the identity row [0, inf, ...]: the chain step would
            # return ``prev`` bit-for-bit, so skip it at runtime — with
            # single-lane launches this skips the DP for the whole
            # pre-arrival prefix of the first tile
            return (prev, best, best_t)

        new, best, best_t = jax.lax.cond(
            (t >= a_min) & (t < T), live, dead, None)
        return (new, best, best_t), new

    (prev, best, best_t), cols = jax.lax.scan(
        slot, (prev, best, best_t),
        (jnp.swapaxes(rows_tile, 0, 1), u_tile.T, ts_tile))
    return prev, best, best_t, jnp.swapaxes(cols, 0, 1), paths


def _slot_scan_kernel(rows_tile, u_tile, prev, best, best_t, paths, t0, a,
                      d_tot, *, T: int, mono: int):
    """``_slot_scan`` as one Pallas kernel call per tile
    (``kernels.minplus.dp_tile``), the TPU's form: bit-identical DP
    columns and pick, no per-slot ``cond``, and no monotone branch
    (``mono`` is ignored and ``paths`` is returned unchanged)."""
    del mono
    dt = prev.dtype
    cols, best, best_t = dp_tile_pallas(rows_tile, u_tile, prev, best,
                                        best_t, a, d_tot, t0, T=T,
                                        eps=_PAY_EPS)
    cols = cols.astype(dt)
    return cols[:, -1], best.astype(dt), best_t, cols, paths


# The slot scan each platform lowers (the tile body's
# ``platform_dependent`` branches; platforms not named take "default"),
# each with the obs counter of the visited tiles it ran
# (``_form_counter``).  On the TPU the scan's per-slot ``cond``, slices
# and gathers cost far more than the VPU work of the min-plus step.
_SLOT_SCAN_FORMS = {
    "tpu": (_slot_scan_kernel, "decide.dp_kernel_tiles"),
    "default": (_slot_scan, "decide.dp_scan_tiles"),
}


def _decide_tiled_core(sd, jd, *, T: int, d1: int, mono: int):
    """Alg. 2 decisions for a lane batch, horizon-tiled with exact early
    exit (module docstring).

    sd: PADDED state arrays from ``_padded_state`` (g (T_pad,H,R),
        v (T_pad,K,R), wcaps (H,R), scaps (K,R), U1 (R,), U2 (R,),
        L1 (), L2 (), pmin (T_pad, R) — the per-slot minimum worker
        price for the live cost floor — and the dual price tables
        p (T_pad,H,R), q (T_pad,K,R), all precomputed per state version)
    jd: lane-batched job arrays —
        resbw (B, 2R+2) = [wres, sres, wbw, psbw],
        WZ (B, 2, M) i32, u (B, T_pad), usmax (B, T_pad) suffix-max of u,
        meta (B, 4) i32 = [a, nchunks, d_tot, dcap], lb (B,) — the
        price-free per-chunk-pass lower-bound base from
        ``_cost_lower_bound`` (a live greedy price floor over the
        cheapest feasible slots is multiplied in on device).
    T: static — the real (unpadded) horizon.
    d1: static — DP columns (padded D_total + 1).
    mono: static — monotone min-plus dispatch level: 0 = chain only,
        1 = staircase-plateau + chain, 2 = also the divide-and-conquer
        branch (``REPRO_MONOTONE_DNC``).  Levels > 0 require a single
        lane; the branch is chosen ONCE PER TILE (per-slot dispatch costs
        more than it saves) and every branch produces bit-identical DP
        values (see ``kernels.minplus.monotone``).  Only the scan form of
        ``_SLOT_SCAN_FORMS`` has the branches: the TPU's ignores ``mono``.

    Returns (best_t (B,) i32 (-1 = reject), payoff (B,), rows
    (B, T_pad, M) — the visited tiles' COST rows, identity rows
    elsewhere — cost (B, T_pad, d1) — the visited tiles' DP columns —
    k0, k_end i32: the visited tile range [k0, k_end), paths (3,) i32 —
    per-branch counts [dnc, plateau, chain] of the tiles the scan form
    ran, all 0 where the tile kernel ran them).

    Device scopes: each visited tile's COST-row build runs under
    ``decide.rows``; the rest of the tile loop — its exit test and live
    cost floor, the slot scan with its payoff pick, the buffer writes —
    under ``decide.dp``.  The slot scan is the lowering platform's form
    in ``_SLOT_SCAN_FORMS``: on a TPU one Pallas kernel call per visited
    tile.
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
    H = g.shape[1]
    K = v.shape[1]
    if mono:
        assert B == 1, "monotone dispatch is single-lane only"

    Wf = W.astype(dt)
    deploy_target = jnp.minimum(Z, W).astype(dt)                 # (B, M)
    feas_n = (W <= nchunks[:, None])[:, None, :]                 # (B, 1, M)
    ms = jnp.arange(M)

    def rows_for_tile(t0):
        """COST_t rows for slots [t0, t0+TILE), all lanes: (B, TILE, M).

        The tile's prefix tables are built from slices of the
        version-cached price tables (``_prefix_tables_b``), so sorts run
        only on visited tiles, which the early exit keeps far below
        T_pad."""
        zero = jnp.zeros_like(t0)
        nr = p_pad.shape[2]
        p_t = jax.lax.dynamic_slice(p_pad, (t0, zero, zero), (TILE, H, nr))
        q_t = jax.lax.dynamic_slice(q_pad, (t0, zero, zero), (TILE, K, nr))
        g_t = jax.lax.dynamic_slice(g, (t0, zero, zero), (TILE, H, nr))
        v_t = jax.lax.dynamic_slice(v, (t0, zero, zero), (TILE, K, nr))
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

    init_col = jnp.full((B, d1), jnp.inf, dt).at[:, 0].set(0.0)
    rows_buf0 = jnp.full((B, T_pad, M), jnp.inf, dt).at[:, :, 0].set(0.0)
    cost_buf0 = jnp.full((B, T_pad, d1), jnp.inf, dt)
    k0 = jnp.min(a).astype(jnp.int32) // TILE

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
            rows_tile = rows_for_tile(t0)
        u_tile = jax.lax.dynamic_slice(u, (zero, t0), (B, TILE))
        prev, best, best_t, cols, paths = jax.lax.platform_dependent(
            rows_tile, u_tile, prev, best, best_t, paths, t0, a, d_tot,
            **{p: functools.partial(fn, T=T, mono=mono)
               for p, (fn, _) in _SLOT_SCAN_FORMS.items()})
        cost_buf = jax.lax.dynamic_update_slice(cost_buf, cols,
                                                (zero, t0, zero))
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


@functools.partial(jax.jit, static_argnames=("T", "d1", "mono"))
def _decide_tiled(sd, jd, T: int, d1: int, mono: int):
    return _decide_tiled_core(sd, jd, T=T, d1=d1, mono=mono)


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
    and ``_pad_patch`` refreshes just those rows.  Falls back to a full
    re-pad when the delta is unknowable or fragmented."""
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


def _form_counter(forms: dict, x: jax.Array) -> str:
    """The obs counter of the form ``forms`` (``_GREEDY_COST_FORMS``,
    ``_SLOT_SCAN_FORMS``) gives the platform of ``x``'s device: the
    platform the launch that made ``x`` was lowered for."""
    platform = next(iter(x.devices())).platform
    return forms.get(platform, forms["default"])[1]


def _decide_jobs(jobs: Sequence[Tuple[int, Job]], state: PriceState, dtype,
                 m_pad: int, d1: int) -> List[_Pending]:
    """Run the tiled core over one shape-bucket group (<= _MAX_LANES jobs
    per launch)."""
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
        mono = 0
        if b_pad == 1 and m_pad <= _mono_band():
            mono = 2 if _mono_dnc() else 1
        with _obs.span("decide.dp_sweep", lanes=len(chunk), T_pad=T_pad,
                       m_pad=m_pad) as dp_span:
            with _obs.dispatch("_decide_tiled"):
                best_t, payoff, rows_buf, cost_buf, k0, k_end, paths = \
                    _decide_tiled(sd, jd, T=T, d1=d1, mono=mono)
            with _obs.span("launch.fetch", program="_decide_tiled"):
                best_t, payoff, k0, k_end, pth = jax.device_get(
                    (best_t, payoff, k0, k_end, paths))
            k0, k_end = int(k0), int(k_end)
            dp_span.set(tiles_visited=k_end - k0, n_tiles=n_tiles)
        if _obs.ENABLED:
            _obs.inc("decide.tiles_visited", k_end - k0)
            _obs.inc("decide.tiles_horizon", n_tiles)
            _obs.inc(_form_counter(_GREEDY_COST_FORMS, rows_buf), k_end - k0)
            _obs.inc(_form_counter(_SLOT_SCAN_FORMS, rows_buf), k_end - k0)
            _obs.observe("decide.early_exit_frac",
                         (k_end - k0) / max(n_tiles, 1))
            # tiles per min-plus branch of the scan form (bit-identical
            # paths): how often the monotone fast paths fired against the
            # chain fallback; 0 where the tile kernel ran
            _obs.inc("decide.path_dnc", int(pth[0]))
            _obs.inc("decide.path_plateau", int(pth[1]))
            _obs.inc("decide.path_chain", int(pth[2]))
        for bi, (_, job) in enumerate(chunk):
            out.append(_Pending(
                job=job, best_t=int(best_t[bi]), payoff=float(payoff[bi]),
                rows_full=rows_buf, cost_full=cost_buf, lane=bi,
                t_start=k0 * TILE, W=tables[bi][0], Z=tables[bi][1]))
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
                        precision: str = "auto") -> Optional[Schedule]:
    """Alg. 2 for one job through the fused jit engine.

    The default path is the tiled early-exit core; ``use_pallas=True``
    routes through the legacy monolithic core with the Pallas sweep
    kernel (the TPU path)."""
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
                _obs.inc(_form_counter(_GREEDY_COST_FORMS, out[0]),
                         _pad_tiles(T) // TILE)
            return _schedule_from_outputs(
                job, state, int(best_t), float(cost), int(d_left),
                d_slots, y, z)
        pend = _decide_jobs([(0, job)], state, dtype, m_pad, d1)[0]
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
    the decision and the launch's row/cost tables, split and placement
    deferred to ``_materialize`` — in input order (None for dcap-0 jobs).  Commit
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
