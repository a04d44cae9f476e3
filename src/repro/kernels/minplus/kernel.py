"""Pallas TPU kernel for the banded min-plus (tropical) convolution at the
heart of the paper's DP subroutine (Alg. 2), swept over all T slots in one
launch:

    cost_t[d]  = min_{j in [0, DC]} rows[t, j] + cost_{t-1}[d - j]
    split_t[d] = first argmin_j (same)

This is the only super-linear term in OASiS (O(T N^2 E^2), Theorem 3) —
the paper's hot spot.  Min-plus is not a ring matmul, so the MXU cannot
be used; the kernel runs on the VPU.

Layout.  The grid walks the T slots in order ("arbitrary" semantics); the
carried row ``cost_{t-1}`` lives in a VMEM scratch of ``L = d1p + dc1p``
lanes whose tail ``[d1p, L)`` stays +inf, so a lane rotation by ``j``
(``pltpu.roll``) yields the tap-``j`` window ``prev[d - j]`` with the
wrapped-around lanes reading +inf — no dynamic lane slicing, which the
TPU compiler does not lower.  The 8 sublanes of a vreg hold 8 consecutive
taps side by side; each keeps its own running first minimum, and the
final sublane reduction keeps the smallest tap among the equal minima, so
the argmin is the same first minimum a sequential scan gives.  A tap's
row value is picked out of the lane-major row by a masked lane
reduction, not a dynamic lane index.
"""
from __future__ import annotations

import functools
from typing import Optional

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUB = 8        # float32 sublanes per vreg: taps evaluated side by side
_LANE = 128


def _pad_lanes(n: int) -> int:
    return ((n + _LANE - 1) // _LANE) * _LANE


def _minplus_sweep_kernel(init_ref, rows_ref, out_ref, arg_ref, carry_ref, *,
                          dc1p: int, d1p: int):
    """init: (1, L) first carry, +inf from lane D+1 on; rows block
    (1, dc1p), +inf beyond DC; out/arg blocks (1, d1p); carry scratch
    (1, L)."""
    t = pl.program_id(0)

    @pl.when(t == 0)
    def _init():
        carry_ref[...] = init_ref[...]

    L = d1p + dc1p
    sub = jax.lax.broadcasted_iota(jnp.int32, (_SUB, 1), 0)
    lane = jax.lax.broadcasted_iota(jnp.int32, (_SUB, dc1p), 1)
    row8 = jnp.broadcast_to(rows_ref[...], (_SUB, dc1p))
    x = jnp.broadcast_to(carry_ref[...], (_SUB, L))
    # sublane s starts at tap s: win[s, d] = prev[d - s]
    win = x
    for s in range(1, _SUB):
        win = jnp.where(sub == s, pltpu.roll(x, s, 1), win)

    def body(b, c):
        win, best, arg = c
        j = b * _SUB + sub                                      # (8, 1)
        r = jnp.min(jnp.where(lane == j, row8, jnp.inf), axis=1,
                    keepdims=True)                              # rows[t, j]
        cand = r + win[:, :d1p]
        take = cand < best
        return (pltpu.roll(win, _SUB, 1), jnp.where(take, cand, best),
                jnp.where(take, j, arg))

    _, best, arg = jax.lax.fori_loop(
        0, dc1p // _SUB, body,
        (win, jnp.full((_SUB, d1p), jnp.inf, jnp.float32),
         jnp.zeros((_SUB, d1p), jnp.int32)))
    m = jnp.min(best, axis=0, keepdims=True)
    out_ref[...] = m
    arg_ref[...] = jnp.min(jnp.where(best == m, arg, dc1p), axis=0,
                           keepdims=True)
    carry_ref[:, :d1p] = m                              # carry to slot t+1


@functools.partial(jax.jit, static_argnames=("d_total", "interpret"))
def minplus_sweep_pallas(rows: jax.Array, d_total: int,
                         init: Optional[jax.Array] = None, *,
                         interpret: bool = False):
    """rows: (T, DC+1) float32 (+inf infeasible).  Returns
    (cost (T, D+1) float32, split (T, D+1) int32) for the full DP sweep —
    one kernel launch for all T slots — from the carry ``init`` (D+1,),
    by default the DP identity [0, inf, ...]."""
    T, dc1 = rows.shape
    d1 = d_total + 1
    dc1p, d1p = _pad_lanes(dc1), _pad_lanes(d1)
    L = d1p + dc1p
    rowsp = jnp.full((T, 1, dc1p), jnp.inf, jnp.float32)
    rowsp = rowsp.at[:, 0, :dc1].set(rows.astype(jnp.float32))
    if init is None:
        init = jnp.full((d1,), jnp.inf, jnp.float32).at[0].set(0.0)
    initp = jnp.full((1, L), jnp.inf, jnp.float32)
    initp = initp.at[0, :d1].set(init.astype(jnp.float32))
    out, arg = pl.pallas_call(
        functools.partial(_minplus_sweep_kernel, dc1p=dc1p, d1p=d1p),
        grid=(T,),
        in_specs=[pl.BlockSpec((1, L), lambda t: (0, 0)),
                  pl.BlockSpec((None, 1, dc1p), lambda t: (t, 0, 0))],
        out_specs=[pl.BlockSpec((None, 1, d1p), lambda t: (t, 0, 0)),
                   pl.BlockSpec((None, 1, d1p), lambda t: (t, 0, 0))],
        out_shape=[jax.ShapeDtypeStruct((T, 1, d1p), jnp.float32),
                   jax.ShapeDtypeStruct((T, 1, d1p), jnp.int32)],
        scratch_shapes=[pltpu.VMEM((1, L), jnp.float32)],
        compiler_params=pltpu.CompilerParams(
            dimension_semantics=("arbitrary",)),
        interpret=interpret,
    )(initp, rowsp)
    return out[:, 0, :d1], arg[:, 0, :d1]

