"""Pallas TPU kernel for one tile of the tiled decision core's slot scan.

``core/schedule_jax._decide_tiled_core`` walks the horizon in ``TILE``-slot
tiles.  Inside a tile every slot takes the banded min-plus step of
``tiled.minplus_chain_step`` for a batch of lanes, then the payoff pick
with the reference tie rule.  This kernel runs a whole tile in one call:

    new[b, d] = min_j rows[b, t, j] + prev[b, d - j]       (each slot t)
    pay[b]    = u[b, t] - new[b, d_tot[b]]   where a[b] <= t0 + t < T
    switch    = pay > best + eps             (best, best_t carried)

Layout.  The lanes sit on the 8 sublanes; padding lanes are all +inf and
stay +inf.  The carry ``prev`` is held as ``d1p + mp`` lanes whose tail
``[d1p, d1p + mp)`` is +inf, so a lane rotation by ``j`` (``pltpu.roll``)
yields the tap-``j`` window ``prev[d - j]`` with the wrapped-around lanes
reading +inf, as in ``kernel.py``.  A tap's row value is a one-lane slice
of the row, rotated 8 lanes on after every 8 taps.  The columns go to the
output as they are made; the payoff pick reads them back after the last
slot, off the carry's path.

Every candidate is the chain's own float32 add ``row[j] + prev[d - j]``
over the same taps, and a min is exact and order-free, so each DP cell
equals ``minplus_chain_step``'s bit for bit.  There is no live/dead test:
the engine gives a dead slot the identity row ``[0, inf, ...]``, whose
step returns the carry bit for bit, and the payoff test masks dead slots
per lane as the scan does.

The kernel's name carries no ``minplus``: the benchmark finds the sweep
kernel of ``kernel.py`` by that word and costs it with the sweep's byte
formula.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

_SUB = 8        # float32 sublanes per vreg: one lane of the batch each
_LANE = 128
_TAPS = 8       # taps per step of the tap loop (the band is a multiple of 64)
_PICKS = 8      # slots per step of the payoff loop


def _pad_lanes(n: int) -> int:
    return ((n + _LANE - 1) // _LANE) * _LANE


def _dp_tile_kernel(rows_ref, u_ref, prev_ref, best_ref, bt_ref, ints_ref,
                    cols_ref, best_out, bt_out, *, m: int, n_slots: int,
                    eps: float):
    """rows (S, 8, mp); u (8, 128), +inf beyond S; prev (8, d1p); best
    (8, 1) f32; bt (8, 1) i32; ints (4, 8, 1) i32 = [a - t0, T - t0,
    d_tot, t0]; cols (S, 8, d1p)."""
    d1p = cols_ref.shape[2]
    mp = rows_ref.shape[2]
    inf = jnp.float32(jnp.inf)
    tail = jnp.full((_SUB, mp), inf, jnp.float32)

    def slot(t, x):
        """Column t from the carry ``x`` (8, d1p + mp), +inf from d1p on.
        Each step of the tap loop takes 8 taps from one rotation of the
        carry: their windows are rotations of it by 0..7, independent of
        one another, and their candidates meet in a min tree."""
        def taps(blk, c):
            base, acc, row = c          # base[b, d] = prev[b, d - 8 blk]
            cands = [row[:, i:i + 1]    # row[b, 8 blk + i]
                     + (pltpu.roll(base, i, 1) if i else base)[:, :d1p]
                     for i in range(_TAPS)]
            while len(cands) > 1:
                cands = [jnp.minimum(cands[k], cands[k + 1])
                         for k in range(0, len(cands), 2)]
            return (pltpu.roll(base, _TAPS, 1), jnp.minimum(acc, cands[0]),
                    pltpu.roll(row, mp - _TAPS, 1))

        _, new, _ = jax.lax.fori_loop(
            0, m // _TAPS, taps,
            (x, jnp.full((_SUB, d1p), inf), rows_ref[t]))
        cols_ref[t] = new
        return jnp.concatenate([new, tail], axis=1)

    jax.lax.fori_loop(0, n_slots, slot,
                      jnp.concatenate([prev_ref[...], tail], axis=1))

    lo, hi, d_tot, t0 = ints_ref[0], ints_ref[1], ints_ref[2], ints_ref[3]
    lane_d = jax.lax.broadcasted_iota(jnp.int32, (_SUB, d1p), 1)
    lane_s = jax.lax.broadcasted_iota(jnp.int32, u_ref.shape, 1)
    u = u_ref[...]

    def picks(k, c):
        best, best_t = c
        for i in range(_PICKS):
            t = k * _PICKS + i
            cost_d = jnp.min(jnp.where(lane_d == d_tot, cols_ref[t], inf),
                             axis=1, keepdims=True)
            u_t = jnp.min(jnp.where(lane_s == t, u, inf), axis=1,
                          keepdims=True)
            live = (t >= lo) & (t < hi) & (jnp.abs(cost_d) < inf)
            pay = jnp.where(live, u_t - cost_d, -inf)
            switch = pay > best + eps
            best = jnp.where(switch, pay, best)
            best_t = jnp.where(switch, t0 + t, best_t)
        return best, best_t

    best, best_t = jax.lax.fori_loop(0, n_slots // _PICKS, picks,
                                     (best_ref[...], bt_ref[...]))
    best_out[...] = best
    bt_out[...] = best_t


@functools.partial(jax.jit, static_argnames=("T", "eps", "interpret"))
def dp_tile_pallas(rows, u, prev, best, best_t, a, d_tot, t0, *, T: int,
                   eps: float, interpret: bool = False):
    """One tile of the slot scan for B <= 8 lanes, in one kernel call.

    rows (B, S, M) COST rows of the tile's S slots (dead slots: the
    identity row); u (B, S) utilities; prev (B, D1) DP carry entering the
    tile; best (B,), best_t (B,) i32 the payoff pick so far; a, d_tot
    (B,) i32 arrivals and workloads; t0 () i32 the tile's first slot; T
    the horizon.  Returns ``(cols (B, S, D1), best, best_t)``: the DP
    column after each slot (the last is the carry out) and the pick after
    the tile, in float32."""
    B, S, M = rows.shape
    assert B <= _SUB and S <= _LANE and S % _PICKS == 0 and M % _TAPS == 0
    d1 = prev.shape[1]
    mp, d1p = _pad_lanes(M), _pad_lanes(d1)
    f32, i32 = jnp.float32, jnp.int32
    inf = jnp.inf
    rows_p = jnp.full((S, _SUB, mp), inf, f32).at[:, :B, :M].set(
        jnp.swapaxes(rows, 0, 1).astype(f32))
    u_p = jnp.full((_SUB, _LANE), inf, f32).at[:B, :S].set(u.astype(f32))
    prev_p = jnp.full((_SUB, d1p), inf, f32).at[:B, :d1].set(
        prev.astype(f32))
    best_p = jnp.zeros((_SUB, 1), f32).at[:B, 0].set(best.astype(f32))
    bt_p = jnp.full((_SUB, 1), -1, i32).at[:B, 0].set(best_t.astype(i32))
    t0 = jnp.asarray(t0, i32)
    ints = jnp.stack([a.astype(i32) - t0, jnp.full((B,), T, i32) - t0,
                      d_tot.astype(i32), jnp.broadcast_to(t0, (B,))])
    # padding lanes: arrival past the tile, so no slot is live for them
    ints_p = jnp.zeros((4, _SUB, 1), i32).at[:, :B, 0].set(ints)
    ints_p = ints_p.at[0, B:, 0].set(S)
    vmem = 4 * (S * _SUB * (mp + d1p) + _SUB * (d1p + _LANE))
    cols, best_o, bt_o = pl.pallas_call(
        functools.partial(_dp_tile_kernel, m=M, n_slots=S, eps=eps),
        out_shape=[jax.ShapeDtypeStruct((S, _SUB, d1p), f32),
                   jax.ShapeDtypeStruct((_SUB, 1), f32),
                   jax.ShapeDtypeStruct((_SUB, 1), i32)],
        compiler_params=pltpu.CompilerParams(
            vmem_limit_bytes=2 * vmem + (8 << 20)),
        interpret=interpret,
    )(rows_p, u_p, prev_p, best_p, bt_p, ints_p)
    return (jnp.swapaxes(cols[:, :B, :d1], 0, 1), best_o[:B, 0],
            bt_o[:B, 0])
