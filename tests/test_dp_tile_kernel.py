"""The tiled core's slot scan as one Pallas kernel per tile
(``kernels.minplus.dp_tile``), run by the Pallas interpreter, against the
XLA scan form (``schedule_jax._slot_scan``): bit-equal DP columns, carry,
``best`` and ``best_t`` in float32, as on the chip; then the burst engine
with the kernel swapped in for the scan."""
import functools

import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs as obslib
from repro.core import price_params_from_jobs
from repro.core import schedule_jax as sj
from repro.core.pricing import PriceState
from repro.kernels.minplus.dp_tile import dp_tile_pallas
from repro.kernels.minplus.tiled import TILE
from repro.sim import make_cluster, make_jobs


def _tile(lanes, m_pad, d1, t0, T, seed, arrival_span=40):
    """Engine-shaped inputs of one tile: staircase COST rows with some
    infeasible taps, the identity row on every lane's dead slots (before
    its arrival or past the horizon), a carry with a +inf tail."""
    rng = np.random.default_rng(seed)
    rows = np.cumsum(rng.uniform(0.5, 3.0, (lanes, TILE, m_pad)),
                     axis=2).astype(np.float32)
    rows[:, :, 0] = 0.0
    rows[rng.random(rows.shape) < 0.1] = np.inf
    a = rng.integers(t0, t0 + arrival_span, lanes).astype(np.int32)
    prev = np.cumsum(rng.uniform(0.0, 2.0, (lanes, d1)),
                     axis=1).astype(np.float32)
    prev[:, 0] = 0.0
    for b in range(lanes):
        prev[b, rng.integers(d1 // 4, d1):] = np.inf
    u = rng.uniform(0.0, 3.0 * d1, (lanes, TILE)).astype(np.float32)
    d_tot = rng.integers(1, d1 - 1, lanes).astype(np.int32)
    best = rng.uniform(0.0, 20.0, lanes).astype(np.float32)
    best_t = np.full(lanes, -1, np.int32)
    return dict(rows=rows, u=u, prev=prev, best=best, best_t=best_t, a=a,
                d_tot=d_tot, t0=t0, T=T)


def _identity_dead(c):
    ts = c["t0"] + np.arange(TILE)
    dead = (ts[None, :] < c["a"][:, None]) | (ts >= c["T"])[None, :]
    c["rows"][dead, 0] = 0.0
    c["rows"][dead, 1:] = np.inf
    return c


def _all_inf(c):
    """Lane 0: whole rows +inf (tap 0 too) on a run of slots; the last
    lane: rows +inf but for tap 0, the identity, on another run."""
    c = _identity_dead(c)
    c["rows"][0, 40:44, :] = np.inf
    c["rows"][-1, 20:30, 1:] = np.inf
    return c


def _tie(c):
    """Zero-cost rows, so the DP column reads 0 at d_tot = 1 from the
    first live slot on: lane 0's payoff stays within ``_PAY_EPS`` of the
    initial 0, lane 1's climbs by steps below and above ``_PAY_EPS``."""
    c["rows"][:] = np.inf
    c["rows"][:, :, :2] = 0.0
    c["prev"][:] = np.inf
    c["prev"][:, 0] = 0.0
    c["a"][:] = c["t0"]
    c["d_tot"][:] = 1
    c["best"][:] = 0.0
    c["u"][0] = 5e-13
    c["u"][1] = 3e-12
    c["u"][1, 5] = 3.5e-12          # within eps of 3e-12: no switch
    c["u"][1, 9] = 4.5e-12          # beyond eps: switch
    c["u"][1, 12] = 4.5e-12         # a tie: no switch
    return c


CASES = (
    # lanes 1, 2, 4, 8 x m_pad 64, 128, each lane with its own arrival
    # and the tile opening on a dead prefix before the first of them
    [pytest.param(b, m, 256, 64, 500, _identity_dead, id=f"lanes{b}-m{m}")
     for b in (1, 2, 4, 8) for m in (64, 128)]
    + [pytest.param(4, 64, 256, 448, 500, _identity_dead, id="crosses-T"),
       pytest.param(4, 128, 256, 64, 500, _all_inf, id="all-inf-rows"),
       pytest.param(2, 64, 256, 0, 500, _tie, id="payoff-tie"),
       pytest.param(2, 384, 512, 64, 500, _identity_dead, id="band-384"),
       pytest.param(8, 128, 1280, 128, 500, _identity_dead, id="d1-1280")])


@pytest.mark.parametrize("lanes,m_pad,d1,t0,T,shape", CASES)
def test_tile_kernel_equals_the_slot_scan(lanes, m_pad, d1, t0, T, shape):
    c = shape(_tile(lanes, m_pad, d1, t0, T, seed=lanes * 1000 + m_pad + t0))
    f32 = {k: jnp.asarray(c[k]) for k in
           ("rows", "u", "prev", "best", "best_t", "a", "d_tot")}
    t0_j = jnp.int32(t0)
    scan = jax.jit(functools.partial(sj._slot_scan, T=T, mono=0))
    carry, best, best_t, cols, paths = jax.device_get(scan(
        f32["rows"], f32["u"], f32["prev"], f32["best"], f32["best_t"],
        jnp.zeros(3, jnp.int32), t0_j, f32["a"], f32["d_tot"]))
    assert cols.dtype == np.float32
    k_cols, k_best, k_bt = jax.device_get(dp_tile_pallas(
        f32["rows"], f32["u"], f32["prev"], f32["best"], f32["best_t"],
        f32["a"], f32["d_tot"], t0_j, T=T, eps=sj._PAY_EPS,
        interpret=True))
    np.testing.assert_array_equal(k_cols, cols)
    np.testing.assert_array_equal(k_cols[:, -1], carry)
    np.testing.assert_array_equal(k_best, best)
    np.testing.assert_array_equal(k_bt, best_t)
    assert paths.tolist() == [0, 0, 1]
    if shape is _tie:
        assert best_t.tolist() == [-1, t0 + 9]
    else:
        assert np.isfinite(cols).any() and (best_t >= 0).any()


def _burst(jobs, cluster):
    """``decide_burst`` in float32 at 8 lanes a launch, with its obs
    counters."""
    state = PriceState(cluster, price_params_from_jobs(jobs, cluster))
    ob = obslib.Obs()
    with jax.enable_x64(False), obslib.activate(ob):
        pends = sj.decide_burst(jobs, state, precision="f32")
    return pends, ob.metrics.snapshot()["counters"]


def test_engine_with_the_tile_kernel_equals_the_scan(monkeypatch):
    """The burst engine with the TPU's slot scan swapped in for the CPU's
    decides every lane as the XLA scan does, with the same DP columns for
    the backtrack.  Each form's visited tiles count under its own counter
    of ``_SLOT_SCAN_FORMS``; ``decide.path_*`` count only the scan's."""
    cluster = make_cluster(T=100, H=6, K=6)
    jobs = make_jobs(6, T=100, seed=2, small=True)
    monkeypatch.setattr(sj, "_MAX_LANES", 8)
    jax.clear_caches()
    scan, c_scan = _burst(jobs, cluster)
    forms = sj._SLOT_SCAN_FORMS
    monkeypatch.setitem(forms, "default", forms["tpu"])
    monkeypatch.setattr(sj, "dp_tile_pallas",
                        functools.partial(dp_tile_pallas, interpret=True))
    jax.clear_caches()
    try:
        kern, c_kern = _burst(jobs, cluster)
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    assert c_scan["decide.tiles_visited"] > 0
    assert c_scan["decide.dp_scan_tiles"] == c_scan["decide.tiles_visited"]
    assert "decide.dp_kernel_tiles" not in c_scan
    assert c_scan["decide.path_chain"] == c_scan["decide.tiles_visited"]
    assert c_kern["decide.dp_kernel_tiles"] == c_kern["decide.tiles_visited"]
    assert "decide.dp_scan_tiles" not in c_kern
    assert all(c_kern[f"decide.path_{p}"] == 0
               for p in ("dnc", "plateau", "chain"))
    assert any(p.best_t >= 0 for p in scan)
    for s, k in zip(scan, kern):
        assert (s.best_t, s.payoff, s.t_start) == (k.best_t, k.payoff,
                                                   k.t_start)
        np.testing.assert_array_equal(np.asarray(k.cost_full),
                                      np.asarray(s.cost_full))
