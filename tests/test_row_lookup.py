"""The COST-row build's two lowerings give the same numbers, bit for bit.

Every platform sorts the servers with one stable sort that carries the
capacities (``_sort_servers``), which must equal an ``argsort`` with
gathers.  A TPU lowers the greedy-cost lookup as dense masked reductions
(``_greedy_cost_dense``), every other platform as a binary search
(``_greedy_cost_search``).  These tests run both lookups on the CPU: the
helpers directly, then the whole engine with the TPU's swapped in.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro import obs as obslib
from repro.core import OASiS, price_params_from_jobs
from repro.core import schedule_jax as sj
from repro.core.pricing import PriceState
from repro.kernels.minplus.tiled import TILE
from repro.sim import make_cluster, make_jobs

S, M = 100, 64

# name -> (leading shape, table draw): the batched core's (B, TILE) and
# the single-lane core's (T,) tables, with ties, full fleets and counts
# at and beyond the ends of the pool
CASES = {
    "batched_b1": ((1, TILE), "random"),
    "batched_b8": ((8, TILE), "random"),
    "single": ((40,), "random"),
    "unit_cost_ties": ((2, TILE), "ties"),
    "mostly_full_fleet": ((2, TILE), "full"),
    "zero_counts": ((2, TILE), "zero_counts"),
    "counts_above_pool": ((40,), "over_pool"),
    "empty_pool": ((2, TILE), "empty"),
}


def _draw(lead, kind, rng):
    """(unit, cap, counts) as the row build meets them: unit costs per
    server, integral capacities (0 for a full server) and integral
    instance counts per DP column."""
    unit = rng.uniform(0.5, 4.0, lead + (S,))
    cap = rng.integers(0, 9, lead + (S,)).astype(np.float64)
    if kind == "ties":
        unit = rng.choice([1.0, 1.5, 2.0], lead + (S,))
    elif kind == "full":
        cap[rng.random(lead + (S,)) < 0.85] = 0.0
    elif kind == "empty":
        cap[:] = 0.0
    pool = cap.sum(-1, keepdims=True)
    hi = np.maximum(pool, 1.0) * 1.5
    counts = np.floor(rng.random(lead + (M,)) * hi)
    counts[..., 0] = 0.0
    if kind == "zero_counts":
        counts[rng.random(counts.shape) < 0.5] = 0.0
    elif kind == "over_pool":
        counts[..., -8:] = pool + np.arange(1, 9)
    return unit, cap, counts


@pytest.mark.parametrize("case", sorted(CASES))
def test_dense_row_lookup_equals_search(case):
    lead, kind = CASES[case]
    rng = np.random.default_rng(sorted(CASES).index(case))
    unit, cap, counts = _draw(lead, kind, rng)
    for x64 in (False, True):
        with jax.enable_x64(x64):
            dt = jnp.float64 if x64 else jnp.float32
            u, c, n = (jnp.asarray(a, dt) for a in (unit, cap, counts))
            order = jnp.argsort(u, axis=-1, stable=True)
            want = (jnp.take_along_axis(u, order, axis=-1),
                    jnp.take_along_axis(c, order, axis=-1), order)
            for with_order in (False, True):
                got = jax.jit(sj._sort_servers, static_argnums=2)(
                    u, c, with_order)
                assert len(got) == 2 + with_order
                for g, w in zip(got, want):
                    assert g.dtype == w.dtype
                    assert np.array_equal(np.asarray(g), np.asarray(w))
            scost, scap = want[0], want[1]
            ccap = jnp.cumsum(scap, axis=-1)
            ccost = jnp.cumsum(scap * scost, axis=-1)
            want = jax.jit(sj._greedy_cost_search)(ccap, ccost, scost, n)
            got = jax.jit(sj._greedy_cost_dense)(ccap, ccost, scost, n)
            assert got.dtype == want.dtype
            assert np.array_equal(np.asarray(got), np.asarray(want))
            # on the CPU the dispatcher lowers the search form
            assert np.array_equal(
                np.asarray(jax.jit(sj._greedy_cost)(ccap, ccost, scost, n)),
                np.asarray(want))


def _episode():
    """The burst-vs-sequential set-up of ``tests/test_fused_engine.py``:
    full-size jobs on 40 + 40 servers, where split ties are common."""
    from repro.sim.engine import _with_quantum
    T, H, K = 60, 40, 40
    cluster = make_cluster(T=T, H=H, K=K)
    jobs = [_with_quantum(j, 0)
            for j in make_jobs(100, T=T, seed=0, small=False)]
    return T, cluster, jobs, price_params_from_jobs(jobs, cluster)


def _run_episode():
    """Sequential ``on_arrival`` and per-slot ``on_arrivals`` (the burst
    path) over the episode, and ``_decide_one`` decisions (the TPU's
    single-arrival launch, interpret mode here) for the first jobs at the
    sequential run's final prices."""
    T, cluster, jobs, params = _episode()
    seq = OASiS(cluster, params, impl="jax")
    for j in sorted(jobs, key=lambda x: (x.arrival, x.jid)):
        seq.on_arrival(j)
    bat = OASiS(cluster, params, impl="jax")
    by_slot = {}
    for j in jobs:
        by_slot.setdefault(j.arrival, []).append(j)
    for t in range(T):
        bat.on_arrivals(sorted(by_slot.get(t, []), key=lambda x: x.jid))
    one = [sj.best_schedule_fused(j, seq.state, use_pallas=True)
           for j in jobs[:4]]
    return seq, bat, one


def _same_schedule(a, b):
    assert (a is None) == (b is None)
    if a is None:
        return
    assert a.finish == b.finish and a.cost == b.cost          # exact
    assert a.workers.keys() == b.workers.keys()
    for t in a.workers:
        assert np.array_equal(a.workers[t], b.workers[t])
        assert np.array_equal(a.ps[t], b.ps[t])


def test_engine_with_tpu_row_lookup_equals_search(monkeypatch):
    """The whole engine with the TPU's lookup swapped in for the CPU's:
    the burst launches (row cache and order cache on at this size), the
    sequential re-solves and ``_decide_one`` decide, place and cost every
    job as the binary search does, and the rows built are counted under
    the dense form's counter."""
    search = _run_episode()
    forms = sj._GREEDY_COST_FORMS
    monkeypatch.setitem(forms, "default", forms["tpu"])
    jax.clear_caches()
    ob = obslib.Obs()
    try:
        with obslib.activate(ob):
            dense = _run_episode()
    finally:
        monkeypatch.undo()
        jax.clear_caches()
    c = ob.metrics.snapshot()["counters"]
    assert c["decide.rows_dense_tiles"] > 0
    assert c.get("decide.rows_search_tiles", 0) == 0
    for s, d in zip(search[:2], dense[:2]):
        assert s.accepted.keys() == d.accepted.keys()
        assert s.total_utility == d.total_utility             # exact
        for jid in s.accepted:
            _same_schedule(s.accepted[jid], d.accepted[jid])
    assert len(search[0].accepted) > 0
    for s, d in zip(search[2], dense[2]):
        _same_schedule(s, d)


def test_cpu_rows_counted_as_search_tiles():
    """On the CPU every COST-row tile a launch builds is counted under
    ``decide.rows_search_tiles``: every visited tile of a burst or a
    re-solve, and a ``_decide_one`` launch's whole horizon."""
    cluster = make_cluster(T=100, H=6, K=6)
    jobs = make_jobs(3, T=100, seed=2, small=True)
    state = PriceState(cluster, price_params_from_jobs(jobs, cluster))
    ob = obslib.Obs()
    with obslib.activate(ob):
        sj.decide_burst(jobs, state)
    c = ob.metrics.snapshot()["counters"]
    assert c["decide.tiles_visited"] > 0
    assert c["decide.rows_search_tiles"] == c["decide.tiles_visited"]
    assert c.get("decide.rows_dense_tiles", 0) == 0

    # a re-solve builds the rows of every tile it visits
    ob = obslib.Obs()
    with obslib.activate(ob):
        sj.best_schedule_fused(jobs[0], state)
    c = ob.metrics.snapshot()["counters"]
    assert c["decide.tiles_visited"] > 0
    assert c["decide.rows_search_tiles"] == c["decide.tiles_visited"]
    assert c.get("decide.rows_dense_tiles", 0) == 0

    ob = obslib.Obs()
    with obslib.activate(ob):
        sj.best_schedule_fused(jobs[0], state, use_pallas=True)
    c = ob.metrics.snapshot()["counters"]
    assert c["decide.rows_search_tiles"] == sj._pad_tiles(100) // TILE
    assert c.get("decide.rows_dense_tiles", 0) == 0
