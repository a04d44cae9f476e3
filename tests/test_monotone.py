"""Monotone min-plus dispatch: bit-exactness against the chain oracle.

The structure-aware slot kernel (``kernels.minplus.monotone``) may take a
convexity-gated divide-and-conquer branch, a run-compressed plateau scan,
or fall back to the banded chain — and every branch must produce the SAME
floating-point sums as ``minplus_chain_step`` (the engine's reference),
bit for bit, on arbitrary rows: staircases, certified-convex curves,
+inf-infeasible tails, NaN/-inf poisoned rows, and tie-heavy plateaus.

A seeded randomized sweep always runs; the hypothesis variant (optional
dev dependency, requirements-dev.txt) explores adversarial rows when
available and skips cleanly otherwise.  The engine-level tests pin the
acceptance contract: with the monotone dispatch active the fallback
counter stays below 100% (the fast paths actually fire) and the decision
trajectory is bit-identical to the chain-only engine.
"""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.minplus.monotone import (PATH_CHAIN, PATH_DNC,
                                            PATH_PLATEAU,
                                            convex_certificate,
                                            convex_certificate_np,
                                            monotone_dnc_step,
                                            monotone_path_ref,
                                            monotone_step,
                                            monotone_step_with_path,
                                            monotone_sweep,
                                            plateau_step_unrolled,
                                            run_count, run_count_np)
from repro.kernels.minplus.tiled import minplus_chain_step


def _chain(row, prev):
    """The engine's reference slot: lane-batched banded chain."""
    return np.asarray(minplus_chain_step(jnp.asarray(row)[None],
                                         jnp.asarray(prev)[None])[0])


# jit once per (shape, dtype): the dispatcher is built for use inside the
# engine's compiled decide loop — eagerly it re-traces every call, which
# at 60 randomized calls per test would dominate the suite's wall clock
_step = jax.jit(monotone_step)


def _mk_row(kind: str, rng, dc1: int, dtype):
    js = np.arange(dc1, dtype=np.float64)
    if kind == "random":
        row = rng.random(dc1)
    elif kind == "convex":
        row = js * (js - 1) / 2.0       # exact second difference 1
    elif kind == "stair":
        row = np.repeat(rng.random(max(dc1 // 8, 1)),
                        8)[:dc1].astype(np.float64)
        row = np.resize(row, dc1)
    elif kind == "inf_tail":
        row = rng.random(dc1)
        row[int(dc1 * 0.6):] = np.inf
    elif kind == "ties":
        row = np.round(rng.random(dc1) * 3) / 3.0
    else:
        raise AssertionError(kind)
    row[0] = 0.0                         # COST_t(0 passes) = 0
    return row.astype(dtype)


KINDS = ["random", "convex", "stair", "inf_tail", "ties"]


@pytest.mark.parametrize("kind", KINDS)
@pytest.mark.parametrize("dtype", [np.float32, np.float64])
@pytest.mark.parametrize("dc1,d1", [(9, 33), (33, 65), (64, 129)])
def test_monotone_step_matches_chain(kind, dtype, dc1, d1):
    """Every dispatch outcome == the chain, bit for bit."""
    rng = np.random.default_rng(dc1 * d1 + len(kind))
    row = _mk_row(kind, rng, dc1, dtype)
    prev = rng.random(d1).astype(dtype)
    prev[rng.random(d1) < 0.2] = np.inf
    prev[0] = 0.0
    got = np.asarray(_step(jnp.asarray(row), jnp.asarray(prev)))
    assert np.array_equal(got, _chain(row, prev)), kind


@pytest.mark.parametrize("kind,want_path", [
    ("convex", PATH_DNC), ("stair", PATH_PLATEAU), ("random", PATH_CHAIN),
])
def test_dispatch_path_matches_oracle(kind, want_path):
    """The device dispatch picks the branch the numpy oracle names (the
    host COST-row flags drive the same decision in ``cost_row_flags``)."""
    rng = np.random.default_rng(3)
    row = _mk_row(kind, rng, 48, np.float64)
    prev = rng.random(97)
    prev[0] = 0.0
    new, path = monotone_step_with_path(jnp.asarray(row), jnp.asarray(prev))
    ref = monotone_path_ref(row)
    assert ref == want_path
    # D&C may legally spill to chain (overflow guard); never the reverse
    assert int(path) == ref or (ref == PATH_DNC and int(path) == PATH_CHAIN)
    assert np.array_equal(np.asarray(new), _chain(row, prev))


def test_poisoned_rows_fall_back_to_chain():
    """NaN / -inf rows are not 'clean': dispatch must refuse the fast
    paths (whose run/convex algebra assumes ordered totals) and still
    return the chain's exact output."""
    rng = np.random.default_rng(11)
    prev = rng.random(33)
    prev[0] = 0.0
    for poison in (np.nan, -np.inf):
        row = rng.random(17)
        row[0] = 0.0
        row[5] = poison
        new, path = monotone_step_with_path(jnp.asarray(row),
                                            jnp.asarray(prev))
        assert int(path) == PATH_CHAIN
        assert np.array_equal(np.asarray(new), _chain(row, prev),
                              equal_nan=True)


def test_convex_certificate_is_exact():
    """The certificate is a *certificate*: exact compensated second
    differences, no tolerance — a one-ulp dent must decertify."""
    js = np.arange(32, dtype=np.float64)
    row = js * js
    assert bool(convex_certificate(jnp.asarray(row)))
    assert bool(convex_certificate_np(row))
    # knife edge: a linear row (flat 2nd differences) certifies; one ulp
    # up at an interior point makes its triple exactly -2 ulp — must
    # decertify.  float32 so the perturbation survives device transfer
    # regardless of the ambient x64 mode.
    lin = (js * 3.0).astype(np.float32)
    assert bool(convex_certificate(jnp.asarray(lin)))
    dent = lin.copy()
    dent[7] = np.nextafter(dent[7], np.float32(np.inf))
    assert not bool(convex_certificate(jnp.asarray(dent)))
    assert not bool(convex_certificate_np(dent))
    # infeasible suffix stays certified; an interior +inf hole does not
    tail = row.copy()
    tail[20:] = np.inf
    assert bool(convex_certificate(jnp.asarray(tail)))
    hole = row.copy()
    hole[5] = np.inf
    assert not bool(convex_certificate(jnp.asarray(hole)))


def test_run_count_matches_np():
    rng = np.random.default_rng(4)
    rows = np.repeat(rng.random((8, 6)), 5, axis=1)[:, :29]
    dev = np.asarray(jax.vmap(run_count)(jnp.asarray(rows)))
    assert np.array_equal(dev, run_count_np(rows))


@pytest.mark.parametrize("r_max", [4, 16])
def test_plateau_unrolled_matches_chain(r_max):
    """The r_max-bounded unrolled plateau scan (the engine's in-loop
    form) == chain whenever the row actually fits in r_max runs."""
    rng = np.random.default_rng(r_max)
    vals = rng.random(r_max)
    vals[0] = 0.0                        # COST_t(0 passes) = 0, same run
    row = np.repeat(vals, 7)[:r_max * 7 - 3]
    prev = rng.random(129)
    prev[0] = 0.0
    assert int(run_count_np(row)) <= r_max
    got = np.asarray(plateau_step_unrolled(jnp.asarray(row),
                                           jnp.asarray(prev), r_max))
    assert np.array_equal(got, _chain(row, prev))


@pytest.mark.parametrize("use_pallas", [False, True])
def test_minplus_monotone_dispatch_equals_minplus(use_pallas):
    """The monotone dispatcher == ops.minplus cost output on every row
    kind, for both chain entries (the Pallas kernel and the jnp
    reference)."""
    from repro.kernels.minplus.ops import minplus
    rng = np.random.default_rng(21)
    for kind in KINDS:
        row = _mk_row(kind, rng, 40, np.float32)
        prev = rng.random(101).astype(np.float32)
        prev[0] = 0.0
        want = np.asarray(minplus(jnp.asarray(row), jnp.asarray(prev),
                                  use_pallas=use_pallas)[0])
        got = np.asarray(monotone_step(jnp.asarray(row), jnp.asarray(prev)))
        assert np.array_equal(got, want), kind


def test_monotone_sweep_matches_sweep_cost():
    from repro.kernels.minplus.ref import minplus_sweep_cost
    rng = np.random.default_rng(8)
    T, dc1, d1 = 40, 13, 57
    rows = np.repeat(rng.random((T, 4)), 4, axis=1)[:, :dc1]
    rows[rng.random((T, dc1)) < 0.2] = np.inf
    rows[:, 0] = 0.0
    got = np.asarray(monotone_sweep(jnp.asarray(rows), d1 - 1))
    want = np.asarray(minplus_sweep_cost(jnp.asarray(rows), d1 - 1))
    assert np.array_equal(got, want)


def test_monotone_dnc_overflow_is_flagged_not_wrong():
    """When the D&C interval buffer would overflow it must say so (the
    dispatcher then reruns the chain) — never return a wrong value."""
    rng = np.random.default_rng(5)
    js = np.arange(24, dtype=np.float64)
    row = js * (js + 3) / 2
    prev = rng.random(49)
    new, ovf = monotone_dnc_step(jnp.asarray(row), jnp.asarray(prev))
    if not bool(ovf):
        assert np.array_equal(np.asarray(new), _chain(row, prev))


# -- randomized sweep (chain equivalence on arbitrary rows) ------------------

@pytest.mark.parametrize("seed", range(6))
def test_monotone_matches_chain_randomized(seed):
    """Arbitrary rows — random run structure, random +inf placement,
    random dtype — dispatched through every branch, == chain bitwise.
    Shapes come from a small fixed set so the jit compilations amortize
    across seeds (a fresh shape costs ~1s of XLA compile each)."""
    rng = np.random.default_rng(seed)
    for _ in range(10):
        dc1 = int(rng.choice([5, 17, 64]))
        d1 = int(rng.choice([33, 129]))
        dtype = np.float32 if rng.integers(2) else np.float64
        nvals = int(rng.integers(1, dc1 + 1))
        row = rng.choice(rng.random(nvals), size=dc1).astype(dtype)
        row[rng.random(dc1) < rng.random() * 0.5] = np.inf
        row[0] = 0.0
        prev = rng.random(d1).astype(dtype)
        prev[rng.random(d1) < 0.3] = np.inf
        got = np.asarray(_step(jnp.asarray(row), jnp.asarray(prev)))
        assert np.array_equal(got, _chain(row, prev)), (seed, dc1, d1)


# -- engine acceptance: fast paths fire, trajectory pinned -------------------

def test_engine_monotone_fallback_below_100_percent():
    """Paper-scale instance with the monotone dispatch active: the
    obs path counters (``decide.path_*``) must show the plateau path firing
    (fallback < 100%) AND the trajectory must equal the chain-only
    engine (REPRO_MONOTONE_BAND=0) exactly."""
    import os
    from repro import obs as obslib
    from repro.sim import make_cluster, make_jobs, simulate
    cluster = make_cluster(T=100, H=50, K=50)
    jobs = make_jobs(200, T=100, seed=0, small=True)
    ob = obslib.Obs()
    with obslib.activate(ob):
        a = simulate(cluster, jobs, scheduler="oasis", impl="jax",
                     quantum=0)
    c = ob.metrics.snapshot()["counters"]
    snap = {k: c.get(f"decide.path_{k}", 0)
            for k in ("dnc", "plateau", "chain")}
    total = sum(snap.values())
    assert total > 0, "monotone dispatch never active at paper scale"
    assert snap["chain"] < total, f"fallback at 100%: {snap}"
    old = os.environ.get("REPRO_MONOTONE_BAND")
    os.environ["REPRO_MONOTONE_BAND"] = "0"
    try:
        b = simulate(cluster, jobs, scheduler="oasis", impl="jax",
                     quantum=0)
    finally:
        if old is None:
            del os.environ["REPRO_MONOTONE_BAND"]
        else:
            os.environ["REPRO_MONOTONE_BAND"] = old
    assert a.accepted == b.accepted
    assert a.completion == b.completion
    assert a.total_utility == b.total_utility          # bit-identical


# -- hypothesis variant ------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=50, deadline=None)
    @given(seed=st.integers(0, 2 ** 16),
           dc1=st.sampled_from([5, 17, 64]), d1=st.sampled_from([33, 129]),
           nvals=st.integers(1, 12), inf_frac=st.floats(0.0, 0.6),
           f32=st.booleans())
    def test_monotone_matches_chain_hypothesis(seed, dc1, d1, nvals,
                                               inf_frac, f32):
        rng = np.random.default_rng(seed)
        dtype = np.float32 if f32 else np.float64
        row = rng.choice(rng.random(nvals), size=dc1).astype(dtype)
        row[rng.random(dc1) < inf_frac] = np.inf
        row[0] = 0.0
        prev = rng.random(d1).astype(dtype)
        prev[rng.random(d1) < inf_frac] = np.inf
        got = np.asarray(_step(jnp.asarray(row), jnp.asarray(prev)))
        assert np.array_equal(got, _chain(row, prev))
