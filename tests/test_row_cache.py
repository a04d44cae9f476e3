"""Row-cache invalidation properties.

The incremental host COST-row path must agree with the from-scratch
oracle after ANY interleaving of price-state mutations:
``PriceState.dirty_spans_since`` + ``cost_t_rows(..., slots=...)`` must
reconstruct exactly ``cost_t_rows`` recomputed from scratch.

A seeded randomized sweep always runs; the hypothesis variant (optional
dev dependency, requirements-dev.txt) explores adversarial interleavings
when available and skips cleanly otherwise.
"""
import numpy as np
import pytest

from repro.core import price_params_from_jobs
from repro.core.pricing import PriceState
from repro.core.subroutine import cost_t_rows
from repro.sim import make_cluster, make_jobs

from random_ops import apply_random_ops


def _host_roundtrip(seed: int, window, n_rounds: int = 6, n_ops: int = 3):
    """Cached-incremental host rows == from-scratch rows after every round."""
    T = 24
    cluster = make_cluster(T=T, H=3, K=3)
    jobs = make_jobs(6, T=T, seed=seed, small=True)
    params = price_params_from_jobs(jobs, cluster)
    state = PriceState(cluster, params, window=window)
    job = jobs[0]
    dcap = min(job.max_chunks_per_slot, job.workload)
    if dcap == 0:
        pytest.skip("degenerate job")
    rng = np.random.default_rng(seed)

    def scratch():
        return cost_t_rows(job, state, state.worker_prices(),
                           state.ps_prices(), dcap)

    cached = scratch()
    version = state.version
    committed = []
    for _ in range(n_rounds):
        apply_random_ops(rng, state, jobs, committed, n_ops,
                         allow_advance=window is not None)
        spans = state.dirty_spans_since(version)
        p, q = state.worker_prices(), state.ps_prices()
        if spans is None:                          # unknowable: full rebuild
            cached = cost_t_rows(job, state, p, q, dcap)
        elif spans:
            slots = np.unique(np.concatenate(
                [np.arange(t0, t1) for t0, t1 in spans]))
            slots = slots[slots < state.horizon]
            cached[slots] = cost_t_rows(job, state, p, q, dcap, slots=slots)
        version = state.version
        want = scratch()
        assert np.array_equal(cached, want), (seed, window)


@pytest.mark.parametrize("seed", range(4))
@pytest.mark.parametrize("window", [None, 16])
def test_host_row_cache_randomized(seed, window):
    _host_roundtrip(seed, window)


def test_dirty_span_log_semantics():
    """dirty_spans_since: exact spans for commits, None past the floor."""
    cluster = make_cluster(T=16, H=2, K=2)
    jobs = make_jobs(3, T=16, seed=0, small=True)
    params = price_params_from_jobs(jobs, cluster)
    state = PriceState(cluster, params)
    v0 = state.version
    assert state.dirty_spans_since(v0) == []
    w = {4: np.array([1, 0], np.int64), 6: np.array([0, 1], np.int64)}
    z = {5: np.array([1, 0], np.int64)}
    state.commit(jobs[0], w, z)
    spans = state.dirty_spans_since(v0)
    assert spans is not None and len(spans) == 2
    covered = set()
    for t0, t1 in spans:
        covered.update(range(t0, t1))
    assert {4, 5, 6} <= covered                    # every touched slot dirty
    assert state.dirty_spans_since(state.version) == []
    # advance re-indexes slots: older versions become unknowable
    state.advance(2)
    assert state.dirty_spans_since(v0) is None
    assert state.dirty_spans_since(state.version) == []
    # mutable g/v access invalidates even current-version caches
    v1 = state.version
    _ = state.g
    assert state.dirty_spans_since(v1) is None


# -- hypothesis variant ------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                # pragma: no cover
    HAVE_HYPOTHESIS = False

if HAVE_HYPOTHESIS:
    @settings(max_examples=25, deadline=None)
    @given(seed=st.integers(0, 2 ** 16),
           window=st.sampled_from([None, 12, 16]),
           n_rounds=st.integers(1, 8),
           n_ops=st.integers(1, 5))
    def test_host_row_cache_hypothesis(seed, window, n_rounds, n_ops):
        _host_roundtrip(seed, window, n_rounds=n_rounds, n_ops=n_ops)
