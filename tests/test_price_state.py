"""PriceState device-residency suite (PR 4).

Pins the contract of the dual-representation price state
(`core/pricing.py`):

* ``release`` exactly inverts ``commit`` on the host mirror — bit-equal
  ``g``/``v``, version bumped twice (hypothesis property, dyadic demands
  so float adds are exact);
* the host mirror stays bit-consistent with the device residency across
  interleaved fused-engine decisions and direct commits/releases;
* a full jax-impl run performs O(1) full host→device uploads
  (``device_uploads``), not one per accepted job — the tentpole claim;
* handing out the mutable host arrays (``.g``/``.v`` reads, rebinds)
  conservatively drops and re-uploads the residency.
"""
import numpy as np
import pytest

from repro.core import OASiS, price_params_from_jobs
from repro.core.pricing import PriceState, size_bucket
from repro.core.types import Job, SigmoidUtility
from repro.sim import make_cluster, make_jobs


def _mk_job(jid, wres, sres):
    return Job(jid=jid, arrival=0, epochs=2, num_chunks=3,
               minibatches_per_chunk=10, tau=0.02, grad_size=0.05,
               worker_bw=1.0, ps_bw=4.0, worker_res=np.asarray(wres, float),
               ps_res=np.asarray(sres, float),
               utility=SigmoidUtility(50.0, 1.0, 3.0))


def _state(T=12, H=4, K=4):
    cluster = make_cluster(T=T, H=H, K=K)
    jobs = make_jobs(8, T=T, seed=0, small=True)
    return PriceState(cluster, price_params_from_jobs(jobs, cluster))


def _alloc(rng, T, S, n_slots):
    slots = rng.choice(T, size=min(n_slots, T), replace=False)
    return {int(t): rng.integers(0, 4, size=S).astype(np.int64)
            for t in slots}


def test_release_inverts_commit_randomized():
    """(g + d) - d == g bitwise for dyadic demands; version bumped twice."""
    rng = np.random.default_rng(0)
    state = _state()
    T, H, K = state.cluster.T, state.cluster.H, state.cluster.K
    # a prior commit so the inversion starts from a non-zero tensor
    base = _mk_job(0, rng.integers(0, 8, 5) / 4.0, rng.integers(0, 8, 5) / 4.0)
    state.commit(base, _alloc(rng, T, H, 3), _alloc(rng, T, K, 2))
    for trial in range(25):
        job = _mk_job(trial + 1, rng.integers(0, 16, 5) / 4.0,
                      rng.integers(0, 16, 5) / 4.0)
        workers = _alloc(rng, T, H, int(rng.integers(1, T)))
        ps = _alloc(rng, T, K, int(rng.integers(1, T)))
        g0, v0 = state.g.copy(), state.v.copy()
        ver0 = state.version
        state.commit(job, workers, ps)
        assert state.version == ver0 + 1
        state.release(job, workers, ps)
        assert state.version == ver0 + 2
        assert np.array_equal(state.g, g0), "release did not invert commit (g)"
        assert np.array_equal(state.v, v0), "release did not invert commit (v)"


def test_commit_semantics_match_dense_sum():
    """commit accumulates exactly y*res / z*res at the committed slots."""
    rng = np.random.default_rng(1)
    state = _state()
    T, H, K = state.cluster.T, state.cluster.H, state.cluster.K
    want_g = np.zeros((T, H, 5))
    want_v = np.zeros((T, K, 5))
    for jid in range(5):
        job = _mk_job(jid, rng.integers(0, 8, 5) / 4.0,
                      rng.integers(0, 8, 5) / 4.0)
        workers = _alloc(rng, T, H, int(rng.integers(1, 5)))
        ps = _alloc(rng, T, K, int(rng.integers(1, 5)))
        state.commit(job, workers, ps)
        for t, y in workers.items():
            want_g[t] += y[:, None] * job.worker_res[None, :]
        for t, z in ps.items():
            want_v[t] += z[:, None] * job.ps_res[None, :]
    assert np.array_equal(state.g, want_g)
    assert np.array_equal(state.v, want_v)


def test_window_prices_match_full_tables():
    rng = np.random.default_rng(2)
    state = _state()
    job = _mk_job(0, rng.integers(1, 8, 5) / 4.0, rng.integers(1, 8, 5) / 4.0)
    state.commit(job, _alloc(rng, state.cluster.T, state.cluster.H, 4),
                 _alloc(rng, state.cluster.T, state.cluster.K, 4))
    slots = np.array([0, 3, 7])
    assert np.array_equal(state.worker_prices_at(slots),
                          state.worker_prices()[slots])
    assert np.array_equal(state.ps_prices_at(slots), state.ps_prices()[slots])


def test_capacity_ok_and_gpu_slot_usage():
    rng = np.random.default_rng(3)
    state = _state()
    job = _mk_job(0, rng.integers(1, 8, 5) / 4.0, rng.integers(1, 8, 5) / 4.0)
    state.commit(job, _alloc(rng, state.cluster.T, state.cluster.H, 4),
                 _alloc(rng, state.cluster.T, state.cluster.K, 4))
    assert np.array_equal(state.gpu_slot_usage(), state.g[:, :, 0].sum(axis=1))
    ok_w, ok_ps = state.capacity_ok()
    assert ok_w == bool(np.all(state.g <= state.cluster.worker_caps[None] + 1e-6))
    assert ok_ps == bool(np.all(state.v <= state.cluster.ps_caps[None] + 1e-6))
    state.g[:] = state.cluster.worker_caps[None] + 1.0     # force violation
    assert state.capacity_ok() == (False, ok_ps)


def test_device_mirror_consistent_after_interleaved_commits():
    """Interleave device-resident commits/releases with direct host-path
    bookkeeping: the download of the residency must stay bit-equal to the
    host mirror (CPU float64)."""
    rng = np.random.default_rng(4)
    state = _state()
    T, H, K = state.cluster.T, state.cluster.H, state.cluster.K
    dev = state.device_state()                     # residency begins: 1 upload
    assert state.device_uploads == 1
    trace = []
    for jid in range(6):
        job = _mk_job(jid, rng.integers(0, 8, 5) / 4.0,
                      rng.integers(0, 8, 5) / 4.0)
        workers = _alloc(rng, T, H, int(rng.integers(1, 6)))
        ps = _alloc(rng, T, K, int(rng.integers(1, 6)))
        state.commit(job, workers, ps)
        trace.append((job, workers, ps))
        if jid % 2:                                # interleave releases
            state.release(*trace.pop(0))
    dev = state.device_state()
    assert state.device_uploads == 1, "interleaved commits forced re-uploads"
    assert np.array_equal(np.asarray(dev[0]), state._g_host)
    assert np.array_equal(np.asarray(dev[1]), state._v_host)


def test_float32_residency_is_the_rounded_mirror():
    """A float32 residency (the TPU's) stays exactly the float64 mirror
    rounded to float32 through commits and releases of non-dyadic
    demands — no drift, no phantom allocation after a release — and is
    never re-uploaded."""
    rng = np.random.default_rng(7)
    state = _state()
    T, H, K = state.cluster.T, state.cluster.H, state.cluster.K
    state.device_state(np.float32)
    trace = []
    for jid in range(30):
        job = _mk_job(jid, rng.uniform(0.1, 3.0, 5), rng.uniform(0.1, 3.0, 5))
        workers = _alloc(rng, T, H, int(rng.integers(1, T)))
        ps = _alloc(rng, T, K, int(rng.integers(1, T)))
        state.commit(job, workers, ps)
        trace.append((job, workers, ps))
    for job, workers, ps in trace[::3]:
        state.release(job, workers, ps)
    dev = state.device_state(np.float32)
    assert state.device_uploads == 1
    assert np.array_equal(np.asarray(dev[0]),
                          state._g_host.astype(np.float32))
    assert np.array_equal(np.asarray(dev[1]),
                          state._v_host.astype(np.float32))


def test_jax_impl_run_is_o1_uploads():
    """The tentpole claim: a whole impl="jax" simulation performs O(1)
    full host→device state syncs, not one per accepted job."""
    cluster = make_cluster(T=40, H=8, K=8)
    jobs = make_jobs(40, T=40, seed=3, small=True)
    params = price_params_from_jobs(jobs, cluster)
    sched = OASiS(cluster, params, impl="jax")
    by_slot = {}
    for j in jobs:
        by_slot.setdefault(j.arrival, []).append(j)
    for t in sorted(by_slot):
        sched.on_arrivals(by_slot[t])
    assert len(sched.accepted) > 5, "degenerate instance"
    assert sched.state.device_uploads == 1, (
        f"{sched.state.device_uploads} uploads for "
        f"{len(sched.accepted)} accepted jobs — the per-accept re-upload "
        f"is back")


def test_host_reads_and_rebinds_invalidate_residency():
    rng = np.random.default_rng(5)
    state = _state()
    state.device_state()
    assert state.device_uploads == 1
    # reading .g hands out the mutable mirror -> residency dropped
    g = state.g
    g[3] += 1.0
    dev = state.device_state()
    assert state.device_uploads == 2
    assert np.array_equal(np.asarray(dev[0]), state._g_host)
    # rebinding likewise
    state.v = rng.uniform(0, 2, state._v_host.shape)
    dev = state.device_state()
    assert state.device_uploads == 3
    assert np.array_equal(np.asarray(dev[1]), state._v_host)


def test_commit_window_at_horizon_edges():
    """Bucketed windows near t = T-1 and windows wider than T stay in
    bounds and land on the right slots."""
    rng = np.random.default_rng(6)
    state = _state(T=10)
    state.device_state()
    job = _mk_job(0, rng.integers(1, 8, 5) / 4.0, rng.integers(1, 8, 5) / 4.0)
    y = np.ones(state.cluster.H, dtype=np.int64)
    z = np.ones(state.cluster.K, dtype=np.int64)
    state.commit(job, {9: y}, {9: z})                        # last slot
    state.commit(job, {0: y, 9: y}, {0: z, 9: z})            # window == T
    want = np.zeros((10, state.cluster.H, 5))
    want[9] += y[:, None] * job.worker_res[None, :]
    for t in (0, 9):
        want[t] += y[:, None] * job.worker_res[None, :]
    dev = state.device_state()
    assert state.device_uploads == 1
    assert np.array_equal(np.asarray(dev[0]), want)
    assert np.array_equal(state._g_host, want)


# ---------------------------------------------------------------------------
# rolling window (continuous serving mode)
# ---------------------------------------------------------------------------

def _mirror_commit(full, win, origin, job, workers, ps, release=False):
    """Apply the same logical commit to the windowed state (window-local
    slots) and the fixed-horizon state (absolute slots)."""
    op_w = win.release if release else win.commit
    op_f = full.release if release else full.commit
    op_w(job, workers, ps)
    op_f(job, {origin + t: y for t, y in workers.items()},
         {origin + t: z for t, z in ps.items()})


def test_rolling_window_matches_fixed_horizon_deterministic():
    """A windowed PriceState driven by ``advance`` + window-local commits
    stays bit-equal to the fixed-horizon state on the overlapping slots —
    host mirror AND device residency — while the slid-out slots retire
    into exact aggregates."""
    rng = np.random.default_rng(8)
    T, W = 40, 12
    cluster = make_cluster(T=T, H=4, K=4)
    params = price_params_from_jobs(make_jobs(8, T=T, seed=0, small=True),
                                    cluster)
    full = PriceState(cluster, params)
    win = PriceState(cluster, params, window=W)
    full.device_state()
    win.device_state()
    origin = 0
    for step in range(12):
        origin += int(rng.integers(0, 5))
        win.advance(origin)
        live = max(min(T - origin, W), 0)
        if live:
            job = _mk_job(step, rng.integers(0, 8, 5) / 4.0,
                          rng.integers(0, 8, 5) / 4.0)
            slots = rng.choice(live, size=min(3, live), replace=False)
            workers = {int(t): rng.integers(0, 4, 4).astype(np.int64)
                       for t in slots}
            ps = {int(t): rng.integers(0, 4, 4).astype(np.int64)
                  for t in slots}
            _mirror_commit(full, win, origin, job, workers, ps)
            if step % 3 == 2:
                _mirror_commit(full, win, origin, job, workers, ps,
                               release=True)
        ov = max(min(T - origin, W), 0)
        assert np.array_equal(win._g_host[:ov],
                              full._g_host[origin:origin + ov])
        assert np.array_equal(win._v_host[:ov],
                              full._v_host[origin:origin + ov])
        dw, df = win.device_state(), full.device_state()
        assert np.array_equal(np.asarray(dw[0])[:ov],
                              np.asarray(df[0])[origin:origin + ov])
        assert np.array_equal(np.asarray(dw[1])[:ov],
                              np.asarray(df[1])[origin:origin + ov])
    assert win.device_uploads == 1, "advance() must slide on-device, not re-upload"
    assert win.retired_slots == origin
    # dyadic demands -> every float add is exact, so the retired GPU-slot
    # aggregate equals the full table's prefix sum bit for bit
    assert win.retired_gpu_slots == full._g_host[:min(origin, T), :, 0].sum()


def test_advance_is_o1_uploads_and_monotone():
    """The serving-loop invariant: thousands of advances never re-upload
    the residency (O(1) full syncs per run), and the clock cannot move
    backwards."""
    state = _state(T=12)
    win = PriceState(state.cluster, state.params, window=6)
    win.device_state()
    rng = np.random.default_rng(9)
    now = 0
    for step in range(200):
        now += int(rng.integers(0, 3))
        win.advance(now)
        job = _mk_job(step, rng.integers(0, 8, 5) / 4.0,
                      rng.integers(0, 8, 5) / 4.0)
        win.commit(job, {int(rng.integers(0, 6)):
                         rng.integers(0, 3, 4).astype(np.int64)}, {})
        dev = win.device_state()
        assert np.array_equal(np.asarray(dev[0]), win._g_host)
    assert win.device_uploads == 1
    assert win.retired_slots == now
    with pytest.raises(ValueError):
        win.advance(now - 1)


def test_window_geq_T_is_episodic():
    """window >= T clamps to the full horizon: the windowed state is the
    fixed-horizon state (the safety rail for every existing suite)."""
    state = _state(T=10)
    win = PriceState(state.cluster, state.params, window=99)
    assert win.horizon == 10
    assert win.window == 10
    assert win._g_host.shape == state._g_host.shape


def test_size_bucket_monotone():
    prev = 0
    for n in range(1, 400):
        b = size_bucket(n, floor=8, step=64)
        assert b >= n and b >= prev
        prev = b


# ---------------------------------------------------------------------------
# hypothesis property: release inverts commit on arbitrary dyadic traces
# ---------------------------------------------------------------------------

try:
    from hypothesis import given, settings, strategies as st
    HAVE_HYPOTHESIS = True
except ImportError:                                  # pragma: no cover
    HAVE_HYPOTHESIS = False


if HAVE_HYPOTHESIS:
    @st.composite
    def _commit_case(draw):
        T = draw(st.integers(2, 16))
        H = draw(st.integers(1, 5))
        K = draw(st.integers(1, 5))
        dyadic = st.integers(0, 32).map(lambda q: q / 4.0)
        wres = np.array([draw(dyadic) for _ in range(5)])
        sres = np.array([draw(dyadic) for _ in range(5)])
        n_slots = draw(st.integers(1, T))
        slots = draw(st.permutations(range(T)))[:n_slots]
        workers = {t: np.array([draw(st.integers(0, 7)) for _ in range(H)],
                               dtype=np.int64) for t in slots}
        ps_slots = draw(st.permutations(range(T)))[:draw(st.integers(1, T))]
        ps = {t: np.array([draw(st.integers(0, 7)) for _ in range(K)],
                          dtype=np.int64) for t in ps_slots}
        prior = draw(st.integers(0, 3))
        return T, H, K, wres, sres, workers, ps, prior

    @settings(max_examples=40, deadline=None)
    @given(_commit_case())
    def test_hypothesis_release_inverts_commit(case):
        T, H, K, wres, sres, workers, ps, prior = case
        cluster = make_cluster(T=T, H=H, K=K)
        jobs = make_jobs(4, T=T, seed=0, small=True)
        state = PriceState(cluster, price_params_from_jobs(jobs, cluster))
        rng = np.random.default_rng(7)
        for jid in range(prior):                   # arbitrary starting tensor
            pj = _mk_job(100 + jid, rng.integers(0, 8, 5) / 4.0,
                         rng.integers(0, 8, 5) / 4.0)
            state.commit(pj, _alloc(rng, T, H, 2), _alloc(rng, T, K, 2))
        job = _mk_job(0, wres, sres)
        g0, v0 = state._g_host.copy(), state._v_host.copy()
        ver0 = state.version
        state.commit(job, workers, ps)
        state.release(job, workers, ps)
        assert state.version == ver0 + 2
        assert np.array_equal(state._g_host, g0)
        assert np.array_equal(state._v_host, v0)
if HAVE_HYPOTHESIS:
    @st.composite
    def _window_trace(draw):
        T = draw(st.integers(6, 20))
        W = draw(st.integers(2, T))
        n_steps = draw(st.integers(1, 6))
        steps = [(draw(st.integers(0, 3)),          # advance increment
                  draw(st.integers(0, 2 ** 16)),    # per-step alloc seed
                  draw(st.booleans()))              # release after commit?
                 for _ in range(n_steps)]
        return T, W, steps

    @settings(max_examples=30, deadline=None)
    @given(_window_trace())
    def test_hypothesis_rolling_window_equals_fixed_horizon(case):
        """advance() + commit/release on the window == the fixed-horizon
        PriceState on the overlapping slots, bit for bit, in both the
        host mirror and the device residency — with O(1) uploads."""
        T, W, steps = case
        cluster = make_cluster(T=T, H=3, K=3)
        params = price_params_from_jobs(
            make_jobs(4, T=T, seed=0, small=True), cluster)
        full = PriceState(cluster, params)
        win = PriceState(cluster, params, window=W)
        full.device_state()
        win.device_state()
        origin = 0
        for adv, jseed, do_release in steps:
            origin += adv
            win.advance(origin)
            live = max(min(T - origin, win.horizon), 0)
            if live:
                rng = np.random.default_rng(jseed)
                job = _mk_job(jseed, rng.integers(0, 8, 5) / 4.0,
                              rng.integers(0, 8, 5) / 4.0)
                workers = {int(t): rng.integers(0, 4, 3).astype(np.int64)
                           for t in rng.choice(live, size=min(2, live),
                                               replace=False)}
                ps = {int(t): rng.integers(0, 4, 3).astype(np.int64)
                      for t in rng.choice(live, size=min(2, live),
                                          replace=False)}
                _mirror_commit(full, win, origin, job, workers, ps)
                if do_release:
                    _mirror_commit(full, win, origin, job, workers, ps,
                                   release=True)
            ov = max(min(T - origin, win.horizon), 0)
            assert np.array_equal(win._g_host[:ov],
                                  full._g_host[origin:origin + ov])
            assert np.array_equal(win._v_host[:ov],
                                  full._v_host[origin:origin + ov])
            dw, df = win.device_state(), full.device_state()
            assert np.array_equal(np.asarray(dw[0])[:ov],
                                  np.asarray(df[0])[origin:origin + ov])
            assert np.array_equal(np.asarray(dw[1])[:ov],
                                  np.asarray(df[1])[origin:origin + ov])
        assert win.device_uploads == 1 and full.device_uploads == 1
        assert win.retired_slots == origin
else:                                                # pragma: no cover
    @pytest.mark.skip(reason="hypothesis not installed")
    def test_hypothesis_release_inverts_commit():
        pass

    @pytest.mark.skip(reason="hypothesis not installed")
    def test_hypothesis_rolling_window_equals_fixed_horizon():
        pass
