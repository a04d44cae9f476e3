"""Dual price functions (paper eq. (22)-(26)) and mutable price state.

Prices are maintained per (slot t, server, resource r):

    p_h^r(t) = L1 * (U1^r / L1) ** (g_h^r(t) / c_h^r)        (workers pool)
    q_k^r(t) = L2 * (U2^r / L2) ** (v_k^r(t) / c_k^r)        (PS pool)

``U`` bounds are the max per-unit-resource utility over jobs, ``L`` the
min unit-time-unit-resource utility scaled by 1/(4*eta).  In the online
setting the exact values need future knowledge, so the operator supplies
*estimates* (benchmarks/fig6 sweeps their accuracy).

``PriceState`` keeps the allocation tensors in two representations:

* a **host mirror** (numpy float64) — the source of truth for the numpy
  backends (``ref``/``fast``/``loop``) and for all read access via the
  ``g``/``v`` properties; always kept in sync by ``commit``/``release``
  with the same IEEE ops the pre-device implementation used, so the
  equivalence suites pin identical semantics;
* a **device residency** (jax arrays), materialised lazily on the first
  ``device_state()`` call (one full host→device upload, counted in
  ``device_uploads``) and then maintained *incrementally*: each
  ``commit``/``release`` streams only the committed slot window of the
  updated host mirror to the device and writes it with a jit-compiled
  dense window store (buffers donated off-CPU).  The residency is thus
  always the host mirror rounded to the device dtype — bit-equal in
  float64, never drifting in float32 however many commits and releases
  it absorbs.  The fused jax engine reads prices directly from this
  resident state, so a long simulation performs one full-state upload
  instead of one per accepted job.

Reading the ``g``/``v`` properties hands out the mutable host arrays, so
it conservatively drops the device residency (the caller may write); the
jax hot path never touches them — it goes through ``device_state``,
``capacity_ok`` and ``gpu_slot_usage`` instead.

**Rolling horizon (continuous serving mode).**  ``PriceState(...,
window=W)`` keeps only a ``W``-slot sliding window of the price tables:
local slot ``i`` is absolute slot ``origin + i``, and ``advance(now)``
slides the window forward, retiring past slots into scalar aggregates
(``retired_slots``, ``retired_gpu_slots``) and opening exact-zero future
slots at the tail.  Both representations slide *in place*: the host
mirror by a shift-and-zero copy, the device residency by a jit roll —
pure moves of existing values, so slots shared by the pre- and
post-advance windows stay bit-equal in both representations (any dtype)
and ``device_uploads`` stays O(1) across an entire streamed run.  With
``window`` omitted (or ``>= T``) the arrays are the full ``(T, ...)``
tables and nothing changes: the fixed-horizon episodic mode is the
``window >= T`` special case of this state.
"""
from __future__ import annotations

import contextlib
import dataclasses
import functools
import math
from typing import Optional, Sequence

import numpy as np

from .types import ClusterSpec, Job, R
from .. import obs as _obs


@dataclasses.dataclass(frozen=True)
class PriceParams:
    U1: np.ndarray  # (R,)
    U2: np.ndarray  # (R,)
    L1: float
    L2: float

    def scaled(self, factor: float) -> "PriceParams":
        """Scale the U/L *ratio* by ``factor`` keeping L fixed (Fig. 6 sweeps)."""
        ratio1 = np.maximum(self.U1 / self.L1, 1.0 + 1e-6) ** factor
        ratio2 = np.maximum(self.U2 / self.L2, 1.0 + 1e-6) ** factor
        return PriceParams(U1=self.L1 * ratio1, U2=self.L2 * ratio2,
                           L1=self.L1, L2=self.L2)

    @property
    def alpha(self) -> float:
        """Competitive-ratio parameter: alpha = max_r(1, ln U1/L1, ln U2/L2)."""
        a = 1.0
        for r in range(len(self.U1)):
            if self.L1 > 0 and self.U1[r] > 0:
                a = max(a, math.log(max(self.U1[r] / self.L1, 1.0)))
            if self.L2 > 0 and self.U2[r] > 0:
                a = max(a, math.log(max(self.U2[r] / self.L2, 1.0)))
        return a


def price_params_from_jobs(jobs: Sequence[Job], cluster: ClusterSpec,
                           floor_frac: float = 0.05) -> PriceParams:
    """U1^r, U2^r (23)(24) and L1, L2 (25)(26) from a job population.

    ``floor_frac`` clamps each job's worst-case utility f_i(T - a_i) to
    at least floor_frac * f_i(best): the paper's literal min degenerates
    to ~0 whenever a time-critical sigmoid job exists (f(T-a) is doubly-
    exponentially small), which disables the price filter entirely.  The
    paper itself runs with *estimated* U/L "based on past experience"
    (Sec. IV-B, Fig. 6); this is that estimator.  Pass floor_frac=0 for
    the literal formulas (used by the competitive-ratio tests — the
    Theorem-4 bound is w.r.t. the literal values).
    """
    T = cluster.T
    U1 = np.zeros(R)
    U2 = np.zeros(R)
    L1_num = math.inf
    L2_num = math.inf
    eta1_inv = math.inf  # min over i of the eta_1 bound RHS
    eta2_inv = math.inf
    cap_w = float(cluster.worker_caps.sum())
    cap_s = float(cluster.ps_caps.sum())
    for job in jobs:
        f_max = job.utility(job.min_duration)          # best achievable utility
        f_min = job.utility(T - job.arrival)           # worst (finish at T)
        f_min = max(f_min, floor_frac * f_max)
        total_work = math.ceil(job.total_work_slots)  # ceil(E N M (tau+2e/b))
        for r in range(R):
            if job.worker_res[r] > 0:
                U1[r] = max(U1[r], f_max / job.worker_res[r])
            if job.ps_res[r] > 0:
                U2[r] = max(U2[r], f_max / job.ps_res[r])
        wsum = float(job.worker_res.sum())
        ssum = float(job.ps_res.sum())
        # A job with zero demand on a pool places no constraint on that
        # pool's prices: worker-only jobs (ssum == 0) are a legal workload
        # and must not divide by zero here.
        if wsum > 0:
            L1_num = min(L1_num, f_min / (total_work * wsum))
            if cap_w > 0:
                eta1_inv = min(eta1_inv, total_work * wsum / (T * cap_w))
        if ssum > 0:
            L2_num = min(L2_num, f_min / (total_work * ssum))
            if cap_s > 0:
                eta2_inv = min(eta2_inv, total_work * ssum / (T * cap_s))
    eta1 = 1.0 / max(eta1_inv, 1e-12) if math.isfinite(eta1_inv) else 1.0
    eta2 = 1.0 / max(eta2_inv, 1e-12) if math.isfinite(eta2_inv) else 1.0
    eta1 = max(eta1, 1.0)  # paper requires 1/eta <= 1
    eta2 = max(eta2, 1.0)
    # No job constrains a pool -> any finite price works; fall back to the
    # other pool's floor (or 1.0) so the exponential price stays defined.
    if not math.isfinite(L1_num):
        L1_num = L2_num if math.isfinite(L2_num) else 4.0
    if not math.isfinite(L2_num):
        L2_num = L1_num
    L1 = L1_num / (4.0 * eta1)
    L2 = L2_num / (4.0 * eta2)
    # Guard degenerate resources (e.g. PS pool has no GPUs): keep U >= L so
    # the exponential price is well defined; a zero-demand resource never
    # contributes to cost anyway.
    U1 = np.maximum(U1, L1 * (1.0 + 1e-9))
    U2 = np.maximum(U2, L2 * (1.0 + 1e-9))
    return PriceParams(U1=U1, U2=U2, L1=L1, L2=L2)


# dirty-slot log length cap: on overflow the oldest half is dropped and
# the floor moves up (older caches then take one full recompute).  4096
# commit windows of history is far more than any burst re-solve needs.
_DIRTY_LOG_MAX = 4096


def size_bucket(n: int, floor: int = 32, step: int = 64) -> int:
    """Size bucket: powers of two up to ``step``, then multiples of ``step``.

    Shared by the fused engine's shape buckets and the price-state's
    commit-window buckets: balances jit recompiles (few distinct shapes)
    against padded work (cost is linear in each padded axis)."""
    b = floor
    while b < n and b < step:
        b *= 2
    if b >= n:
        return b
    return ((n + step - 1) // step) * step


def _pool_prices(alloc: np.ndarray, caps: np.ndarray, U: np.ndarray,
                 L: float) -> np.ndarray:
    """Exponential dual price table  L * (U/L)^(alloc/caps)  (eq. 22/25).

    ``alloc``: (..., S, R) allocation entries; ``caps``: (S, R).  Shared by
    the full-table ``worker_prices``/``ps_prices`` and the slot-window
    reads used by duality tracking — entries are priced elementwise, so a
    window evaluation is bit-identical to the same entries of the full
    table."""
    c = np.maximum(caps, 1e-12)
    ratio = np.maximum(U / L, 1.0 + 1e-9)
    return L * ratio ** (alloc / c)


@functools.lru_cache(maxsize=None)
def _window_set_jit(donate: bool):
    """jit'd dense slot-window store: buf[t0:t0+win] = vals (win static per
    compile via vals' shape, t0 dynamic).  Donated buffers where the
    backend supports it (donation on CPU only triggers a warning)."""
    import jax

    import jax.numpy as jnp

    def _window_set(buf, vals, t0):
        start = (t0,) + (jnp.zeros_like(t0),) * (buf.ndim - 1)
        return jax.lax.dynamic_update_slice(buf, vals, start)

    return jax.jit(_window_set, donate_argnums=(0,) if donate else ())


@functools.lru_cache(maxsize=None)
def _window_roll_jit(donate: bool):
    """jit'd window slide: drop the first ``k`` slots, zero-fill the tail
    (``k`` dynamic, shape static).  Values merely move, so the surviving
    slots stay bit-equal to their pre-slide selves in any dtype."""
    import jax

    import jax.numpy as jnp

    def _window_roll(buf, k):
        rolled = jnp.roll(buf, -k, axis=0)
        idx = jax.lax.broadcasted_iota(jnp.int32, buf.shape, 0)
        return jnp.where(idx < buf.shape[0] - k, rolled, 0)

    return jax.jit(_window_roll, donate_argnums=(0,) if donate else ())


def _x64_if(dtype) -> contextlib.AbstractContextManager:
    """enable_x64 context when the device dtype is float64 (CPU policy) —
    keeps uploads/window ops from being canonicalized down to float32."""
    if np.dtype(dtype) == np.float64:
        import jax
        return jax.enable_x64(True)
    return contextlib.nullcontext()


class PriceState:
    """Allocations g_h^r(t), v_k^r(t) and the derived price tables.

    Host mirror + lazily-materialised device residency (module docstring);
    ``device_uploads`` counts full host→device state syncs — O(1) per
    simulation on the jax path, not O(accepted jobs).

    ``window`` bounds the number of resident slots: slot arrays are
    ``(min(window, T), ...)`` and ``advance(now)`` slides them along the
    absolute clock.  All slot-indexed methods (commit/release, prices,
    headroom, ``alloc_window``) take *local* indices, i.e. offsets from
    ``origin``; with the default ``window=None`` the horizon equals
    ``cluster.T`` and ``origin`` stays 0, so local == absolute and the
    fixed-horizon behaviour is untouched.

    Example — prices start at the ``L1`` floor, rise on ``commit`` and
    return exactly on ``release``::

        >>> import numpy as np
        >>> from repro.core.oasis import OASiS
        >>> from repro.core.pricing import PriceState, price_params_from_jobs
        >>> from repro.sim.workload import make_cluster, make_jobs
        >>> cluster = make_cluster(T=20, H=3, K=3)
        >>> jobs = make_jobs(4, T=20, seed=0, small=True)
        >>> params = price_params_from_jobs(jobs, cluster)
        >>> state = PriceState(cluster, params)
        >>> bool(np.all(state.worker_prices() == params.L1))
        True
        >>> plan = OASiS(cluster, params).propose(jobs[0])   # no commitment
        >>> state.commit(jobs[0], plan.workers, plan.ps)
        >>> bool(np.any(state.worker_prices() > params.L1))
        True
        >>> state.release(jobs[0], plan.workers, plan.ps)
        >>> bool(np.all(state.worker_prices() == params.L1))
        True
    """

    def __init__(self, cluster: ClusterSpec, params: PriceParams,
                 window: Optional[int] = None):
        self.cluster = cluster
        self.params = params
        T, H, K = cluster.T, cluster.H, cluster.K
        self.window = T if window is None else min(int(window), T)
        self._g_host = np.zeros((self.window, H, R))  # alloc on worker servers
        self._v_host = np.zeros((self.window, K, R))  # alloc on PS servers
        # absolute slot of local index 0; advance() moves it forward
        self.origin = 0
        # aggregate accounting for slots retired out of the window
        self.retired_slots = 0
        self.retired_gpu_slots = 0.0        # sum of per-slot GPU units used
        # bumped on every commit/release (consumers may key caches on it)
        self.version = 0
        # dirty-slot log: (version, t0, t1) per commit/release slot window,
        # so caches keyed on the version can refresh only the slots a
        # commit touched.
        # ``_dirty_floor`` is the oldest version the log still covers —
        # ``dirty_spans_since`` answers None (unknowable; invalidate all)
        # for anything older.  advance() and mutable ``g``/``v`` access
        # reset the floor: those change prices outside any logged window.
        self._dirty_log: list = []
        self._dirty_floor = 0
        # device residency: (g_dev, v_dev) jax arrays or None; static side
        # tables (caps + price params) cached per dtype
        self._dev = None
        self._dev_dtype = None
        self._dev_static = {}
        self.device_uploads = 0

    # -- rolling window ----------------------------------------------------
    @property
    def horizon(self) -> int:
        """Number of resident slots — the schedulable lookahead.  Equals
        ``cluster.T`` for fixed-horizon states; the scheduling subroutines
        size their DP tables from this, never from ``cluster.T``."""
        return self._g_host.shape[0]

    @property
    def window_bytes(self) -> int:
        """Host-mirror bytes of the slot-indexed state — the peak-RSS
        proxy the serving benchmark records (the device residency, when
        materialised, is the same shape at the device dtype)."""
        return self._g_host.nbytes + self._v_host.nbytes

    def advance(self, now: int) -> None:
        """Slide the window so local slot 0 is absolute slot ``now``.

        The ``now - origin`` oldest slots are retired into the scalar
        aggregates (their allocation is immutable history: a schedule can
        no longer touch them) and the same number of exact-zero slots
        opens at the tail.  Surviving slots keep their values bit-for-bit
        in both the host mirror and the device residency — the slide is a
        pure move, applied on-device as a jit roll so ``device_uploads``
        stays O(1) across a whole streamed run.  No-op when ``now ==
        origin``; the clock never runs backwards."""
        shift = int(now) - self.origin
        if shift == 0:
            return
        if shift < 0:
            raise ValueError(f"advance({now}) before origin {self.origin}")
        if _obs.ENABLED:
            _obs.inc("price.window_advances")
            _obs.inc("price.window_slots_retired", shift)
        W = self._g_host.shape[0]
        k = min(shift, W)
        self.retired_gpu_slots += float(self._g_host[:k, :, 0].sum())
        self.retired_slots += shift
        self.origin = int(now)
        if k >= W:
            self._g_host[:] = 0.0
            self._v_host[:] = 0.0
        else:
            self._g_host[:W - k] = self._g_host[k:].copy()
            self._g_host[W - k:] = 0.0
            self._v_host[:W - k] = self._v_host[k:].copy()
            self._v_host[W - k:] = 0.0
        if self._dev is not None:
            import jax
            slide = _window_roll_jit(jax.default_backend() != "cpu")
            dev = []
            with _x64_if(self._dev_dtype):
                for buf in self._dev:
                    with _obs.dispatch("_window_roll"):
                        dev.append(slide(buf, np.int32(k)))
            self._dev = tuple(dev)
        self.version += 1
        # a slide remaps every local slot index — caches from before it
        # cannot be patched span-wise, only rebuilt
        self._dirty_log.clear()
        self._dirty_floor = self.version

    # -- host views --------------------------------------------------------
    @property
    def g(self) -> np.ndarray:
        """Worker-pool allocation (T, H, R), host numpy.  Hands out the
        mutable mirror, so the device residency is conservatively dropped
        (re-uploaded on next ``device_state``) and version-keyed caches are
        conservatively invalidated (dirty floor moves past ``version``)."""
        self._dev = None
        self._dirty_log.clear()
        self._dirty_floor = self.version + 1
        return self._g_host

    @g.setter
    def g(self, value: np.ndarray) -> None:
        self._g_host = np.asarray(value, dtype=np.float64)
        self._dev = None
        self._dirty_log.clear()
        self._dirty_floor = self.version + 1

    @property
    def v(self) -> np.ndarray:
        self._dev = None
        self._dirty_log.clear()
        self._dirty_floor = self.version + 1
        return self._v_host

    @v.setter
    def v(self, value: np.ndarray) -> None:
        self._v_host = np.asarray(value, dtype=np.float64)
        self._dev = None
        self._dirty_log.clear()
        self._dirty_floor = self.version + 1

    # -- price tables -----------------------------------------------------
    def worker_prices(self) -> np.ndarray:
        """p (T, H, R) with p = L1 * (U1/L1)^(g/c)."""
        return _pool_prices(self._g_host, self.cluster.worker_caps[None],
                            self.params.U1[None, None], self.params.L1)

    def ps_prices(self) -> np.ndarray:
        return _pool_prices(self._v_host, self.cluster.ps_caps[None],
                            self.params.U2[None, None], self.params.L2)

    def worker_prices_at(self, slots: np.ndarray) -> np.ndarray:
        """Price entries for ``slots`` only, (n, H, R) — bit-identical to
        ``worker_prices()[slots]`` without materializing the full table.
        Read-only (keeps the device residency)."""
        return _pool_prices(self._g_host[slots], self.cluster.worker_caps[None],
                            self.params.U1[None, None], self.params.L1)

    def ps_prices_at(self, slots: np.ndarray) -> np.ndarray:
        return _pool_prices(self._v_host[slots], self.cluster.ps_caps[None],
                            self.params.U2[None, None], self.params.L2)

    # -- bookkeeping (Alg. 1 lines 7-10) -----------------------------------
    def _window_delta(self, alloc: dict, res: np.ndarray, T: int,
                      sign: float):
        """Dense (win, S, R) slot-window delta for one commit/release.

        The window spans [t0, t0+win) with ``win`` bucketed (few distinct
        jit shapes); slots inside the window but absent from ``alloc``
        carry an exact 0.0 delta."""
        ts = np.fromiter(alloc.keys(), dtype=np.int64, count=len(alloc))
        t0, t1 = int(ts.min()), int(ts.max())
        win = min(size_bucket(t1 - t0 + 1, floor=8, step=64), T)
        t0 = min(t0, T - win)
        counts = np.stack([alloc[int(t)] for t in ts]).astype(np.float64)
        delta = np.zeros((win, counts.shape[1], R))
        delta[ts - t0] = sign * (counts[:, :, None] * res[None, None, :])
        return t0, delta

    def _apply(self, workers: dict, ps: dict, wres: np.ndarray,
               sres: np.ndarray, sign: float) -> None:
        T = self._g_host.shape[0]           # == horizon (window-local slots)
        deltas = []
        if workers and self.cluster.H:
            deltas.append((0, self._g_host) + self._window_delta(
                workers, wres, T, sign))
        if ps and self.cluster.K:
            deltas.append((1, self._v_host) + self._window_delta(
                ps, sres, T, sign))
        self._apply_deltas(deltas)

    def _apply_deltas(self, deltas) -> None:
        """Common tail of every state mutation (job commits/releases and
        fleet-churn server blocks): host add, incremental device stream,
        version bump, dirty-span logging."""
        for _, host, t0, delta in deltas:
            host[t0:t0 + delta.shape[0]] += delta
        if self._dev is not None and deltas:
            self._device_apply(deltas)
        self.version += 1
        for _, _, t0, delta in deltas:
            self._dirty_log.append((self.version, t0, t0 + delta.shape[0]))
        if len(self._dirty_log) > _DIRTY_LOG_MAX:
            drop = len(self._dirty_log) - _DIRTY_LOG_MAX // 2
            self._dirty_floor = self._dirty_log[drop - 1][0]
            del self._dirty_log[:drop]

    def _device_apply(self, deltas) -> None:
        """Stream the updated host-mirror windows to the resident device
        arrays.  Storing the mirror's values, not adding the deltas on
        device, keeps a float32 residency equal to the rounded mirror:
        float32 adds would round per commit, and (g + d) - d is not
        exact, so a release would leave phantom allocation behind."""
        import jax
        import jax.numpy as jnp
        store = _window_set_jit(jax.default_backend() != "cpu")
        dev = list(self._dev)
        with _x64_if(self._dev_dtype):
            for pool, host, t0, delta in deltas:
                # a copy: the mirror is mutated again by the next commit
                vals = jnp.array(host[t0:t0 + delta.shape[0]],
                                 self._dev_dtype, copy=True)
                with _obs.dispatch("_window_set"):
                    dev[pool] = store(dev[pool], vals, np.int32(t0))
        self._dev = tuple(dev)

    def commit(self, job: Job, workers: dict, ps: dict) -> None:
        with _obs.span("price.commit", jid=job.jid):
            self._apply(workers, ps, job.worker_res, job.ps_res, 1.0)
        if _obs.ENABLED:
            _obs.inc("price.commits")

    def release(self, job: Job, workers: dict, ps: dict) -> None:
        """Inverse of commit — used when a running job is preempted/killed
        (fault handling), not part of the paper's committed schedules."""
        with _obs.span("price.release", jid=job.jid):
            self._apply(workers, ps, job.worker_res, job.ps_res, -1.0)
        if _obs.ENABLED:
            _obs.inc("price.releases")

    # -- fleet churn (sim/fleet.py): capacity-aware headroom ----------------
    def _server_pool(self, pool: str):
        if pool == "worker":
            return 0, self._g_host, self.cluster.worker_caps
        if pool == "ps":
            return 1, self._v_host, self.cluster.ps_caps
        raise ValueError(f"unknown pool {pool!r}")

    def block_server(self, pool: str, server: int, t0: int = 0) -> float:
        """Fill one server's resident slots ``[t0, horizon)`` to capacity.

        Called when the server fails or drains (after its victims' tails
        have been released): its prices rise to the U bound and — the
        property the scheduling subroutines actually rely on — its
        per-slot headroom drops to exactly 0, so Alg. 2 can never plan
        onto a dead server.  Applied through the same delta machinery as
        ``commit`` (incremental device stream, dirty-span log), so the
        O(1)-upload residency invariant is preserved.  Idempotent per
        slot (already-full slots get an exact-0.0 delta) — the streaming
        engine re-blocks after every ``advance`` to cover the freshly
        opened tail slots.  Returns the GPU-slot units (resource 0)
        added, for the caller's utilization accounting."""
        pool_i, host, caps = self._server_pool(pool)
        T = host.shape[0]
        t0 = int(min(max(t0, 0), T))
        if t0 >= T or host.shape[1] == 0:
            return 0.0
        amt = caps[server][None, :] - host[t0:, server, :]
        win = min(size_bucket(T - t0, floor=8, step=64), T)
        w0 = T - win
        delta = np.zeros((win, host.shape[1], R))
        delta[t0 - w0:, server, :] = amt
        self._apply_deltas([(pool_i, host, w0, delta)])
        if _obs.ENABLED:
            _obs.inc("price.server_blocks")
        return float(amt[:, 0].sum())

    def unblock_server(self, pool: str, server: int, t0: int = 0) -> float:
        """Inverse of :meth:`block_server`: zero the server's resident
        content on ``[t0, horizon)`` when it recovers.  While blocked the
        server's headroom is 0, so nothing can have committed onto it —
        its content *is* the blocked amount, and removing it restores
        the pre-block zeros bit-exactly.  Returns the GPU-slot units
        (resource 0) released."""
        pool_i, host, _ = self._server_pool(pool)
        T = host.shape[0]
        t0 = int(min(max(t0, 0), T))
        if t0 >= T or host.shape[1] == 0:
            return 0.0
        amt = host[t0:, server, :].copy()
        win = min(size_bucket(T - t0, floor=8, step=64), T)
        w0 = T - win
        delta = np.zeros((win, host.shape[1], R))
        delta[t0 - w0:, server, :] = -amt
        self._apply_deltas([(pool_i, host, w0, delta)])
        if _obs.ENABLED:
            _obs.inc("price.server_unblocks")
        return float(amt[:, 0].sum())

    def dirty_spans_since(self, version: int):
        """Slot spans whose prices may have moved since ``version``.

        Returns a list of local-slot ``[t0, t1)`` pairs (possibly
        overlapping, possibly empty), or ``None`` when the delta is
        unknowable — ``version`` predates the log floor (log trimmed, a
        window slide, or mutable ``g``/``v`` access) — in which case the
        caller must invalidate everything.  Commit/release windows are
        logged in :meth:`_apply`; the engine's padded-state cache consumes
        this (``schedule_jax._padded_state``)."""
        if version < self._dirty_floor:
            return None
        return [(t0, t1) for v, t0, t1 in self._dirty_log if v > version]

    def headroom_workers(self, t: int) -> np.ndarray:
        return self.cluster.worker_caps - self._g_host[t]

    def headroom_ps(self, t: int) -> np.ndarray:
        return self.cluster.ps_caps - self._v_host[t]

    # -- whole-state queries (no host/device churn) -------------------------
    def capacity_ok(self, tol: float = 1e-6):
        """(workers_ok, ps_ok): no allocation entry exceeds capacity."""
        ok_w = bool(np.all(self._g_host
                           <= self.cluster.worker_caps[None] + tol))
        ok_p = bool(np.all(self._v_host <= self.cluster.ps_caps[None] + tol))
        return ok_w, ok_p

    def gpu_slot_usage(self) -> np.ndarray:
        """(T,) worker-pool GPU units in use per slot (resource 0)."""
        return self._g_host[:, :, 0].sum(axis=1)

    def alloc_window(self, t0: int, w: int):
        """Per-slot pool-total allocation for slots ``[t0, t0+w)``:
        ``(g_win, v_win)`` of shape (min(w, T-t0), R) each, summed over
        servers.  Read-only (keeps the device residency) — the rl/ env's
        capacity-window observation reads this instead of ``g``/``v``."""
        return (self._g_host[t0:t0 + w].sum(axis=1),
                self._v_host[t0:t0 + w].sum(axis=1))

    # -- device residency ---------------------------------------------------
    def _static_arrays(self, dtype):
        key = np.dtype(dtype).str
        cached = self._dev_static.get(key)
        if cached is not None:
            return cached
        import jax.numpy as jnp
        wcaps, scaps = self.cluster.worker_caps, self.cluster.ps_caps
        # empty pools are padded with one zero-capacity server so engine
        # gathers stay in bounds (it can never be used)
        if wcaps.shape[0] == 0:
            wcaps = np.zeros((1, R))
        if scaps.shape[0] == 0:
            scaps = np.zeros((1, R))
        pp = self.params
        with _x64_if(dtype):
            sd = (jnp.asarray(wcaps, dtype), jnp.asarray(scaps, dtype),
                  jnp.asarray(pp.U1, dtype), jnp.asarray(pp.U2, dtype),
                  jnp.asarray(pp.L1, dtype), jnp.asarray(pp.L2, dtype))
        self._dev_static[key] = sd
        return sd

    def _upload(self, dtype):
        import jax.numpy as jnp
        g, v = self._g_host, self._v_host
        if g.shape[1] == 0:
            g = np.zeros((self.horizon, 1, R))
        if v.shape[1] == 0:
            v = np.zeros((self.horizon, 1, R))
        self.device_uploads += 1
        if _obs.ENABLED:
            _obs.inc("price.device_uploads")
        # jnp.array (not asarray): jax CPU conversion can be zero-copy for
        # aligned buffers, and an aliased residency would silently track
        # (and double-count) subsequent host-mirror writes
        with _x64_if(dtype):
            return (jnp.array(g, dtype, copy=True),
                    jnp.array(v, dtype, copy=True))

    def device_state(self, dtype=None):
        """Engine view ``(g, v, wcaps, scaps, U1, U2, L1, L2)`` on device.

        The first call uploads the full state (counted in
        ``device_uploads``); afterwards ``commit``/``release`` keep the
        residency fresh incrementally, so repeat calls are free.  Empty
        pools are padded with one zero-capacity server."""
        if dtype is None:
            import jax
            dtype = (np.float64 if jax.default_backend() == "cpu"
                     else np.float32)
        if self._dev is None or np.dtype(self._dev_dtype) != np.dtype(dtype):
            self._dev_dtype = np.dtype(dtype)
            self._dev = self._upload(dtype)
        return self._dev + self._static_arrays(dtype)
