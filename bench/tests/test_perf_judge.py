"""The judge that decides ``correct``: its float64 reference agrees with
the program's own Alg. 2 oracle, a sound run passes, and a run with the
timed path broken underneath, or the bfloat16 control, fails."""
import dataclasses
import itertools

import numpy as np
import pytest

from bench import generator, judge, reference as ref, spec


def _program_state(state):
    from repro.core.pricing import PriceParams, PriceState
    from repro.core.types import ClusterSpec
    cl = ClusterSpec(T=state.horizon, worker_caps=state.wcaps,
                     ps_caps=state.scaps)
    p = state.params
    ps = PriceState(cl, PriceParams(U1=p.U1, U2=p.U2, L1=p.L1, L2=p.L2))
    ps.g = state.g.copy()
    ps.v = state.v.copy()
    return ps


@pytest.mark.parametrize("seed", [1, 2, 3])
def test_reference_matches_the_programs_oracle(seed):
    """``reference.alg2`` against the program's loop-faithful Alg. 2 on
    random partly filled states."""
    import math
    from repro.core.subroutine import best_schedule_ref
    from repro.core.types import Job, SigmoidUtility
    rng = np.random.default_rng(seed)
    specs = list(itertools.islice(generator.stream(seed, rate=3.0,
                                                   small=True), 12))
    w, s = generator.make_fleet(4, 4, seed)
    params = ref.price_params([ref.RefJob.from_spec(x) for x in specs], w,
                              s, 10)
    state = ref.RefState(w, s, params, 10)
    state.g[:] = rng.random(state.g.shape) * w[None] * 0.7
    state.v[:] = rng.random(state.v.shape) * s[None] * 0.7
    for x in specs:
        q = max(1, math.ceil(x.epochs * x.num_chunks / ref.QUANTUM_UNITS))
        job = Job(jid=x.jid, arrival=0, epochs=x.epochs,
                  num_chunks=x.num_chunks,
                  minibatches_per_chunk=x.minibatches_per_chunk, tau=x.tau,
                  grad_size=x.grad_size, worker_bw=x.worker_bw,
                  ps_bw=x.ps_bw, worker_res=x.worker_res, ps_res=x.ps_res,
                  utility=SigmoidUtility(x.gamma1, x.gamma2, x.gamma3),
                  quantum=q)
        mine = ref.alg2(ref.RefJob.from_spec(x), state)
        theirs = best_schedule_ref(job, _program_state(state))
        assert (mine is None) == (theirs is None)
        if mine is not None:
            assert mine.payoff == pytest.approx(theirs.payoff, abs=1e-9)
            v = ref.evaluate(ref.RefJob.from_spec(x), state, mine.workers,
                             mine.ps)
            assert v.payoff == pytest.approx(mine.payoff, abs=1e-9)
            assert v.unit_shortfall == 0 and v.breaches == 0


def test_bf16_rounds_to_nearest_even():
    x = np.array([1.0, 1.0 + 2 ** -8, 1.0 + 3 * 2 ** -9, np.inf, -2.5])
    assert list(ref.bf16(x)) == [1.0, 1.0, 1.0 + 2 ** -7, np.inf, -2.5]


# -- whole runs on the CPU at a size a test can hold ------------------------

@pytest.fixture
def isolated_cache(tmp_path, monkeypatch):
    """The run's persistent compile cache in a temporary directory, and the
    process's JAX cache settings restored afterwards."""
    import jax
    monkeypatch.setenv("JAX_COMPILATION_CACHE_DIR", str(tmp_path / "jc"))
    before = (jax.config.jax_compilation_cache_dir,
              jax.config.jax_persistent_cache_min_compile_time_secs)
    yield
    jax.config.update("jax_compilation_cache_dir", before[0])
    jax.config.update("jax_persistent_cache_min_compile_time_secs",
                      before[1])
    from jax.experimental.compilation_cache import compilation_cache
    compilation_cache.reset_cache()


def tiny(**cfg):
    """The burst mix paced at 40 jobs/s: the window holds the same jobs
    (about 80, drawn from the seed) however fast the CPU decides them."""
    cell = spec.load_cell("tableI-h100-w500.burst.paced")
    c = dict(dict(cell.config, H=8, K=8, window=16, check_sample=1000),
             **cfg)
    t = dict(cell.traffic, warmup_slots=20, rate_jobs_per_s=40.0)
    return dataclasses.replace(cell, config=c, traffic=t)


def run_tiny(seed=7, **cfg):
    from bench.run import Run
    run = Run(tiny(**cfg), seed, 2.0, False, require_tpu=False,
              log=lambda *a: None, control=bool(cfg))
    return run.execute(), run.control_numbers


@pytest.mark.parametrize("rehearse", [True, False])
def test_the_rehearsal_leaves_no_compile_in_the_window(rehearse,
                                                       isolated_cache,
                                                       monkeypatch):
    """With no program in memory or on disk, the rehearsal compiles every
    program the window meets before the window; without it the window
    compiles them."""
    import jax
    from bench.run import Run
    if not rehearse:
        monkeypatch.setattr(Run, "_rehearse", lambda *a: None)
    jax.clear_caches()
    run = Run(tiny(), 11, 2.0, False, require_tpu=False,
              log=lambda *a: None)
    out = run.execute()
    assert out["correct"], out["checks"]
    assert run.counter.total_compiles > 0
    assert (run.counter.compiles == 0) is rehearse, run.counter.compiles


def _state_unchanged(monkeypatch):
    from repro.core.pricing import PriceState
    monkeypatch.setattr(PriceState, "_apply_deltas", lambda self, d: None)


def _half_batch(monkeypatch):
    from repro.core.oasis import OASiS
    orig = OASiS.on_arrivals

    def half(self, jobs):
        keep = (len(jobs) + 1) // 2
        return orig(self, jobs[:keep]) + [None] * (len(jobs) - keep)

    monkeypatch.setattr(OASiS, "on_arrivals", half)


def _answer_altered(monkeypatch):
    from repro.core.oasis import OASiS
    orig = OASiS._resolve

    def shifted(self, job, sched):
        if sched is not None:
            sched = dataclasses.replace(
                sched, workers={t: np.roll(y, 1)
                                for t, y in sched.workers.items()})
        return orig(self, job, sched)

    monkeypatch.setattr(OASiS, "_resolve", shifted)


@pytest.mark.parametrize("fault", [None, _state_unchanged, _half_batch,
                                   _answer_altered],
                         ids=["sound", "state_unchanged", "half_batch",
                              "answer_altered"])
def test_a_broken_timed_path_is_not_correct(fault, isolated_cache,
                                            monkeypatch):
    if fault is not None:
        fault(monkeypatch)
    out, _ = run_tiny()
    assert out["attempted"] >= 60
    assert out["correct"] is (fault is None), out["checks"]
    assert list(out)[-1] == "checks"


def test_the_bfloat16_control_is_not_correct(isolated_cache):
    """The reference in bfloat16 put in the program's place fails the
    limits at the decisions the judge samples, on a 50+50 fleet with a
    64-slot window, a size a test can hold (the program on the CPU decides
    in float64)."""
    out, control = run_tiny(seed=5, H=50, K=50, window=64)
    assert out["correct"], out["checks"]
    limits = tiny().config["limits"]
    assert not judge.is_correct(control, limits), control
