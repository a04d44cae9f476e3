"""Decide ``correct``: replay the decisions the timed run made against the
float64 reference (``reference.py``) on the identical price state.

The harness records every ``on_arrivals`` call the engine makes (its jobs
and which it accepted) and the placements of every commit.  After the
window the judge replays them in order on its own price state: it slides
the window to each slot, judges each decision there, and then commits
the program's own schedule, so that every later decision is judged on the
state the program had.  Every accepted schedule is judged (float64 payoff, whole
workload placed, per-slot constraints, capacity after the commit); a
sample of the window's decisions, drawn from the seed, is also decided
by the reference and compared (accept or reject, and payoff).
"""
from __future__ import annotations

import contextlib
import dataclasses
import math
from typing import Dict, List, Optional, Sequence, Set

import numpy as np

from bench import reference as ref

NUMBERS = ("flip_margin", "payoff_gap", "negative_payoff", "capacity_excess",
           "unit_shortfall", "breaches", "utility_gap", "undecided")
# numbers a decider substituted for the program (the control) can be
# judged by at the sampled decisions
SAMPLED = ("flip_margin", "payoff_gap", "negative_payoff",
           "capacity_excess", "unit_shortfall", "breaches")


@dataclasses.dataclass
class Call:
    slot: int                       # absolute slot of the decisions
    jids: List[int]
    accepted: List[bool]


@dataclasses.dataclass(frozen=True)
class Placed:
    """A schedule's placements as they were when the program returned it."""

    workers: Dict[int, np.ndarray]
    ps: Dict[int, np.ndarray]


def _frozen(workers: dict, ps: dict) -> Placed:
    return Placed({int(t): np.array(y, copy=True) for t, y in workers.items()},
                  {int(t): np.array(z, copy=True) for t, z in ps.items()})


class DecisionLog:
    """Records, while installed, each ``OASiS.on_arrivals`` call (jobs and
    which were accepted) and each ``PriceState.commit`` (the placements,
    copied as committed: the program's arrays may alias device buffers it
    reuses before the call returns)."""

    def __init__(self, slot_of: Dict[int, int]):
        self.calls: List[Call] = []
        self.placed: Dict[int, Placed] = {}
        self._slot_of = slot_of

    @contextlib.contextmanager
    def installed(self, oasis_cls, state_cls):
        arrive, commit = oasis_cls.on_arrivals, state_cls.commit
        calls, placed, slot_of = self.calls, self.placed, self._slot_of

        def on_arrivals(sched_self, jobs):
            out = arrive(sched_self, jobs)
            jids = [j.jid for j in jobs]
            calls.append(Call(slot_of[jids[0]] if jids else -1, jids,
                              [s is not None for s in out]))
            return out

        def record_commit(state_self, job, workers, ps):
            placed[job.jid] = _frozen(workers, ps)
            return commit(state_self, job, workers, ps)

        oasis_cls.on_arrivals = on_arrivals
        state_cls.commit = record_commit
        try:
            yield self
        finally:
            oasis_cls.on_arrivals = arrive
            state_cls.commit = commit


@dataclasses.dataclass
class _Acc:
    """Running maxima of the compared numbers."""

    flip_margin: float = 0.0
    payoff_gap: float = 0.0
    negative_payoff: float = 0.0
    capacity_excess: float = 0.0
    unit_shortfall: float = 0.0
    breaches: float = 0.0
    utility_gap: float = 0.0
    undecided: float = 0.0
    sampled: int = 0
    flips: int = 0

    def schedule(self, v: ref.Verdict, excess: float) -> None:
        self.negative_payoff = max(self.negative_payoff, -v.payoff)
        self.unit_shortfall = max(self.unit_shortfall, v.unit_shortfall)
        self.breaches += v.breaches
        self.capacity_excess = max(self.capacity_excess, excess)

    def compare(self, best: Optional[ref.RefSchedule],
                judged: Optional[ref.Verdict]) -> None:
        self.sampled += 1
        if (best is None) != (judged is None):
            self.flips += 1
            margin = best.payoff if best is not None else abs(judged.payoff)
            self.flip_margin = max(self.flip_margin, margin)
        elif best is not None:
            self.payoff_gap = max(self.payoff_gap, best.payoff - judged.payoff)

    def numbers(self, names: Sequence[str]) -> Dict[str, float]:
        return {n: float(getattr(self, n)) for n in names}


def _excess_if_placed(state: ref.RefState, job: ref.RefJob,
                      workers: dict, ps: dict) -> float:
    out = 0.0
    for host, caps, alloc, res in ((state.g, state.wcaps, workers,
                                    job.worker_res),
                                   (state.v, state.scaps, ps, job.ps_res)):
        for t, cnt in alloc.items():
            use = host[t] + np.asarray(cnt, np.float64)[:, None] * res[None]
            out = max(out, float(np.max(use - caps)))
    return out


def sample_jids(window_jids: Sequence[int], specs: Dict[int, object],
                k: int, seed: int) -> Set[int]:
    """``k`` window decisions drawn from the seed, always with the job of
    the largest workload among them."""
    jids = sorted(window_jids)
    if not jids:
        return set()
    rng = np.random.default_rng([int(seed) & 0xFFFFFFFFFFFFFFFF, 0x6A75])
    pick = set(int(j) for j in rng.choice(jids, size=min(k, len(jids)),
                                          replace=False))
    pick.add(max(jids, key=lambda j: specs[j].epochs * specs[j].num_chunks))
    return pick


def judge(calls: Sequence[Call], placed: Dict[int, Placed],
          specs: Dict[int, object], worker_caps: np.ndarray, ps_caps: np.ndarray, window: int,
          quantum: int, window_jids: Set[int], sample: Set[int],
          program_utility: float, program_accepted: int,
          params_jobs: Sequence[object], control: bool = False):
    """Numbers of the program's run (``NUMBERS``), and with ``control`` the
    numbers the bfloat16 reference would get at the sampled decisions
    (``SAMPLED``), else None.  ``params_jobs`` are the stream's first jobs,
    which the price bounds are estimated from."""
    params = ref.price_params([ref.RefJob.from_spec(s) for s in params_jobs],
                              worker_caps, ps_caps, window)
    state = ref.RefState(worker_caps, ps_caps, params, window)
    prog, ctl = _Acc(), _Acc()
    total_utility = 0.0
    accepted = 0
    decided: Set[int] = set()
    for call in calls:
        state.advance(call.slot)
        for jid, acc in zip(call.jids, call.accepted):
            sched = placed.get(jid) if acc else None
            job = ref.RefJob.from_spec(specs[jid], quantum)
            decided.add(jid)
            verdict = None
            if sched is not None:
                verdict = ref.evaluate(job, state, sched.workers, sched.ps)
            if jid in sample:
                best = ref.alg2(job, state)
                prog.compare(best, verdict)
                if control:
                    mine = ref.alg2(job, state, "bfloat16")
                    cv = None
                    if mine is not None:
                        cv = ref.evaluate(job, state, mine.workers, mine.ps)
                        ctl.schedule(cv, _excess_if_placed(
                            state, job, mine.workers, mine.ps))
                    ctl.compare(best, cv)
            if acc and sched is None:
                prog.undecided += 1         # accepted, never committed
            if sched is not None:
                excess = state.commit(job, sched.workers, sched.ps)
                if jid in window_jids:
                    prog.schedule(verdict, excess)
                else:
                    prog.capacity_excess = max(prog.capacity_excess, excess)
                total_utility += verdict.utility
                accepted += 1
    prog.utility_gap = abs(program_utility - total_utility) / max(
        abs(total_utility), 1.0)
    prog.undecided += len(window_jids - decided) + abs(program_accepted
                                                       - accepted)
    return (prog.numbers(NUMBERS), prog.sampled, prog.flips,
            ctl.numbers(SAMPLED) if control else None)


def verdict_line(numbers: Dict[str, float], limits: Dict[str, float]
                 ) -> Dict[str, Dict[str, float]]:
    return {n: {"value": numbers[n], "limit": limits[n]} for n in numbers}


def is_correct(numbers: Dict[str, float], limits: Dict[str, float]) -> bool:
    return all(math.isfinite(v) and v <= limits[n]
               for n, v in numbers.items())
