"""Open-loop pacing of the job stream handed to ``engine.run_stream``.

``run_stream`` reads its iterable lazily with a one-job lookahead: while
it gathers slot ``a``'s arrivals it pulls one job past them, the first
job of the next slot with arrivals, and only then decides slot ``a``.
So the first pull after the feed has handed out a slot's first job is the
moment the engine has finished the previous slot and begins this one.
At that pull the feed

1. records the previous slot's completion time,
2. sleeps until this slot is due, if it is not due yet,
3. records the slot's release time,

and every job of slot ``a`` then waits ``done[a] - due[a]``: the engine
returns a slot's decisions together.  ``bench/tests`` checks the rule
against the real ``run_stream``.

Slots before ``warm_slots`` pass unpaced (warm-up).  The window starts at
the release of the first later slot ``s0``; slot ``s`` is due at
``t0 + (s - s0) * slot_seconds``.  A run hands the feed the jobs of
:func:`window_jobs`: the stream up to the window's last slot.
"""
from __future__ import annotations

import bisect
import time
from typing import Callable, Dict, Iterable, List, Optional


def window_jobs(jobs: Iterable, warm_slots: int, window_slots: int) -> list:
    """The jobs of a stream (in arrival order) from its start through the
    window: the ``window_slots`` slots from the first slot with arrivals at
    or after ``warm_slots``."""
    out = []
    end = None
    for job in jobs:
        s = int(job.arrival)
        if end is None and s >= warm_slots:
            end = s + int(window_slots)
        if end is not None and s >= end:
            break
        out.append(job)
    return out


class PacedFeed:
    """Iterator over jobs (anything with ``.arrival``) that paces slots."""

    def __init__(self, jobs: Iterable, *, warm_slots: int,
                 slot_seconds: float,
                 clock: Callable[[], float] = time.perf_counter,
                 sleep: Callable[[float], None] = time.sleep,
                 on_window_start: Optional[Callable[[], None]] = None):
        self._it = iter(jobs)
        self.warm_slots = int(warm_slots)
        self.slot_seconds = float(slot_seconds)
        self._clock = clock
        self._sleep = sleep
        self._on_window_start = on_window_start
        self.due: Dict[int, float] = {}
        self.release: Dict[int, float] = {}
        self.done: Dict[int, float] = {}
        self.jobs: Dict[int, List[int]] = {}    # slot -> jids handed out
        self.slept = 0.0
        self.waits: List[tuple] = []            # (start, end) of each sleep
        self.t0: Optional[float] = None
        self.s0: Optional[int] = None
        self._armed: Optional[int] = None       # handed out, not yet begun
        self._current: Optional[int] = None     # begun last
        self._last_slot: Optional[int] = None

    def __iter__(self) -> "PacedFeed":
        return self

    def _begin(self, s: int) -> None:
        now = self._clock()
        if self._current is not None:
            self.done[self._current] = now
        if s >= self.warm_slots:
            if self.t0 is None:
                if self._on_window_start is not None:
                    self._on_window_start()
                now = self._clock()
                self.t0, self.s0 = now, s
            due = self.t0 + (s - self.s0) * self.slot_seconds
            if due > now:
                self._sleep(due - now)
                self.slept += due - now
                self.waits.append((now, due))
                now = self._clock()
            self.due[s] = due
        self.release[s] = now
        self._current = s

    def __next__(self):
        if self._armed is not None:
            s, self._armed = self._armed, None
            self._begin(s)
        job = next(self._it)
        s = int(job.arrival)
        if s != self._last_slot:
            self._armed = self._last_slot = s
        self.jobs.setdefault(s, []).append(job.jid)
        return job

    def finish(self) -> None:
        """Record the completion of the last slot once the engine returned."""
        if self._current is not None and self._current not in self.done:
            self.done[self._current] = self._clock()

    # -- the window's record ----------------------------------------------
    def window_slots(self) -> List[int]:
        return sorted(s for s in self.due if s in self.done)

    def latencies(self) -> List[float]:
        """Seconds from due to decided, one entry per job of the window."""
        out: List[float] = []
        for s in self.window_slots():
            out += [self.done[s] - self.due[s]] * len(self.jobs[s])
        return out

    def lateness(self) -> List[float]:
        """Seconds each window slot was released after it was due while the
        engine was idle: how late the generator itself ran."""
        out = []
        prev_done = None
        for s in self.window_slots():
            if prev_done is not None and prev_done <= self.due[s]:
                out.append(self.release[s] - self.due[s])
            prev_done = self.done[s]
        return out

    def backlog(self) -> List[int]:
        """At each window slot's due time, how many earlier-due slots were
        still undecided."""
        slots = self.window_slots()
        done = [self.done[s] for s in slots]     # nondecreasing: one engine
        return [i - bisect.bisect_right(done, self.due[s], 0, i)
                for i, s in enumerate(slots)]
