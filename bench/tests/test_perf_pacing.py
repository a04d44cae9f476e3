"""The pacing feed: its records on a fake clock, and the lookahead rule it
leans on, checked against the real ``engine.run_stream``."""
import dataclasses
import itertools

import pytest

from bench import generator, pacing


@dataclasses.dataclass
class J:
    jid: int
    arrival: int


class FakeClock:
    """Manual clock; with ``tick`` each reading advances it a little, so
    that readings taken in sequence are ordered."""

    def __init__(self, tick=0.0):
        self.now = 0.0
        self.tick = tick

    def __call__(self):
        self.now += self.tick
        return self.now

    def sleep(self, s):
        self.now += s


def drive(feed, clock, work):
    """Mimic run_stream's loop: gather a slot with one job of lookahead,
    then spend ``work[slot]`` seconds deciding it."""
    it = iter(feed)
    nxt = next(it, None)
    order = []
    while nxt is not None:
        t = nxt.arrival
        batch = []
        while nxt is not None and nxt.arrival == t:
            batch.append(nxt)
            nxt = next(it, None)
        clock.now += work.get(t, 0.0)
        order.append((t, [j.jid for j in batch]))
    feed.finish()
    return order


def jobs(slots):
    out, jid = [], 0
    for s, n in slots:
        for _ in range(n):
            out.append(J(jid, s))
            jid += 1
    return out


def test_paced_feed_times_each_slot_from_its_due_time():
    clock = FakeClock()
    js = jobs([(0, 1), (1, 2), (3, 1), (4, 1), (5, 2), (9, 1), (12, 1)])
    feed = pacing.PacedFeed(pacing.window_jobs(js, 3, 6), warm_slots=3,
                            slot_seconds=1.0, clock=clock, sleep=clock.sleep)
    order = drive(feed, clock, {0: 5.0, 1: 5.0, 3: 0.5, 4: 2.5, 5: 0.1})
    # slot 12 lies past the window (slots 3..8 are due in its 6 s)
    assert [t for t, _ in order] == [0, 1, 3, 4, 5]
    assert feed.t0 == 10.0 and feed.s0 == 3
    assert feed.window_slots() == [3, 4, 5]
    assert feed.due == {3: 10.0, 4: 11.0, 5: 12.0}
    # slot 3 runs 0.5 s, slot 4 waits until due at 11, runs 2.5 s; slot 5
    # was due at 12 and starts late at 13.5
    assert feed.release[4] == 11.0 and feed.release[5] == 13.5
    assert feed.done == {0: 5.0, 1: 10.0, 3: 10.5, 4: 13.5, 5: 13.6}
    assert feed.latencies() == pytest.approx([0.5, 2.5, 1.6, 1.6])
    assert feed.backlog() == [0, 0, 1]
    assert feed.lateness() == [0.0]
    assert feed.slept == pytest.approx(0.5)


def test_window_jobs_end_at_the_windows_last_slot():
    js = jobs([(0, 1), (1, 2), (3, 1), (4, 1), (5, 2), (8, 1), (9, 1)])
    # the window starts at slot 3, the first with arrivals from slot 2 on
    assert [j.arrival for j in pacing.window_jobs(js, 2, 6)] == [
        0, 1, 1, 3, 4, 5, 5, 8]
    assert [j.arrival for j in pacing.window_jobs(iter(js), 4, 1)] == [
        0, 1, 1, 3, 4]


def test_lookahead_rule_holds_in_run_stream():
    """Each slot is released after the engine finished the slot before it
    and before the engine starts deciding it; its completion is recorded
    after its decisions returned."""
    from repro.core.oasis import OASiS
    from repro.core.types import ClusterSpec, Job, SigmoidUtility
    from repro.sim import engine

    clock = FakeClock(tick=1e-6)
    calls = []
    orig = OASiS.on_arrivals

    def on_arrivals(self, batch):
        clock.now += 1.0
        start = clock.now
        out = orig(self, batch)
        clock.now += 1.0
        calls.append((batch[0].jid, start, clock.now, len(batch)))
        return out

    def to_job(s):
        return Job(jid=s.jid, arrival=s.arrival, epochs=s.epochs,
                   num_chunks=s.num_chunks,
                   minibatches_per_chunk=s.minibatches_per_chunk, tau=s.tau,
                   grad_size=s.grad_size, worker_bw=s.worker_bw,
                   ps_bw=s.ps_bw, worker_res=s.worker_res, ps_res=s.ps_res,
                   utility=SigmoidUtility(s.gamma1, s.gamma2, s.gamma3))

    specs = list(itertools.islice(generator.stream(
        5, rate=0.8, small=True, diurnal_amp=0.0, burst_prob=0.0), 40))
    slot_of = {s.jid: s.arrival for s in specs}
    w, p = generator.make_fleet(3, 3, 0)
    cluster = ClusterSpec(T=16, worker_caps=w, ps_caps=p)
    feed = pacing.PacedFeed((to_job(s) for s in specs), warm_slots=4,
                            slot_seconds=0.5, clock=clock, sleep=clock.sleep)
    # given params, run_stream pulls nothing ahead to estimate them
    params = engine.stream_price_params([to_job(s) for s in specs], cluster,
                                        16)
    OASiS.on_arrivals = on_arrivals
    try:
        r = engine.run_stream(cluster, feed, scheduler="oasis", impl="fast",
                              window=16, params=params)
    finally:
        OASiS.on_arrivals = orig
    feed.finish()
    assert r.n_jobs == len(specs)
    assert len(calls) == len(feed.release)
    prev_end = None
    for (jid, start, end, n), s in zip(calls, sorted(feed.release)):
        assert slot_of[jid] == s and n == len(feed.jobs[s])
        assert feed.release[s] < start            # released, then decided
        if prev_end is not None:
            assert feed.release[s] > prev_end     # after the slot before
        assert feed.done[s] >= end                # completion after return
        prev_end = end
    # completions are recorded at the next slot's release
    slots = sorted(feed.release)
    for a, b in zip(slots, slots[1:]):
        assert feed.done[a] <= feed.release[b]
