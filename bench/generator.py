"""Seeded fleet and job-stream generator of the benchmark.

A copy of the program's ``sim/workload.py`` (``make_cluster``,
``_sample_job``, ``stream_jobs``), kept here so that the yardstick does
not move when the program's generator changes.  It yields plain
``JobSpec`` records; ``bench/tests`` checks that they equal the
program's jobs for the same seed, draw for draw.

Paper settings (arXiv:1801.00936 Sec. V-A, Table I): EC2-C4-like worker
servers with 8 GPUs, P2/G3-like parameter servers, and job parameters
drawn from the Table-I ranges.
"""
from __future__ import annotations

import dataclasses
import math
from typing import Iterator, List, Optional, Sequence, Tuple

import numpy as np

# resource order: gpu, cpu, mem(GB), storage(GB), bw(Gbps)
C4_LIKE = np.array([8.0, 36.0, 60.0, 400.0, 25.0])
P2_LIKE = np.array([0.0, 64.0, 488.0, 800.0, 25.0])
G3_LIKE = np.array([0.0, 64.0, 488.0, 800.0, 50.0])


@dataclasses.dataclass(frozen=True)
class JobSpec:
    """One Table-I job, as drawn; the fields the program's ``Job`` has."""

    jid: int
    arrival: int
    epochs: int
    num_chunks: int
    minibatches_per_chunk: int
    tau: float
    grad_size: float
    worker_bw: float
    ps_bw: float
    worker_res: np.ndarray
    ps_res: np.ndarray
    gamma1: float
    gamma2: float
    gamma3: float


def make_fleet(H: int, K: int, seed: int = 0,
               scale: float = 1.0) -> Tuple[np.ndarray, np.ndarray]:
    """(worker_caps (H, 5), ps_caps (K, 5)); each PS server is P2- or
    G3-like with even odds, from ``seed``."""
    rng = np.random.default_rng(seed)
    worker_caps = np.tile(C4_LIKE, (H, 1)) * scale
    ps_rows = [(P2_LIKE if rng.random() < 0.5 else G3_LIKE)
               for _ in range(K)]
    ps_caps = np.stack(ps_rows) * scale
    ps_caps[:, 0] = 0.0
    return worker_caps, ps_caps


def sample_job(jid: int, arrival: int, rng: np.random.Generator,
               small: bool, time_insensitive: float,
               time_sensitive: float) -> JobSpec:
    """One job from the Table-I ranges, in the program's draw order."""
    if small:
        E = int(rng.integers(1, 4))
        N = int(rng.integers(1, 5))
        M = int(rng.integers(5, 20))
    else:
        E = int(rng.integers(50, 201))
        N = int(rng.integers(5, 101))
        M = int(rng.integers(10, 101))
    tau = float(rng.uniform(0.001, 0.1))
    e = float(rng.uniform(30, 575)) / 1000.0
    b = float(rng.uniform(0.1, 5.0))
    B = float(rng.uniform(5.0, 20.0))
    # fastest duration E*M*(tau+2e/b) scaled into [2, 16] slots
    ct = M * (tau + 2 * e / b)
    min_dur = E * ct
    target = float(rng.uniform(2.0, 16.0))
    target = min(target, 0.9 * E)
    scale = target / min_dur
    tau *= scale
    e *= scale
    w = np.array([float(rng.integers(0, 5)), float(rng.integers(1, 11)),
                  float(rng.uniform(2, 32)), float(rng.uniform(5, 10)), b])
    s = np.array([0.0, float(rng.integers(1, 11)),
                  float(rng.uniform(2, 32)), float(rng.uniform(5, 10)), B])
    u = rng.random()
    gamma1 = float(rng.uniform(1, 100))
    if u < time_insensitive:
        gamma2 = 0.0
    elif u < time_insensitive + time_sensitive:
        gamma2 = float(rng.uniform(0.01, 1.0))
    else:
        gamma2 = float(rng.uniform(4.0, 6.0))
    min_dur_slots = max(1.0, target - 1.0)
    gamma3 = float(np.clip(min_dur_slots * rng.uniform(1.0, 2.5), 1, 40))
    return JobSpec(jid=jid, arrival=arrival, epochs=E, num_chunks=N,
                   minibatches_per_chunk=M, tau=tau, grad_size=e,
                   worker_bw=b, ps_bw=B, worker_res=w, ps_res=s,
                   gamma1=gamma1, gamma2=gamma2, gamma3=gamma3)


def stream(seed: int, rate: float, max_slots: Optional[int] = None, *,
           diurnal_period: int = 288, diurnal_amp: float = 0.6,
           burst_prob: float = 0.01, burst_mean_len: int = 12,
           burst_tail: float = 1.5, burst_cap: float = 8.0,
           small: bool = False, time_insensitive: float = 0.10,
           time_sensitive: float = 0.55) -> Iterator[JobSpec]:
    """Jobs in arrival order from per-slot Poisson counts with intensity
    ``rate * (1 + diurnal_amp sin(2 pi t / diurnal_period)) * burst(t)``;
    a burst episode starts with probability ``burst_prob`` per slot, lasts
    a geometric ``burst_mean_len`` slots and multiplies the rate by
    ``min(1 + Pareto(burst_tail), burst_cap)``."""
    rng = np.random.default_rng(seed)
    jid = 0
    t = 0
    burst_left = 0
    burst_amp = 1.0
    while max_slots is None or t < max_slots:
        if burst_left == 0 and rng.random() < burst_prob:
            burst_left = int(rng.geometric(1.0 / max(burst_mean_len, 1)))
            burst_amp = float(min(1.0 + rng.pareto(burst_tail), burst_cap))
        mult = burst_amp if burst_left > 0 else 1.0
        if burst_left > 0:
            burst_left -= 1
        lam = rate * (1.0 + diurnal_amp
                      * math.sin(2.0 * math.pi * t / diurnal_period)) * mult
        for _ in range(int(rng.poisson(max(lam, 0.0)))):
            yield sample_job(jid, t, rng, small, time_insensitive,
                             time_sensitive)
            jid += 1
        t += 1


def reordered(jobs: Sequence[JobSpec], seed: int) -> List[JobSpec]:
    """The same jobs at the same arrival slots, in an order drawn from
    ``seed``: position ``i`` keeps its slot and gets the sizes and utility
    of another job; job ids follow arrival order."""
    order = np.random.default_rng(int(seed) & 0xFFFFFFFFFFFFFFFF
                                  ).permutation(len(jobs))
    return [dataclasses.replace(jobs[int(k)], jid=i,
                                arrival=jobs[i].arrival)
            for i, k in enumerate(order)]
