"""Random price-state mutations shared by the cache-equivalence tests."""
import numpy as np


def _rand_alloc(rng, T, S, max_count=2):
    """A random slot->counts allocation dict over a contiguous range."""
    t0 = int(rng.integers(0, T))
    t1 = int(rng.integers(t0, min(t0 + 6, T)))
    return {t: rng.integers(0, max_count + 1, size=S).astype(np.int64)
            for t in range(t0, t1 + 1)}


def apply_random_ops(rng, state, jobs, committed, n_ops, allow_advance):
    """Mutate ``state`` with a random commit/release/advance sequence."""
    T = state.horizon
    H, K = state.cluster.H, state.cluster.K
    for _ in range(n_ops):
        op = rng.integers(0, 3 if allow_advance else 2)
        if op == 0:                                # commit
            job = jobs[int(rng.integers(0, len(jobs)))]
            w = _rand_alloc(rng, T, H)
            z = _rand_alloc(rng, T, K, max_count=1)
            state.commit(job, w, z)
            committed.append((job, w, z))
        elif op == 1 and committed:                # release an earlier commit
            job, w, z = committed.pop(int(rng.integers(0, len(committed))))
            state.release(job, w, z)
        elif op == 2:                              # slide the window
            state.advance(state.origin + int(rng.integers(1, 4)))
            committed.clear()                      # slots re-indexed
