"""Per-kernel shape/dtype sweeps vs the pure-jnp oracles (interpret mode)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.flash_attention.kernel import flash_attention
from repro.kernels.flash_attention.ref import attention_ref
from repro.kernels.minplus.kernel import minplus_sweep_pallas
from repro.kernels.minplus.ops import minplus
from repro.kernels.minplus.ref import minplus_ref, minplus_sweep_ref
from repro.kernels.ssd.ops import ssd_op
from repro.kernels.ssd.ref import ssd_ref

KEY = jax.random.PRNGKey(0)


@pytest.mark.parametrize("B,Sq,Sk,H,KV,D", [
    (1, 64, 64, 2, 2, 64),
    (2, 128, 128, 4, 2, 64),
    (1, 130, 130, 4, 1, 128),     # ragged seq (padding path)
    (2, 96, 96, 8, 4, 256),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
@pytest.mark.parametrize("causal,window,cap", [
    (True, 0, 0.0), (True, 32, 0.0), (True, 0, 50.0), (False, 0, 0.0)])
def test_flash_attention_sweep(B, Sq, Sk, H, KV, D, dtype, causal, window, cap):
    ks = jax.random.split(KEY, 3)
    q = jax.random.normal(ks[0], (B, Sq, H, D), dtype)
    k = jax.random.normal(ks[1], (B, Sk, KV, D), dtype)
    v = jax.random.normal(ks[2], (B, Sk, KV, D), dtype)
    got = flash_attention(q, k, v, causal=causal, window=window, softcap=cap,
                          interpret=True)
    want = attention_ref(q, k, v, causal=causal, window=window, softcap=cap)
    tol = 2e-2 if dtype == jnp.bfloat16 else 2e-5
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


@pytest.mark.parametrize("d1,dc1", [(5, 2), (64, 8), (129, 17), (1000, 100),
                                    (4097, 257)])
@pytest.mark.parametrize("inf_frac", [0.0, 0.3])
def test_minplus_sweep(d1, dc1, inf_frac):
    rng = np.random.default_rng(d1)
    prev = rng.random(d1).astype(np.float32)
    row = rng.random(dc1).astype(np.float32)
    prev[rng.random(d1) < inf_frac] = np.inf
    row[rng.random(dc1) < inf_frac] = np.inf
    prev[0] = 0.0
    row[0] = 0.0
    o1, a1 = minplus(jnp.array(row), jnp.array(prev))     # the sweep kernel at T=1
    o2, a2 = minplus_ref(jnp.array(row), jnp.array(prev))
    v1, v2 = np.asarray(o1), np.asarray(o2)
    assert np.all((np.isinf(v1) & np.isinf(v2)) | (np.abs(v1 - v2) < 1e-5))
    assert np.array_equal(np.asarray(a1), np.asarray(a2))


@pytest.mark.parametrize("T,dc1,d1", [(3, 2, 6), (9, 17, 33), (16, 65, 300)])
@pytest.mark.parametrize("inf_frac", [0.0, 0.4])
def test_minplus_sweep_fused_kernel(T, dc1, d1, inf_frac):
    """The single-launch T-slot sweep (grid over slots, carried row in VMEM
    scratch) == a lax.scan of per-slot min-plus convolutions."""
    rng = np.random.default_rng(T * d1)
    rows = rng.random((T, dc1)).astype(np.float32)
    rows[rng.random((T, dc1)) < inf_frac] = np.inf
    rows[:, 0] = 0.0
    c1, a1 = minplus_sweep_pallas(jnp.array(rows), d1 - 1, interpret=True)
    c2, a2 = minplus_sweep_ref(jnp.array(rows), d1 - 1)
    v1, v2 = np.asarray(c1), np.asarray(c2)
    assert np.all((np.isinf(v1) & np.isinf(v2)) | (np.abs(v1 - v2) < 1e-5))
    assert np.array_equal(np.asarray(a1), np.asarray(a2))


@pytest.mark.parametrize("b,L,H,P,G,N,chunk", [
    (1, 32, 2, 16, 1, 16, 16),
    (2, 64, 4, 32, 2, 32, 32),
    (1, 100, 4, 64, 1, 64, 64),   # ragged length (padding path)
    (2, 256, 8, 64, 4, 128, 128),
])
@pytest.mark.parametrize("dtype", [jnp.float32, jnp.bfloat16])
def test_ssd_sweep(b, L, H, P, G, N, chunk, dtype):
    ks = jax.random.split(KEY, 5)
    x = (jax.random.normal(ks[0], (b, L, H, P)) * 0.5).astype(dtype)
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, H))).astype(dtype)
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = (jax.random.normal(ks[3], (b, L, G, N)) * 0.3).astype(dtype)
    C = (jax.random.normal(ks[4], (b, L, G, N)) * 0.3).astype(dtype)
    got = ssd_op(x, dt, A, B, C, chunk=chunk, use_pallas=True)
    rep = H // G
    Bh = jnp.repeat(B[:, :, :, None, :], rep, 3).reshape(b, L, H, N)
    Ch = jnp.repeat(C[:, :, :, None, :], rep, 3).reshape(b, L, H, N)
    want = ssd_ref(
        x.transpose(0, 2, 1, 3).reshape(b * H, L, P).astype(jnp.float32),
        dt.transpose(0, 2, 1).reshape(b * H, L).astype(jnp.float32),
        jnp.tile(A, b),
        Bh.transpose(0, 2, 1, 3).reshape(b * H, L, N).astype(jnp.float32),
        Ch.transpose(0, 2, 1, 3).reshape(b * H, L, N).astype(jnp.float32))
    want = want.reshape(b, H, L, P).transpose(0, 2, 1, 3)
    tol = 5e-2 if dtype == jnp.bfloat16 else 1e-3
    np.testing.assert_allclose(np.asarray(got, np.float32),
                               np.asarray(want, np.float32), atol=tol, rtol=tol)


def test_model_chunked_ssd_matches_kernel():
    """models.mamba2.ssd_chunked (XLA path) == Pallas kernel == sequential."""
    from repro.models.mamba2 import ssd_chunked
    ks = jax.random.split(KEY, 5)
    b, L, H, P, G, N = 2, 96, 4, 32, 1, 32
    x = jax.random.normal(ks[0], (b, L, H, P)) * 0.5
    dt = jax.nn.softplus(jax.random.normal(ks[1], (b, L, H)))
    A = -jnp.exp(jax.random.normal(ks[2], (H,)) * 0.3)
    B = jax.random.normal(ks[3], (b, L, G, N)) * 0.3
    C = jax.random.normal(ks[4], (b, L, G, N)) * 0.3
    y_model, _ = ssd_chunked(x, dt, A, B, C, 32)
    y_kernel = ssd_op(x, dt, A, B, C, chunk=32, use_pallas=True)
    np.testing.assert_allclose(np.asarray(y_model), np.asarray(y_kernel),
                               atol=1e-4, rtol=1e-4)


@pytest.mark.parametrize("T,dc1,d1,start", [
    (64, 7, 21, 0), (128, 9, 33, 37), (192, 33, 129, 64),
    (128, 600, 800, 5),            # wide band: block-scan chain path
    (50, 7, 21, 0),                # rolling serving window: T % tile != 0
    (100, 9, 33, 12),              # partial trailing tile + dynamic start
    (129, 5, 17, 64),              # one slot past a tile boundary
])
@pytest.mark.parametrize("inf_frac", [0.0, 0.4])
def test_minplus_sweep_tiled_matches_cost(T, dc1, d1, start, inf_frac):
    """Horizon-tiled while_loop sweep == the unrolled full sweep, bit for
    bit, including a dynamic start tile over identity-prefix rows."""
    from repro.kernels.minplus.ref import minplus_sweep_cost
    from repro.kernels.minplus.tiled import minplus_sweep_tiled
    rng = np.random.default_rng(T + dc1 + start)
    rows = rng.random((T, dc1)).astype(np.float64)
    rows[rng.random((T, dc1)) < inf_frac] = np.inf
    rows[:, 0] = 0.0
    rows[:start, 1:] = np.inf              # identity prefix (pre-arrival)
    got = np.asarray(minplus_sweep_tiled(jnp.asarray(rows), d1 - 1,
                                         tile=64, start=start))
    want = np.asarray(minplus_sweep_cost(jnp.asarray(rows), d1 - 1))
    assert np.array_equal(got[start:], want[start:])


def test_minplus_chain_step_batched_lanes():
    """The lane-batched chain step equals per-lane reference sweeps."""
    from repro.kernels.minplus.tiled import minplus_chain_step
    rng = np.random.default_rng(5)
    B, dc1, d1 = 5, 11, 29
    row = rng.random((B, dc1)).astype(np.float32)
    prev = rng.random((B, d1)).astype(np.float32)
    row[rng.random((B, dc1)) < 0.3] = np.inf
    row[:, 0] = 0.0
    got = np.asarray(minplus_chain_step(jnp.asarray(row), jnp.asarray(prev)))
    for b in range(B):
        # direct oracle: new[d] = min_j row[j] + prev[d - j], f32 like the op
        want = np.full(d1, np.inf, np.float32)
        for d in range(d1):
            for j in range(min(dc1, d + 1)):
                want[d] = min(want[d], np.float32(row[b, j] + prev[b, d - j]))
        assert np.array_equal(got[b], want)
