"""Per-kernel validation: shape/dtype sweeps, interpret-mode vs pure-jnp
oracle (the required allclose contract for every Pallas kernel)."""
import jax
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.xcorr.ops import lagged_xcorr, max_abs_xcorr
from repro.kernels.xcorr.ref import lagged_xcorr_ref, max_abs_xcorr_ref


@pytest.mark.parametrize("B,M,N,K", [
    (1, 1, 128, 4), (2, 7, 500, 20), (3, 16, 512, 20),
    (1, 33, 500, 31), (4, 8, 1024, 20), (2, 5, 257, 10),
])
def test_xcorr_matches_ref(B, M, N, K):
    rng = np.random.default_rng(B * 1000 + M)
    L = rng.standard_normal((B, N)).astype(np.float32)
    Mx = (rng.standard_normal((B, M, N)) * 3 + 1).astype(np.float32)
    got = lagged_xcorr(jnp.asarray(L), jnp.asarray(Mx), K, use_kernel=True)
    want = lagged_xcorr_ref(jnp.asarray(L), jnp.asarray(Mx), K)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=1e-4, atol=1e-5)


@pytest.mark.parametrize("dtype", [np.float32, np.float64, np.float16])
def test_xcorr_dtypes(dtype):
    rng = np.random.default_rng(0)
    L = rng.standard_normal((2, 256)).astype(dtype)
    Mx = rng.standard_normal((2, 4, 256)).astype(dtype)
    got = lagged_xcorr(jnp.asarray(L), jnp.asarray(Mx), 8, use_kernel=True)
    want = lagged_xcorr_ref(jnp.asarray(L, jnp.float32),
                            jnp.asarray(Mx, jnp.float32), 8)
    np.testing.assert_allclose(np.asarray(got), np.asarray(want),
                               rtol=5e-3, atol=5e-3)


def test_xcorr_recovers_lag_batched():
    rng = np.random.default_rng(1)
    N, K = 512, 20
    sig = rng.standard_normal(N + K)
    L = np.stack([sig[:N], rng.standard_normal(N)]).astype(np.float32)
    M = np.zeros((2, 2, N), np.float32)
    M[0, 0] = sig[5:N + 5]      # leads host-0 latency by 5
    M[0, 1] = rng.standard_normal(N)
    M[1] = rng.standard_normal((2, N))
    c, lags = max_abs_xcorr(jnp.asarray(L), jnp.asarray(M), K)
    assert int(lags[0, 0]) == 5
    assert float(c[0, 0]) > 0.9


def test_engine_and_kernel_agree():
    """The numpy engine's Layer-3 math == the batched kernel path."""
    from repro.core.xcorr import lagged_xcorr as np_xcorr
    rng = np.random.default_rng(5)
    L = rng.standard_normal(500)
    M = rng.standard_normal((6, 500))
    want = np_xcorr(L, M, 20)                       # numpy per-host engine
    got = lagged_xcorr(jnp.asarray(L[None]), jnp.asarray(M[None]), 20,
                       use_kernel=True)[0]
    np.testing.assert_allclose(np.asarray(got), want, rtol=1e-4, atol=1e-5)
