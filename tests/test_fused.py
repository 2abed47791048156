"""Fused spike+xcorr kernel: interpret-mode vs pure-jnp oracle, and
vs the spike-score oracle and the xcorr kernel it replaces."""
import jax.numpy as jnp
import numpy as np
import pytest

from repro.kernels.fused.ops import fused_rca, fused_rca_max
from repro.kernels.fused.ref import fused_rca_ref, spike_scores_ref
from repro.kernels.xcorr.ops import lagged_xcorr


@pytest.mark.parametrize("B,M,N,Nb,K", [
    (1, 1, 128, 128, 4), (2, 7, 500, 2000, 20), (3, 16, 512, 512, 20),
    (1, 33, 500, 1500, 31), (2, 5, 257, 300, 10),
])
def test_fused_matches_ref(B, M, N, Nb, K):
    rng = np.random.default_rng(B * 100 + M)
    L = rng.standard_normal((B, N)).astype(np.float32)
    Mx = (rng.standard_normal((B, M, N)) * 3 + 1).astype(np.float32)
    Bs = (rng.standard_normal((B, M, Nb)) * 2 + 10).astype(np.float32)
    s, rho = fused_rca(jnp.asarray(L), jnp.asarray(Mx), jnp.asarray(Bs), K,
                       use_kernel=True)
    s0, rho0 = fused_rca_ref(jnp.asarray(L), jnp.asarray(Mx),
                             jnp.asarray(Bs), K)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s0),
                               rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(np.asarray(rho), np.asarray(rho0),
                               rtol=1e-4, atol=1e-5)


def test_fused_equals_separate_kernels():
    """Fusion changes data movement, not results."""
    rng = np.random.default_rng(9)
    B, M, N, Nb, K = 2, 9, 512, 1024, 20
    L = rng.standard_normal((B, N)).astype(np.float32)
    Mx = rng.standard_normal((B, M, N)).astype(np.float32)
    Bs = (rng.standard_normal((B, M, Nb)) + 5).astype(np.float32)
    s, rho = fused_rca(jnp.asarray(L), jnp.asarray(Mx), jnp.asarray(Bs), K)
    s_sep = spike_scores_ref(jnp.asarray(Mx), jnp.asarray(Bs))
    rho_sep = lagged_xcorr(jnp.asarray(L), jnp.asarray(Mx), K,
                           use_kernel=True)
    np.testing.assert_allclose(np.asarray(s), np.asarray(s_sep),
                               rtol=1e-5, atol=1e-5)
    np.testing.assert_allclose(np.asarray(rho), np.asarray(rho_sep),
                               rtol=1e-5, atol=1e-6)


def test_fused_max_recovers_lag_and_spike():
    rng = np.random.default_rng(1)
    N, K = 512, 20
    sig = rng.standard_normal(N + K)
    L = sig[:N][None].astype(np.float32)
    M = np.zeros((1, 2, N), np.float32)
    M[0, 0] = sig[5:N + 5]                  # leads latency by 5 samples
    M[0, 1] = rng.standard_normal(N)
    Bs = rng.standard_normal((1, 2, 256)).astype(np.float32)
    Bs[0, 0] -= sig[:256] * 0               # keep baseline quiet
    s, c, lags = fused_rca_max(jnp.asarray(L), jnp.asarray(M),
                               jnp.asarray(Bs), K)
    assert int(lags[0, 0]) == 5
    assert float(c[0, 0]) > 0.9
    assert np.all(np.isfinite(np.asarray(s)))
