"""Pure-jnp oracle for the fused spike+xcorr kernel.

Composes a batched spike-score oracle with the xcorr oracle — proving the
fusion changes data movement, not math.  ``fused_rca_masked_ref`` is the
ragged-row variant (per-row valid lengths) behind the event-batched
Layer-3 path.
"""
from __future__ import annotations

import jax
import jax.numpy as jnp

from repro.core.spike import (
    MASK_NEG as NEG, SIGMA_FLOOR_ABS, SIGMA_FLOOR_REL,
)
from repro.kernels.xcorr.ref import lagged_xcorr_ref

_EPS = 1e-12


def spike_scores_ref(windows: jax.Array, baselines: jax.Array) -> jax.Array:
    """windows (B, M, N), baselines (B, M, Nb) -> scores (B, M) f32.

    S = max_t (w(t) - mu_b) / max(sigma_b, floor)   (one-sided rise).
    """
    w = windows.astype(jnp.float32)
    b = baselines.astype(jnp.float32)
    mu = b.mean(axis=-1)
    sd = b.std(axis=-1)
    floor = jnp.maximum(SIGMA_FLOOR_ABS, SIGMA_FLOOR_REL * jnp.abs(mu))
    sd = jnp.maximum(sd, floor)
    return ((w - mu[..., None]) / sd[..., None]).max(axis=-1)


def fused_rca_ref(latency: jax.Array, metrics: jax.Array,
                  baselines: jax.Array, max_lag: int,
                  ) -> tuple[jax.Array, jax.Array]:
    """latency (B, N), metrics (B, M, N), baselines (B, M, Nb) ->
    (scores (B, M), rho (B, M, 2K+1)) f32."""
    scores = spike_scores_ref(metrics, baselines)
    rho = lagged_xcorr_ref(latency, metrics, max_lag)
    return scores, rho


def fused_rca_masked_ref(latency: jax.Array, metrics: jax.Array,
                         baselines: jax.Array, n_valid: jax.Array,
                         nb_valid: jax.Array, max_lag: int,
                         ) -> tuple[jax.Array, jax.Array]:
    """Ragged-row oracle: rows are left-aligned with zero tails and
    ``n_valid``/``nb_valid`` (B,) int32 give true lengths per row.

    Same math as composing spike_scores_ref + lagged_xcorr_ref on each
    row's valid prefix: baseline moments over the valid baseline samples,
    max-z over the valid window, and overlap-only lag products normalized
    by full-(valid-)window energies.
    """
    B, Mm, N = metrics.shape
    Nb = baselines.shape[-1]
    K = int(max_lag)
    L = latency.astype(jnp.float32)
    Mx = metrics.astype(jnp.float32)
    Bs = baselines.astype(jnp.float32)
    nv = n_valid.astype(jnp.float32)[:, None]                   # (B, 1)
    nbv = nb_valid.astype(jnp.float32)[:, None]
    tmask = (jnp.arange(N)[None, :] < n_valid[:, None]
             ).astype(jnp.float32)                              # (B, N)
    bmask = (jnp.arange(Nb)[None, :] < nb_valid[:, None]
             ).astype(jnp.float32)                              # (B, Nb)

    # Layer 2: baseline stats + window max-z over the valid samples
    b = Bs * bmask[:, None, :]
    mu = jnp.sum(b, axis=-1) / nbv                              # (B, M)
    d = (b - mu[..., None]) * bmask[:, None, :]
    sd = jnp.sqrt(jnp.maximum(jnp.sum(d * d, axis=-1) / nbv, 0.0))
    floor = jnp.maximum(SIGMA_FLOOR_ABS, SIGMA_FLOOR_REL * jnp.abs(mu))
    sd = jnp.maximum(sd, floor)
    w = Mx * tmask[:, None, :]
    z = (w - mu[..., None]) / sd[..., None]
    z = jnp.where(tmask[:, None, :] > 0, z, NEG)
    scores = jnp.max(z, axis=-1)                                # (B, M)

    # Layer 3: centered/normalized series, one gather-based lag sweep
    Lm = L * tmask
    Lc = (Lm - jnp.sum(Lm, axis=-1, keepdims=True) / nv) * tmask
    Ln = jnp.sqrt(jnp.sum(Lc * Lc, axis=-1)) + _EPS             # (B,)
    Mc = (w - jnp.sum(w, axis=-1, keepdims=True) / nv[..., None]
          ) * tmask[:, None, :]
    Mn = jnp.sqrt(jnp.sum(Mc * Mc, axis=-1)) + _EPS             # (B, M)
    Lpad = jnp.pad(Lc, ((0, 0), (K, K)))
    idx = (jnp.arange(2 * K + 1)[:, None]
           + jnp.arange(N)[None, :])                            # (2K+1, N)
    Lshift = Lpad[:, idx]                                       # (B, 2K+1, N)
    rho = jnp.einsum("bmt,bkt->bmk", Mc, Lshift)
    rho = rho / (Mn[..., None] * Ln[:, None, None])
    return scores, rho
