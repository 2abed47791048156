"""Pallas TPU kernel: fused baseline stats + max-z spike score + lagged
cross-correlation.

Scoring spikes and running :mod:`repro.kernels.xcorr` as two dispatches
would move every (host, metric) telemetry window across HBM twice.  Here
one grid cell handles (1 row, block_m metrics) and computes, from a
single VMEM-resident read of the tile:

  * baseline mean/std (VPU row reductions, sigma floor as in core.spike),
  * the window max-z spike score S_i,
  * the full lag sweep rho_i(k), |k| <= K, as one MXU matmul against the
    lag matrix one strided lane rotation builds
    (:func:`repro.kernels.xcorr.xcorr.lag_correlations`).

Rows are hosts (fleet path) or pending events (event-batched eval path).
Each row carries its own valid window/baseline lengths, read from SMEM
(scalar prefetch); the fixed-length entry point passes the same lengths
for every row.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.spike import (
    MASK_NEG as NEG, SIGMA_FLOOR_ABS, SIGMA_FLOOR_REL,
)
from repro.kernels import tuning
from repro.kernels.xcorr.xcorr import lag_correlations

_EPS = 1e-12
LAG_PAD = tuning.DEFAULT_LAG_PAD   # default lag padding (env-overridable)


def _fused_kernel(max_lag: int, nv_ref, nb_ref,
                  lat_ref, met_ref, base_ref, score_ref, rho_ref):
    """nv_ref/nb_ref (B,) i32 SMEM — per-row valid window/baseline
    lengths; lat_ref (1, N); met_ref (bm, N); base_ref (bm, Nb);
    score_ref (bm, 1); rho_ref (bm, LAG_PAD) in reversed lag order."""
    N = lat_ref.shape[-1]
    Nb = base_ref.shape[-1]
    b = pl.program_id(0)
    n_valid = nv_ref[b]
    nb_valid = nb_ref[b]
    valid = (jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
             < n_valid).astype(jnp.float32)
    bmask = (jax.lax.broadcasted_iota(jnp.int32, (1, Nb), 1)
             < nb_valid).astype(jnp.float32)
    nv = n_valid.astype(jnp.float32)
    nb = nb_valid.astype(jnp.float32)

    # ---- Layer 2: baseline stats + window max-z (reads the tile once)
    base = base_ref[...] * bmask
    mu = jnp.sum(base, axis=1, keepdims=True) / nb                 # (bm, 1)
    d = (base - mu) * bmask
    sd = jnp.sqrt(jnp.maximum(jnp.sum(d * d, axis=1, keepdims=True) / nb,
                              0.0))
    floor = jnp.maximum(SIGMA_FLOOR_ABS, SIGMA_FLOOR_REL * jnp.abs(mu))
    sd = jnp.maximum(sd, floor)

    w = met_ref[...] * valid                                       # (bm, N)
    z = jnp.where(valid > 0, (w - mu) / sd, NEG)
    score_ref[...] = jnp.max(z, axis=1, keepdims=True)

    # ---- Layer 3: centered/normalized series, shared with the same tile
    L = lat_ref[...] * valid
    Lc = (L - jnp.sum(L, axis=1, keepdims=True) / nv) * valid
    Ln = jnp.sqrt(jnp.sum(Lc * Lc, axis=1, keepdims=True)) + _EPS  # (1, 1)

    Mc = (w - jnp.sum(w, axis=1, keepdims=True) / nv) * valid
    Mn = jnp.sqrt(jnp.sum(Mc * Mc, axis=1, keepdims=True)) + _EPS  # (bm, 1)

    rho = lag_correlations(Mc, Lc, max_lag, rho_ref.shape[-1])
    rho_ref[...] = rho / (Mn * Ln)


def fused_rca_masked_pallas(latency: jax.Array, metrics: jax.Array,
                            baselines: jax.Array, n_valid: jax.Array,
                            nb_valid: jax.Array, max_lag: int,
                            block_m: int | None = None,
                            lag_pad: int | None = None, *,
                            interpret: bool,
                            ) -> tuple[jax.Array, jax.Array]:
    """Ragged-row fused RCA: per-row valid lengths.

    latency (B, N), metrics (B, M, N), baselines (B, M, Nb) left-aligned
    with zero tails, N and Nb lane-aligned (x128); n_valid/nb_valid (B,)
    int32 give each row's true window/baseline lengths.  Returns
    (scores (B, M), rho (B, M, 2K+1)).  ``interpret`` runs the body in
    the Pallas interpreter (the CPU path — see ``kernels.backend``);
    ``block_m``/``lag_pad`` default to the env-overridable tile config
    (kernels.tuning).
    """
    B, Mm, N = metrics.shape
    Nb = baselines.shape[-1]
    if N % 128 != 0 or Nb % 128 != 0:
        raise ValueError(f"N={N}, Nb={Nb} must be lane-aligned (x128)")
    K = int(max_lag)
    bm = tuning.block_m(block_m)
    lp = tuning.lag_pad(K, lag_pad)
    pad_m = (-Mm) % bm
    if pad_m:
        metrics = jnp.pad(metrics, ((0, 0), (0, pad_m), (0, 0)))
        baselines = jnp.pad(baselines, ((0, 0), (0, pad_m), (0, 0)),
                            constant_values=1.0)
    Mp = Mm + pad_m

    scores, rho = pl.pallas_call(
        functools.partial(_fused_kernel, K),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=2,
            grid=(B, Mp // bm),
            in_specs=[
                pl.BlockSpec((None, 1, N), lambda b, j, nv, nb: (b, 0, 0)),
                pl.BlockSpec((None, bm, N), lambda b, j, nv, nb: (b, j, 0)),
                pl.BlockSpec((None, bm, Nb), lambda b, j, nv, nb: (b, j, 0)),
            ],
            out_specs=[
                pl.BlockSpec((None, bm, 1), lambda b, j, nv, nb: (b, j, 0)),
                pl.BlockSpec((None, bm, lp), lambda b, j, nv, nb: (b, j, 0)),
            ],
        ),
        out_shape=[
            jax.ShapeDtypeStruct((B, Mp, 1), jnp.float32),
            jax.ShapeDtypeStruct((B, Mp, lp), jnp.float32),
        ],
        interpret=interpret,
    )(n_valid.astype(jnp.int32).reshape(B), nb_valid.astype(jnp.int32)
      .reshape(B), latency.astype(jnp.float32)[:, None, :],
      metrics.astype(jnp.float32), baselines.astype(jnp.float32))
    return scores[:, :Mm, 0], rho[:, :Mm, 2 * K::-1]


def fused_rca_pallas(latency: jax.Array, metrics: jax.Array,
                     baselines: jax.Array, max_lag: int,
                     n_valid: int | None = None, nb_valid: int | None = None,
                     block_m: int | None = None, lag_pad: int | None = None,
                     *, interpret: bool,
                     ) -> tuple[jax.Array, jax.Array]:
    """latency (B, N), metrics (B, M, N), baselines (B, M, Nb) ->
    (scores (B, M), rho (B, M, 2K+1)), fp32.

    N and Nb must be lane-aligned (pad + pass n_valid/nb_valid); every
    row shares those lengths.  The ragged kernel with constant lengths.
    """
    B, _, N = metrics.shape
    Nb = baselines.shape[-1]
    nv = jnp.full((B,), N if n_valid is None else int(n_valid), jnp.int32)
    nb = jnp.full((B,), Nb if nb_valid is None else int(nb_valid), jnp.int32)
    return fused_rca_masked_pallas(latency, metrics, baselines, nv, nb,
                                   max_lag, block_m, lag_pad,
                                   interpret=interpret)
