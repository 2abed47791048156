"""Pallas TPU kernel: batched lagged cross-correlation.

Fleet-scale Layer 3: one correlation engine ingests windows from B hosts
x M metrics and correlates each against that host's latency window over
lags |k| <= K.

TPU mapping: for one (host, metric-block) grid cell we build the
lag-shifted latency matrix in VMEM once — one strided lane rotation of
the zero-extended row (:func:`lag_correlations`) — then the whole lag
sweep is a single MXU matmul:

    rho_block = Mc (bm, N) @ R^T (N, LAG_PAD)

with fp32 accumulation; means/norms are VPU row reductions.  Block shapes
keep the working set ((bm + LAG_PAD + 2) * N * 4 bytes ~ 0.15 MB for
bm=8, N=512) far under VMEM, and N is lane-aligned (multiple of 128).
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.kernels import tuning

_EPS = 1e-12
LAG_PAD = tuning.DEFAULT_LAG_PAD   # default lag padding (env-overridable)


def lag_correlations(mc: jax.Array, lc: jax.Array, max_lag: int,
                     rows: int) -> jax.Array:
    """Un-normalized lag products ``(bm, rows)`` of centered metric rows
    ``mc`` (bm, N) against the centered latency row ``lc`` (1, N), N
    lane-aligned — one MXU matmul against a lag matrix ``R``.

    ``R[r, t] = lc[0, t + K - r]``, zero outside ``[0, N)``: row r holds
    lag ``K - r`` (positive lag = metric leads, matching core.xcorr), so
    output columns ``0..2K`` are the lag sweep in *descending* lag order
    and columns past ``2K`` are don't-cares; the caller flips the lag
    axis back.  The descending order is what lets one strided lane
    rotation (row r rotated r lanes further) build every row of ``R``
    from ``lc`` extended by a zero tail of at least K lanes, so that
    rotated-in lanes read zeros.  Shared by this kernel and
    kernels.fused.
    """
    N = lc.shape[-1]
    K = int(max_lag)
    ext = -(-max(K, 1) // 128) * 128
    W = N + ext
    p = jnp.concatenate([lc, jnp.zeros((1, ext), lc.dtype)], axis=1)
    p = pltpu.roll(p, (W - K) % W, 1)                 # p[v] = lc[v + K]
    r = pltpu.roll(jnp.broadcast_to(p, (rows, W)), 0, 1,
                   stride=1, stride_axis=0)           # r[i, t] = p[t - i]
    return jax.lax.dot_general(
        mc, r[:, :N], (((1,), (1,)), ((), ())),
        precision=jax.lax.Precision.HIGHEST,
        preferred_element_type=jnp.float32)


def _xcorr_kernel(n_valid: int, max_lag: int,
                  lat_ref, met_ref, out_ref):
    """lat_ref (1, N); met_ref (bm, N); out_ref (bm, LAG_PAD)."""
    N = lat_ref.shape[-1]
    valid = (jax.lax.broadcasted_iota(jnp.int32, (1, N), 1)
             < n_valid).astype(jnp.float32)
    nv = jnp.float32(n_valid)

    L = lat_ref[...] * valid
    Lc = (L - jnp.sum(L, axis=1, keepdims=True) / nv) * valid
    Ln = jnp.sqrt(jnp.sum(Lc * Lc, axis=1, keepdims=True)) + _EPS

    M = met_ref[...] * valid                           # (bm, N)
    Mc = (M - jnp.sum(M, axis=1, keepdims=True) / nv) * valid
    Mn = jnp.sqrt(jnp.sum(Mc * Mc, axis=1, keepdims=True)) + _EPS

    rho = lag_correlations(Mc, Lc, max_lag, out_ref.shape[-1])
    out_ref[...] = rho / (Mn * Ln)


def lagged_xcorr_pallas(latency: jax.Array, metrics: jax.Array,
                        max_lag: int, n_valid: int | None = None,
                        block_m: int | None = None,
                        lag_pad: int | None = None, *,
                        interpret: bool) -> jax.Array:
    """latency (B, N), metrics (B, M, N) -> rho (B, M, 2K+1), fp32.

    N must be a multiple of 128 (pad + pass ``n_valid``).  ``interpret``
    runs the body in the Pallas interpreter (the CPU path — see
    ``kernels.backend``).  ``block_m``/``lag_pad`` default to the
    env-overridable tile config (kernels.tuning).
    """
    B, Mm, N = metrics.shape
    if N % 128 != 0:
        raise ValueError(f"N={N} must be lane-aligned (multiple of 128)")
    n_valid = N if n_valid is None else int(n_valid)
    K = int(max_lag)
    bm = tuning.block_m(block_m)
    lp = tuning.lag_pad(K, lag_pad)
    pad_m = (-Mm) % bm
    if pad_m:
        metrics = jnp.pad(metrics, ((0, 0), (0, pad_m), (0, 0)))
    Mp = Mm + pad_m

    out = pl.pallas_call(
        functools.partial(_xcorr_kernel, n_valid, K),
        grid=(B, Mp // bm),
        in_specs=[
            pl.BlockSpec((None, 1, N), lambda b, j: (b, 0, 0)),
            pl.BlockSpec((None, bm, N), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, bm, lp), lambda b, j: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Mp, lp), jnp.float32),
        interpret=interpret,
    )(latency.astype(jnp.float32)[:, None, :], metrics.astype(jnp.float32))
    return out[:, :Mm, 2 * K::-1]
