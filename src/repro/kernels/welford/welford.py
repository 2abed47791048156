"""Pallas TPU kernel: chunk-parallel Welford merge for streaming baselines.

Maintaining per-channel baseline (mean, var) over long horizons needs a
single-pass, numerically stable reduction (naive sum-of-squares cancels
catastrophically in fp32 when mean >> std, which is routine for byte
counters).  The kernel walks lane-aligned chunks of the window with a
``fori_loop``, carrying (count, mean, M2) in VMEM scratch and merging each
chunk with Chan's parallel-Welford update:

  delta = mean_c - mean;  mean += delta * n_c / n;  M2 += M2_c + delta^2 *
  n * n_c / (n + n_c)
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

CHUNK = 128


def _welford_kernel(n_valid: int, x_ref, mean_ref, var_ref):
    """x_ref (bm, N); mean/var (bm, 1)."""
    N = x_ref.shape[-1]
    bm = x_ref.shape[0]
    n_chunks = N // CHUNK

    def body(c, carry):
        cnt, mean, m2 = carry                         # (bm, 1) each
        lo = pl.multiple_of(c * CHUNK, CHUNK)
        idx = lo + jax.lax.broadcasted_iota(jnp.int32, (1, CHUNK), 1)
        valid = (idx < n_valid).astype(jnp.float32)   # (1, CHUNK)
        xc = x_ref[:, pl.ds(lo, CHUNK)]
        n_c = jnp.sum(valid, axis=1, keepdims=True)   # (1, 1)
        # chunk stats (masked)
        safe = jnp.maximum(n_c, 1.0)
        mean_c = jnp.sum(xc * valid, axis=1, keepdims=True) / safe
        d = (xc - mean_c) * valid
        m2_c = jnp.sum(d * d, axis=1, keepdims=True)
        # Chan merge
        tot = cnt + n_c
        tot_safe = jnp.maximum(tot, 1.0)
        delta = mean_c - mean
        mean_new = mean + delta * n_c / tot_safe
        m2_new = m2 + m2_c + delta * delta * cnt * n_c / tot_safe
        # skip empty chunks
        mean_new = jnp.where(n_c > 0, mean_new, mean)
        m2_new = jnp.where(n_c > 0, m2_new, m2)
        cnt_new = jnp.where(n_c > 0, tot, cnt)
        return cnt_new, mean_new, m2_new

    z = jnp.zeros((bm, 1), jnp.float32)
    cnt, mean, m2 = jax.lax.fori_loop(0, n_chunks, body, (z, z, z))
    mean_ref[...] = mean
    var_ref[...] = m2 / jnp.maximum(cnt, 1.0)


def welford_pallas(x: jax.Array, n_valid: int | None = None,
                   block_m: int = 8, *, interpret: bool):
    """x (B, M, N) -> (mean, var) each (B, M) f32.  N % 128 == 0;
    ``interpret`` runs the body in the Pallas interpreter (the CPU path)."""
    B, M, N = x.shape
    if N % 128 != 0:
        raise ValueError("N must be lane-aligned")
    n_valid = N if n_valid is None else int(n_valid)
    pad_m = (-M) % block_m
    if pad_m:
        x = jnp.pad(x, ((0, 0), (0, pad_m), (0, 0)))
    Mp = M + pad_m
    out_spec = pl.BlockSpec((None, block_m, 1), lambda b, j: (b, j, 0))
    mean, var = pl.pallas_call(
        functools.partial(_welford_kernel, n_valid),
        grid=(B, Mp // block_m),
        in_specs=[pl.BlockSpec((None, block_m, N), lambda b, j: (b, j, 0))],
        out_specs=[out_spec, out_spec],
        out_shape=[jax.ShapeDtypeStruct((B, Mp, 1), jnp.float32),
                   jax.ShapeDtypeStruct((B, Mp, 1), jnp.float32)],
        interpret=interpret,
    )(x.astype(jnp.float32))
    return mean[:, :M, 0], var[:, :M, 0]
