"""Pallas TPU kernel: fused baseline stats + max-z spike score.

One grid cell handles (1 host, block_m metrics): baseline mean/std and the
window max-z are VPU row reductions over lane-aligned windows; the fusion
avoids materializing the (B, M, N) z-score tensor in HBM — the kernel reads
each telemetry row once and writes one score.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl

from repro.kernels import tuning

SIGMA_FLOOR_REL = 1e-3
SIGMA_FLOOR_ABS = 1e-9
NEG = -3.4e38


def _spike_kernel(nw_valid: int, nb_valid: int, win_ref, base_ref, out_ref):
    """win_ref (bm, Nw), base_ref (bm, Nb), out_ref (bm, 1)."""
    Nw = win_ref.shape[-1]
    Nb = base_ref.shape[-1]
    wmask = jax.lax.broadcasted_iota(jnp.int32, (1, Nw), 1) < nw_valid
    bmask = (jax.lax.broadcasted_iota(jnp.int32, (1, Nb), 1)
             < nb_valid).astype(jnp.float32)
    nb = jnp.float32(nb_valid)

    b = base_ref[...] * bmask
    mu = jnp.sum(b, axis=1, keepdims=True) / nb                   # (bm, 1)
    var = jnp.sum((b - mu) * bmask * (b - mu), axis=1, keepdims=True) / nb
    sd = jnp.sqrt(jnp.maximum(var, 0.0))
    floor = jnp.maximum(SIGMA_FLOOR_ABS, SIGMA_FLOOR_REL * jnp.abs(mu))
    sd = jnp.maximum(sd, floor)

    z = jnp.where(wmask, (win_ref[...] - mu) / sd, NEG)
    out_ref[...] = jnp.max(z, axis=1, keepdims=True)


def spike_scores_pallas(windows: jax.Array, baselines: jax.Array,
                        nw_valid: int | None = None,
                        nb_valid: int | None = None,
                        block_m: int | None = None, *, interpret: bool,
                        ) -> jax.Array:
    """windows (B, M, Nw), baselines (B, M, Nb) -> (B, M) f32.

    ``block_m`` defaults to the env-overridable tile config; ``interpret``
    runs the body in the Pallas interpreter (the CPU path)."""
    B, M, Nw = windows.shape
    Nb = baselines.shape[-1]
    if Nw % 128 or Nb % 128:
        raise ValueError("window dims must be lane-aligned")
    nw_valid = Nw if nw_valid is None else int(nw_valid)
    nb_valid = Nb if nb_valid is None else int(nb_valid)
    block_m = tuning.block_m(block_m)
    pad_m = (-M) % block_m
    if pad_m:
        windows = jnp.pad(windows, ((0, 0), (0, pad_m), (0, 0)))
        baselines = jnp.pad(baselines, ((0, 0), (0, pad_m), (0, 0)),
                            constant_values=1.0)
    Mp = M + pad_m
    out = pl.pallas_call(
        functools.partial(_spike_kernel, nw_valid, nb_valid),
        grid=(B, Mp // block_m),
        in_specs=[
            pl.BlockSpec((None, block_m, Nw), lambda b, j: (b, j, 0)),
            pl.BlockSpec((None, block_m, Nb), lambda b, j: (b, j, 0)),
        ],
        out_specs=pl.BlockSpec((None, block_m, 1), lambda b, j: (b, j, 0)),
        out_shape=jax.ShapeDtypeStruct((B, Mp, 1), jnp.float32),
        interpret=interpret,
    )(windows.astype(jnp.float32), baselines.astype(jnp.float32))
    return out[:, :M, 0]
