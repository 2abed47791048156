"""Jit'd wrapper for the batched spike-score kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import interpret_mode
from repro.kernels.spike.ref import spike_scores_ref
from repro.kernels.spike.spike import spike_scores_pallas


def _pad128(x: jax.Array, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % 128
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def spike_scores(windows: jax.Array, baselines: jax.Array,
                 use_kernel: bool = True) -> jax.Array:
    """Batched spike scores (B, M) for (B, M, Nw) windows vs (B, M, Nb)."""
    if not use_kernel:
        return spike_scores_ref(windows, baselines)
    nw, nb = windows.shape[-1], baselines.shape[-1]
    w = _pad128(windows.astype(jnp.float32), 2)
    b = _pad128(baselines.astype(jnp.float32), 2)
    return spike_scores_pallas(w, b, nw_valid=nw, nb_valid=nb,
                               interpret=interpret_mode())
