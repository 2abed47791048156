"""Jit'd wrapper for the Welford kernel."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import interpret_mode
from repro.kernels.welford.ref import welford_ref
from repro.kernels.welford.welford import welford_pallas


@functools.partial(jax.jit, static_argnames=("use_kernel",))
def welford(x: jax.Array, use_kernel: bool = True):
    """(mean, var) over the last axis of (B, M, N)."""
    if not use_kernel:
        return welford_ref(x)
    n = x.shape[-1]
    pad = (-n) % 128
    if pad:
        x = jnp.pad(x, ((0, 0), (0, 0), (0, pad)))
    return welford_pallas(x.astype(jnp.float32), n_valid=n,
                          interpret=interpret_mode())
