"""Jit'd public wrapper for the lagged-xcorr kernel with CPU fallback."""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import interpret_mode
from repro.kernels.xcorr.ref import lagged_xcorr_ref
from repro.kernels.xcorr.xcorr import lagged_xcorr_pallas


def _pad_to(x: jax.Array, mult: int, axis: int) -> jax.Array:
    n = x.shape[axis]
    pad = (-n) % mult
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("max_lag", "use_kernel"))
def lagged_xcorr(latency: jax.Array, metrics: jax.Array, max_lag: int = 20,
                 use_kernel: bool = True) -> jax.Array:
    """Batched rho (B, M, 2K+1).  latency (B, N), metrics (B, M, N).

    ``use_kernel=True`` dispatches to the Pallas TPU kernel (interpreted
    on the CPU — see ``kernels.backend``); False uses the pure-jnp
    reference — also the AD-friendly path.
    """
    if latency.ndim != 2 or metrics.ndim != 3:
        raise ValueError(f"latency {latency.shape}, metrics {metrics.shape}")
    if not use_kernel:
        return lagged_xcorr_ref(latency, metrics, max_lag)
    n = latency.shape[-1]
    lat = _pad_to(latency.astype(jnp.float32), 128, 1)
    met = _pad_to(metrics.astype(jnp.float32), 128, 2)
    return lagged_xcorr_pallas(lat, met, max_lag, n_valid=n,
                               interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("max_lag", "use_kernel"))
def max_abs_xcorr(latency, metrics, max_lag: int = 20,
                  use_kernel: bool = True):
    """(c, lag) per (B, M): max |rho| over lags and its arg-max lag."""
    rho = lagged_xcorr(latency, metrics, max_lag, use_kernel)
    idx = jnp.argmax(jnp.abs(rho), axis=-1)
    c = jnp.take_along_axis(jnp.abs(rho), idx[..., None], axis=-1)[..., 0]
    return c, idx - max_lag
