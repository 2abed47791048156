"""Jit'd public wrapper for the fused spike+xcorr kernel with CPU fallback.

This is the fleet-RCA hot path: one dispatch yields, for every (host,
metric), the spike score against its baseline AND the full lag sweep against
that host's latency window — the two quantities confidence fusion consumes.
Rows can be hosts (fleet path) or pending events (event-batched eval path,
via the ragged ``fused_rca_max_ragged``).

``DISPATCH_COUNT`` counts python-level fused Layer-3 dispatches (one per
``fused_rca_max``/``fused_rca_max_ragged`` call, jit cache hits included) —
the eval harness asserts the 68-trial run issues exactly one per diagnoser.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp

from repro.kernels.backend import interpret_mode
from repro.kernels.fused.fused import fused_rca_masked_pallas, fused_rca_pallas
from repro.kernels.fused.ref import fused_rca_masked_ref, fused_rca_ref

#: python-level fused-dispatch counter (see module docstring)
DISPATCH_COUNT = 0


def _pad128(x: jax.Array, axis: int) -> jax.Array:
    pad = (-x.shape[axis]) % 128
    if pad == 0:
        return x
    widths = [(0, 0)] * x.ndim
    widths[axis] = (0, pad)
    return jnp.pad(x, widths)


@functools.partial(jax.jit, static_argnames=("max_lag", "use_kernel"))
def fused_rca(latency: jax.Array, metrics: jax.Array, baselines: jax.Array,
              max_lag: int = 20, use_kernel: bool = True,
              ) -> tuple[jax.Array, jax.Array]:
    """(scores (B, M), rho (B, M, 2K+1)) for latency (B, N), metrics
    (B, M, N), baselines (B, M, Nb).

    ``use_kernel=True`` dispatches the fused Pallas kernel (compiled on a
    TPU, interpreted on the CPU — see ``kernels.backend``); False composes
    the pure-jnp references — also the AD-friendly path.
    """
    if latency.ndim != 2 or metrics.ndim != 3 or baselines.ndim != 3:
        raise ValueError(f"latency {latency.shape}, metrics {metrics.shape}, "
                         f"baselines {baselines.shape}")
    if not use_kernel:
        return fused_rca_ref(latency, metrics, baselines, max_lag)
    n, nb = metrics.shape[-1], baselines.shape[-1]
    lat = _pad128(latency.astype(jnp.float32), 1)
    met = _pad128(metrics.astype(jnp.float32), 2)
    base = _pad128(baselines.astype(jnp.float32), 2)
    return fused_rca_pallas(lat, met, base, max_lag, n_valid=n, nb_valid=nb,
                            interpret=interpret_mode())


@functools.partial(jax.jit, static_argnames=("max_lag", "use_kernel"))
def _fused_rca_max_jit(latency, metrics, baselines, max_lag, use_kernel):
    scores, rho = fused_rca(latency, metrics, baselines, max_lag, use_kernel)
    idx = jnp.argmax(jnp.abs(rho), axis=-1)
    c = jnp.take_along_axis(jnp.abs(rho), idx[..., None], axis=-1)[..., 0]
    return scores, c, idx - max_lag


def fused_rca_max(latency, metrics, baselines, max_lag: int = 20,
                  use_kernel: bool = True):
    """(scores, c, lag) per (B, M): spike scores plus max |rho| over lags
    and its arg-max lag — the exact inputs of confidence.rank_causes."""
    global DISPATCH_COUNT
    DISPATCH_COUNT += 1
    return _fused_rca_max_jit(latency, metrics, baselines, int(max_lag),
                              bool(use_kernel))


@functools.partial(jax.jit, static_argnames=("max_lag", "use_kernel"))
def _fused_rca_max_ragged_jit(latency, metrics, baselines, n_valid, nb_valid,
                              max_lag, use_kernel):
    # zero the tails inside the jit (XLA fuses it) so the masked math sees
    # exact zeros regardless of caller padding garbage
    N, Nb = metrics.shape[-1], baselines.shape[-1]
    tmask = jnp.arange(N)[None, :] < n_valid[:, None]
    bmask = jnp.arange(Nb)[None, :] < nb_valid[:, None]
    latency = jnp.where(tmask, latency, 0.0)
    metrics = jnp.where(tmask[:, None, :], metrics, 0.0)
    baselines = jnp.where(bmask[:, None, :], baselines, 0.0)
    if use_kernel:
        lat = _pad128(latency.astype(jnp.float32), 1)
        met = _pad128(metrics.astype(jnp.float32), 2)
        base = _pad128(baselines.astype(jnp.float32), 2)
        scores, rho = fused_rca_masked_pallas(lat, met, base, n_valid,
                                              nb_valid, max_lag,
                                              interpret=interpret_mode())
    else:
        scores, rho = fused_rca_masked_ref(latency, metrics, baselines,
                                           n_valid, nb_valid, max_lag)
    idx = jnp.argmax(jnp.abs(rho), axis=-1)
    c = jnp.take_along_axis(jnp.abs(rho), idx[..., None], axis=-1)[..., 0]
    return scores, c, idx - max_lag


def fused_rca_max_ragged(latency, metrics, baselines, n_valid, nb_valid,
                         max_lag: int = 20, use_kernel: bool = False):
    """Ragged-row :func:`fused_rca_max`: rows (events or hosts) carry their
    own valid window/baseline lengths.

    ``latency`` (B, N), ``metrics`` (B, M, N), ``baselines`` (B, M, Nb) are
    left-aligned with arbitrary (ignored) tails beyond ``n_valid[b]`` /
    ``nb_valid[b]``.  One dispatch for the whole stack — the event-batched
    Layer-3 path of ``run_eval``.  ``use_kernel=False`` (default) runs the
    masked XLA reference, the CPU timing path; True dispatches the masked
    Pallas kernel (interpreted on the CPU, compiled on a TPU).
    """
    global DISPATCH_COUNT
    DISPATCH_COUNT += 1
    latency = jnp.asarray(latency)
    metrics = jnp.asarray(metrics)
    baselines = jnp.asarray(baselines)
    if latency.ndim != 2 or metrics.ndim != 3 or baselines.ndim != 3:
        raise ValueError(f"latency {latency.shape}, metrics {metrics.shape}, "
                         f"baselines {baselines.shape}")
    return _fused_rca_max_ragged_jit(latency, metrics, baselines,
                                     jnp.asarray(n_valid, jnp.int32),
                                     jnp.asarray(nb_valid, jnp.int32),
                                     int(max_lag), bool(use_kernel))
