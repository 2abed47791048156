"""Pallas TPU kernel: batched Layer-2 detection sweep — per-tick spike
score + persistence gate + onset for EVERY row of the (rows, T) latency
slab in one dispatch.

The per-trial eval loop ran :func:`repro.core.spike.detect_sweep` once per
latency row: per-row f64 conversion, per-row prefix sums and a fully
materialized (#ticks, wn) z-matrix.  Here one grid cell handles
(``block_r`` rows x ``block_t`` ticks): the cell keeps its rows' full f32
latency series VMEM-resident and walks its ticks in a loop.  Each tick
reads one lane-aligned span of the resident rows that covers the tick's
window ``x[r, tick - wn : tick]`` (a ``pl.ds`` slice at a 128-aligned
start — Mosaic has no vector gather), masks the lanes outside the window,
and computes against *precomputed* rolling baseline moments:

  * the window max-z spike score per (row, tick),
  * the above-threshold sample count (integer persistence gate, decided
    host-side in exact f64 by ``ops.persistence_count``),
  * the onset index — first above-threshold sample, with the fleet
    monitor's arg-max-z fallback behind a flag (``detect_rows`` vs
    ``detect`` convention, see core.spike),
  * an epsilon-marginality bit: whether any window z sits within ``eps``
    of the threshold, i.e. whether f32 rounding could flip this tick's
    decision against the f64 oracle (the ops layer re-checks flagged
    ticks exactly).

Baseline moments (mu, sd) arrive as (rows, #ticks) inputs — the rolling
prefix-sum pass is O(rows * T) scalar work the host does once in exact
f64 (``ops.rolling_moments``); the kernel spends its
bandwidth on the O(rows * #ticks * wn) window reductions, tick-blocked so
the working set stays at (block_r, span) per tick instead of the full
(rows, #ticks, wn) tensor.  Tick indices ride in SMEM (scalar prefetch).
``MASK_NEG`` lane masking covers padded lanes, padded ticks AND ragged
per-row valid lengths, so FleetAggregator slabs with masked/young hosts
feed it directly.
"""
from __future__ import annotations

import functools

import jax
import jax.numpy as jnp
from jax.experimental import pallas as pl
from jax.experimental.pallas import tpu as pltpu

from repro.core.spike import MASK_NEG as NEG
from repro.kernels import tuning

_LANE = 128


def _span(wn: int) -> int:
    """Lanes read per tick: the window rounded up to whole vregs, plus one
    vreg because its start is rounded down to a 128-lane boundary."""
    return -(-wn // _LANE) * _LANE + _LANE


def _sweep_kernel(wn: int, n_ticks: int, threshold: float, min_hot: int,
                  eps: float, argmax_fallback: bool,
                  ticks_ref, valid_ref, x_ref, mu_ref, sd_ref,
                  fire_ref, score_ref, onset_ref, marg_ref):
    """ticks_ref (ntp,) i32 SMEM; valid_ref (br, 1) i32; x_ref (br, Tp)
    f32; mu_ref/sd_ref (br, bt) f32; outputs (br, bt) i32/f32."""
    br, bt = mu_ref.shape
    span = _span(wn)
    j = pl.program_id(1)
    nv = valid_ref[...]                                        # (br, 1)
    col = jax.lax.broadcasted_iota(jnp.int32, (br, bt), 1)
    lane = jax.lax.broadcasted_iota(jnp.int32, (br, span), 1)
    mu_all = mu_ref[...]
    sd_all = sd_ref[...]

    def tick(i, acc):
        fire, score, onset, marg = acc
        g = j * bt + i
        t = ticks_ref[g]
        lo = t - wn
        a = pl.multiple_of((lo // _LANE) * _LANE, _LANE)
        w = x_ref[:, pl.ds(a, span)]                           # (br, span)
        k = lane + (a - lo)                                    # window index
        # padded ticks (beyond the true grid) + ragged rows
        ok = (t <= nv) & (g < n_ticks)                         # (br, 1)
        live = (k >= 0) & (k < wn) & ok                        # (br, span)
        sel = col == i
        # this tick's moments: the one selected column (max is exact)
        mu = jnp.max(jnp.where(sel, mu_all, -jnp.inf), axis=1, keepdims=True)
        sd = jnp.max(jnp.where(sel, sd_all, -jnp.inf), axis=1, keepdims=True)

        zm = jnp.where(live, (w - mu) / sd, NEG)
        s = jnp.max(zm, axis=1, keepdims=True)                 # (br, 1)
        hot = zm > threshold
        cnt = jnp.sum(hot.astype(jnp.int32), axis=1, keepdims=True)
        first_hot = jnp.min(jnp.where(hot, k, wn), axis=1, keepdims=True)
        if argmax_fallback:
            # arg-max via first index attaining the max (np.argmax tie rule)
            none = jnp.min(jnp.where(live & (zm == s), k, wn), axis=1,
                           keepdims=True)
        else:
            none = jnp.full_like(cnt, -1)
        on = jnp.where(cnt > 0, first_hot, none)
        f = (s > threshold) & (cnt >= min_hot) & ok
        m = jnp.max(((jnp.abs(zm - threshold) < eps) & live)
                    .astype(jnp.int32), axis=1, keepdims=True) > 0
        if argmax_fallback:
            # arg-max fallback onsets can swap under f32 rounding when two
            # samples near-tie for the row max — flag those ticks marginal
            tie = jnp.sum(((zm >= s - eps) & live).astype(jnp.int32),
                          axis=1, keepdims=True) >= 2
            m = m | (tie & (cnt == 0) & ok)
        return (jnp.where(sel, f.astype(jnp.int32), fire),
                jnp.where(sel, jnp.where(ok, s, 0.0), score),
                jnp.where(sel, jnp.where(ok, on, -1), onset),
                jnp.where(sel, m.astype(jnp.int32), marg))

    zi = jnp.zeros((br, bt), jnp.int32)
    fire, score, onset, marg = jax.lax.fori_loop(
        0, bt, tick, (zi, jnp.zeros((br, bt), jnp.float32), zi, zi))
    fire_ref[...] = fire
    score_ref[...] = score
    onset_ref[...] = onset
    marg_ref[...] = marg


def sweep_rows_pallas(x: jax.Array, mu: jax.Array, sd: jax.Array,
                      ticks: jax.Array, valid_n: jax.Array, wn: int,
                      threshold: float, min_hot: int, eps: float,
                      argmax_fallback: bool, block_r: int | None = None,
                      block_t: int | None = None, *, interpret: bool,
                      ) -> tuple[jax.Array, jax.Array, jax.Array, jax.Array]:
    """x (R, T) f32, mu/sd (R, nt) f32, ticks (nt,) i32 in [wn, T],
    valid_n (R,) i32 -> (fire i32, score f32, onset i32, marginal i32),
    each (R, nt).

    One dispatch for the whole slab; grid (rows / block_r, ticks /
    block_t).  ``interpret`` runs the body in the Pallas interpreter (the
    CPU path; ``repro.kernels.backend.interpret_mode`` decides it from the
    platform).  Tile sizes default to the env-overridable config
    (kernels.tuning).
    """
    R, T = x.shape
    nt = int(ticks.shape[0])
    br = tuning.sweep_block_r(block_r)
    bt = max(1, min(tuning.sweep_block_t(block_t), nt))
    pad_r = (-R) % br
    pad_t = (-nt) % bt
    # every tick's aligned span [a, a + span) must lie inside the resident
    # series: a <= T - wn rounded down to the lane tile (ticks <= T)
    Tp = (max(T - wn, 0) // _LANE) * _LANE + _span(wn)
    x = jnp.pad(x, ((0, pad_r), (0, Tp - T)))
    if pad_r:
        valid_n = jnp.pad(valid_n, (0, pad_r))        # 0 => every tick masked
    if pad_t:
        # padded ticks read a safe in-range window; masked via n_ticks
        ticks = jnp.pad(ticks, (0, pad_t), constant_values=int(wn))
    if pad_r or pad_t:
        mu = jnp.pad(mu, ((0, pad_r), (0, pad_t)))
        sd = jnp.pad(sd, ((0, pad_r), (0, pad_t)), constant_values=1.0)
    Rp, ntp = R + pad_r, nt + pad_t

    out_spec = pl.BlockSpec((br, bt), lambda i, j, tk: (i, j))
    fire, score, onset, marg = pl.pallas_call(
        functools.partial(_sweep_kernel, int(wn), nt, float(threshold),
                          int(min_hot), float(eps), bool(argmax_fallback)),
        grid_spec=pltpu.PrefetchScalarGridSpec(
            num_scalar_prefetch=1,
            grid=(Rp // br, ntp // bt),
            in_specs=[
                pl.BlockSpec((br, 1), lambda i, j, tk: (i, 0)),
                pl.BlockSpec((br, Tp), lambda i, j, tk: (i, 0)),
                pl.BlockSpec((br, bt), lambda i, j, tk: (i, j)),
                pl.BlockSpec((br, bt), lambda i, j, tk: (i, j)),
            ],
            out_specs=[out_spec] * 4,
        ),
        out_shape=[
            jax.ShapeDtypeStruct((Rp, ntp), jnp.int32),
            jax.ShapeDtypeStruct((Rp, ntp), jnp.float32),
            jax.ShapeDtypeStruct((Rp, ntp), jnp.int32),
            jax.ShapeDtypeStruct((Rp, ntp), jnp.int32),
        ],
        interpret=interpret,
    )(ticks.astype(jnp.int32), valid_n.astype(jnp.int32)[:, None],
      x.astype(jnp.float32), mu.astype(jnp.float32), sd.astype(jnp.float32))
    return (fire[:R, :nt], score[:R, :nt], onset[:R, :nt], marg[:R, :nt])
