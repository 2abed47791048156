"""Kernel microbenchmarks: wall time of the fleet-scale correlation math.

CPU wall-times here are indicative only (TPU is the target); the benchmark
exists to (a) exercise the jit'd wrappers end-to-end, (b) record the
fleet-scale problem sizes from DESIGN.md §6, and (c) compare kernel
(interpret) vs pure-jnp reference paths for parity.
"""
from __future__ import annotations

import time
from typing import List, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.kernels import tuning
from repro.kernels.backend import interpret_mode
from repro.kernels.detect.ops import detect_hosts
from repro.kernels.fused.fused import fused_rca_pallas
from repro.kernels.fused.ops import fused_rca
from repro.kernels.spike.ops import spike_scores
from repro.kernels.welford.ops import welford
from repro.kernels.xcorr.ops import lagged_xcorr


def _time(fn, *args, reps=3) -> float:
    fn(*args)  # compile
    t0 = time.perf_counter()
    for _ in range(reps):
        out = fn(*args)
    for leaf in jax.tree.leaves(out):
        if hasattr(leaf, "block_until_ready"):
            leaf.block_until_ready()
    return (time.perf_counter() - t0) / reps * 1e6


def kernel_microbench(B: int = 256, M: int = 16, N: int = 512,
                      K: int = 20, detect_h: int = 1024,
                      ) -> List[Tuple[str, float, str]]:
    rng = np.random.default_rng(0)
    rows = []
    # fleet-scale default: 256 hosts x 16 metrics x 512-sample windows
    L = jnp.asarray(rng.standard_normal((B, N)), jnp.float32)
    Mx = jnp.asarray(rng.standard_normal((B, M, N)), jnp.float32)
    us_ref = _time(lambda a, b: lagged_xcorr(a, b, K, use_kernel=False), L, Mx)
    rows.append((f"kernel/xcorr_ref_jnp/{B}x{M}x{N}", us_ref,
                 f"{2 * B * M * (2 * K + 1) * N / 1e6:.1f}MFLOP"))
    us_k = _time(lambda a, b: lagged_xcorr(a, b, K, use_kernel=True), L, Mx)
    rows.append((f"kernel/xcorr_pallas_interp/{B}x{M}x{N}", us_k,
                 "interpret-mode (CPU correctness path)"))
    W = jnp.asarray(rng.standard_normal((B, M, N)), jnp.float32)
    Bs = jnp.asarray(rng.standard_normal((B, M, 4 * N)), jnp.float32)
    rows.append((f"kernel/spike_ref_jnp/{B}x{M}", _time(
        lambda a, b: spike_scores(a, b, use_kernel=False), W, Bs), ""))
    rows.append((f"kernel/welford_ref_jnp/{B}x{M}", _time(
        lambda a: welford(a, use_kernel=False), Bs), ""))
    # fused spike+xcorr (single pass over each tile) vs the two dispatches
    us_sep = _time(lambda a, b, c: (spike_scores(b, c, use_kernel=False),
                                    lagged_xcorr(a, b, K, use_kernel=False)),
                   L, Mx, Bs)
    us_fused = _time(lambda a, b, c: fused_rca(a, b, c, K, use_kernel=False),
                     L, Mx, Bs)
    rows.append((f"kernel/fused_ref_jnp/{B}x{M}x{N}", us_fused,
                 "one pass: stats+spike+xcorr"))
    rows.append((f"kernel/fused_vs_separate/{B}x{M}x{N}", us_sep / us_fused,
                 "separate spike+xcorr dispatches / fused"))
    rows.append((f"kernel/fused_pallas_interp/{B}x{M}x{N}", _time(
        lambda a, b, c: fused_rca(a, b, c, K, use_kernel=True), L, Mx, Bs),
        "interpret-mode (CPU correctness path)"))
    # streaming detect: score + persistence gate + onset, one dispatch over
    # the (hosts, wn) slab (vs spike dispatch + f64 detect_rows replay)
    H = detect_h
    Wd = jnp.asarray(rng.standard_normal((H, 500)) + 4, jnp.float32)
    Bd = jnp.asarray(rng.standard_normal((H, 2000)) + 4, jnp.float32)
    rows.append((f"kernel/detect_ref_jnp/{H}x500", _time(
        lambda a, b: detect_hosts(a, b, 3.0, 0.35, use_kernel=False),
        Wd, Bd), "fleet Layer-2: one streaming dispatch"))
    rows.append((f"kernel/detect_pallas_interp/{H}x500", _time(
        lambda a, b: detect_hosts(a, b, 3.0, 0.35, use_kernel=True),
        Wd, Bd, reps=1), "interpret-mode (CPU correctness path)"))
    return rows


def tile_sweep_rows() -> List[Tuple[str, float, str]]:
    """block_m sweep for the fused kernel (the TPU-tuning hook):
    candidate tile sizes from kernels.tuning, one row each, so a hardware
    run starts from a measured grid.  On the CPU the kernel runs in
    interpret mode and the walls rank dispatch/trace overhead only —
    trends, not absolutes.
    """
    interpret = interpret_mode()
    rng = np.random.default_rng(1)
    B, M, N, Nb, K = 8, 16, 512, 512, 20
    L = jnp.asarray(rng.standard_normal((B, N)), jnp.float32)
    Mx = jnp.asarray(rng.standard_normal((B, M, N)), jnp.float32)
    Bs = jnp.asarray(rng.standard_normal((B, M, Nb)) + 5, jnp.float32)
    rows: List[Tuple[str, float, str]] = []
    for bm in tuning.BLOCK_M_CANDIDATES:
        fn = jax.jit(lambda a, b, c, _bm=bm: fused_rca_pallas(
            a, b, c, K, block_m=_bm, interpret=interpret))
        us = _time(fn, L, Mx, Bs, reps=1)
        rows.append((f"kernel/tile_sweep/fused_block_m{bm}/{B}x{M}x{N}", us,
                     f"REPRO_BLOCK_M={bm} candidate"
                     + (" (interpret)" if interpret else "")))
    return rows
