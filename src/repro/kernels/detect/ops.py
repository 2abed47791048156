"""Streaming fleet detect = the batched Layer-2 sweep at a single tick.

This is the ``diagnose_fleet`` Layer-2 hot path: ONE dispatch over the
(hosts, bn + wn) trailing latency slab yields, per host, the spike score,
the persistence-gated straggler decision, and the onset estimate.  Since
PR 5 the implementation IS :mod:`repro.kernels.sweep` — the fleet's
boundary evaluation is the suite sweep with one evaluation tick at the
slab edge and the ``detect_rows`` arg-max onset fallback — so the fleet
and the eval no longer maintain two sweep kernels.

Exactness: baseline moments are computed here in f64 exactly as
:func:`repro.core.spike.detect_rows` does (direct mean/std + sigma
floor), and any host whose window holds a z within the sweep's epsilon
guard of the threshold is re-decided through the f64 oracle — the
fast-path flagged set and onsets are byte-exact against ``detect_rows``
by construction, not merely on the tested slabs.
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

from repro.core import spike as spike_mod
from repro.core.spans import span
from repro.kernels.sweep import ops as sweep_ops
from repro.kernels.sweep.ops import persistence_count  # re-export (tests/API)

__all__ = ["detect_hosts", "detect_hosts_slab", "detect_hosts_slab_launch",
           "persistence_count"]


class DetectPending:
    """A fleet detect whose sweep is in flight: :meth:`collect` pulls it
    and re-decides the marginal rows through the f64 oracle."""

    __slots__ = ("_sweep", "_patch_win", "_patch_base", "_threshold",
                 "_persistence", "_exact")

    def __init__(self, sweep, patch_win, patch_base, threshold: float,
                 persistence: float, exact: bool) -> None:
        self._sweep = sweep
        self._patch_win, self._patch_base = patch_win, patch_base
        self._threshold, self._persistence = threshold, persistence
        self._exact = exact

    def collect(self, in_flight: int = 1,
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
        """``(fire, score, onset)`` per host; ``in_flight`` as in
        :meth:`repro.kernels.sweep.ops.SweepPending.collect`."""
        fire, score, onset, marg = (
            x[:, 0] for x in self._sweep.collect(in_flight))
        if self._exact and marg.any():
            # guard band hit: re-decide those hosts through the f64 oracle
            # so the fast path cannot split from detect_rows at the
            # threshold
            rows = np.flatnonzero(marg)
            with span("detect.redecide", rows=rows.size):
                f2, s2, o2 = spike_mod.detect_rows(
                    np.asarray(self._patch_win[rows], np.float64),
                    np.asarray(self._patch_base[rows], np.float64),
                    self._threshold, self._persistence)
                fire[rows], score[rows], onset[rows] = f2, s2, o2
        return fire.astype(bool), score, onset.astype(np.intp)


def _detect_tail_launch(tail32: np.ndarray, patch_win: np.ndarray,
                        patch_base: np.ndarray, wn: int, bn: int,
                        threshold: float, persistence: float,
                        use_kernel: bool, exact: bool,
                        device=None, moments=None, window=None,
                        tick_end: Optional[int] = None) -> DetectPending:
    """Single-tick sweep over the (H, bn + wn) trailing slab, launched.

    ``patch_win``/``patch_base`` are the caller's original (H, Nw)/(H, Nb)
    arrays, any dtype — only epsilon-marginal rows are ever upcast from
    them for the exact ``detect_rows`` re-decision in the collect.

    ``moments`` (mu, sd) — each (H,) f64, sd already sigma-floored —
    skips the O(H * bn) direct moment pass (the incremental streaming
    state supplies these at O(delta)); marginal rows are still re-decided
    through the f64 oracle from the raw patch, so epsilon-close moments
    cannot move a decision.

    ``window`` (with ``moments``; see :func:`detect_hosts_slab_launch`)
    takes ``tail32`` unstaged: the device window stages only what it
    puts.
    """
    H, T = tail32.shape
    if moments is not None:
        mu, sd = (np.asarray(m, np.float64).reshape(H) for m in moments)
    else:
        # detect_rows' f64 moments, bit for bit: accumulating the f32 rows
        # in f64 (dtype=) adds each exactly-representable element in the
        # same pairwise order as upcasting first, without (H, Nb) f64 copies
        mu = patch_base.mean(axis=1, dtype=np.float64)
        sd = np.maximum(patch_base.std(axis=1, dtype=np.float64),
                        np.maximum(spike_mod.SIGMA_FLOOR_ABS,
                                   spike_mod.SIGMA_FLOOR_REL * np.abs(mu)))
    if moments is not None:
        # with moments supplied the sweep never touches the baseline
        # columns — dispatch on the window slice only, so the staged
        # copy and the kernel's slab stay O(wn) instead of O(wn + bn)
        # (onsets are window-relative either way; verified equivalent
        # for both kernel and reference dispatch)
        disp, bn_d = tail32[:, bn:], 0
        if window is None:
            with span("detect.stage") as sp:
                disp = np.ascontiguousarray(disp)
                sp.set_metadata(bytes=disp.nbytes)
        ticks = np.array([wn], np.int64)
    else:
        disp, bn_d = tail32, bn
        ticks = np.array([T], np.int64)
    sweep = sweep_ops.sweep_launch(
        disp, wn, bn_d, ticks, threshold, persistence,
        moments=(mu[:, None], sd[:, None]), argmax_fallback=True,
        use_kernel=use_kernel, device=device, window=window,
        tick_end=tick_end)
    return DetectPending(sweep, patch_win, patch_base, threshold,
                         persistence, exact)


def detect_hosts(windows, baselines, threshold: float = 3.0,
                 persistence: float = 0.0, use_kernel: bool = True,
                 exact: bool = True,
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Batched Layer-2 decision per host row, one dispatch.

    ``windows`` (H, Nw) vs ``baselines`` (H, Nb) -> ``(fire, score, onset)``
    numpy arrays of length H: fire is the full scalar :func:`spike.detect`
    rule (max-z above threshold AND >= ``persistence`` of the window hot),
    onset the first above-threshold sample with arg-max z fallback —
    exactly :func:`repro.core.spike.detect_rows` (``exact=True`` makes the
    agreement byte-exact via the marginality guard), without the
    intermediate (H, Nw) z materialization in host memory.
    """
    windows = np.asarray(windows)
    baselines = np.asarray(baselines)
    if windows.ndim != 2 or baselines.ndim != 2 \
            or windows.shape[0] != baselines.shape[0]:
        raise ValueError(f"shape mismatch: windows {windows.shape} "
                         f"baselines {baselines.shape}")
    wn, bn = windows.shape[1], baselines.shape[1]
    tail32 = np.concatenate([np.asarray(baselines, np.float32),
                             np.asarray(windows, np.float32)], axis=1)
    return _detect_tail_launch(
        tail32, windows, baselines, wn, bn, float(threshold),
        float(persistence), bool(use_kernel), bool(exact)).collect()


def detect_hosts_slab(tail, wn: int, bn: int, threshold: float = 3.0,
                      persistence: float = 0.0, use_kernel: bool = True,
                      exact: bool = True,
                      valid: Optional[np.ndarray] = None,
                      force_oracle: bool = False, device=None,
                      moments=None,
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """:func:`detect_hosts_slab_launch` and its ``collect()`` back to
    back: ``(fire, score, onset)`` per host."""
    return detect_hosts_slab_launch(
        tail, wn, bn, threshold, persistence, use_kernel, exact, valid,
        force_oracle, device, moments).collect()


def detect_hosts_slab_launch(tail, wn: int, bn: int, threshold: float = 3.0,
                             persistence: float = 0.0,
                             use_kernel: bool = True, exact: bool = True,
                             valid: Optional[np.ndarray] = None,
                             force_oracle: bool = False, device=None,
                             moments=None, window=None,
                             tick_end: Optional[int] = None):
    """:func:`detect_hosts` over a trailing latency slab, launched: the
    fast path returns a :class:`DetectPending` whose ``collect()`` gives
    ``(fire, score, onset)`` per host; the oracle path returns its
    result already resolved.

    ``tail`` is the (H, bn + wn) slab — baseline columns then window
    columns, exactly the layout of a trailing ring snapshot — staged as
    ONE contiguous f32 block (jax aliases aligned contiguous f32 numpy on
    CPU zero-copy, whereas a strided slab view takes the slow elementwise
    transfer path).

    ``valid`` (H, bn + wn) bool adds per-tick validity (chaos
    hardening): masked decisions route through the f64 oracle
    ``spike.detect_rows_masked`` — poisoned cells enter neither the
    moments nor the max/argmax, and hosts whose baseline keeps fewer
    than ``MIN_VALID_BASELINE_N`` valid samples stay quiet.  Corruption
    is the exceptional path, so it takes the oracle, not the kernel: the
    two can then never disagree.  An all-true mask is dropped and the
    call is byte-identical to ``valid=None``.

    ``force_oracle=True`` routes through the masked f64 oracle even for
    a clean (or absent) mask, as if an all-true mask were corrupt.  The
    sharded fleet monitor needs this: a single-slab round with ANY
    invalid cell takes the oracle for EVERY host, so when one shard sees
    corruption the clean shards must take the oracle too — otherwise the
    oracle-vs-fast split would follow shard boundaries and the parity
    contract would depend on where a host happens to live.

    ``device`` pins the fast path's sweep dispatch to one ``jax.Device``
    (see :func:`repro.kernels.sweep.ops.sweep_rows`); None keeps the
    default placement.

    ``moments`` (mu, sd) f64 arrays of length H pre-empt the direct
    baseline moment pass on the clean fast path (see
    :class:`repro.core.rolling.IncrementalMoments`); ignored on the
    masked/forced oracle path, which always derives exact masked moments
    itself.

    ``window`` (a :class:`repro.kernels.sweep.ops.DeviceWindow`, with
    ``moments`` and the exclusive absolute ``tick_end`` of the tail's
    last column) keeps the tail's last ``wn`` columns on ``device``
    between calls: the clean fast path then stages and puts only the
    columns that slid in since the window's last sweep, and the tail
    itself is never copied — the re-decision upcasts its marginal rows
    from the caller's views.  The window trusts that a tick once seen
    does not change, as the incremental moments do.
    """
    tail = np.asarray(tail)
    if tail.ndim != 2 or tail.shape[-1] != wn + bn:
        raise ValueError(f"tail {tail.shape} vs bn+wn={bn + wn}")
    v = None
    if valid is not None:
        v = np.asarray(valid, bool)
        if v.shape != tail.shape:
            raise ValueError(f"valid {v.shape} vs tail {tail.shape}")
        if v.all():
            v = None
    if v is not None or force_oracle:
        if v is None:
            v = np.ones(tail.shape, bool)
        t64 = np.asarray(tail, np.float64)
        fire, score, onset = spike_mod.detect_rows_masked(
            t64[:, bn:], t64[:, :bn], v[:, bn:], v[:, :bn],
            float(threshold), float(persistence))
        return sweep_ops.Resolved(
            (fire.astype(bool), score, onset.astype(np.intp)))
    if window is None or moments is None:
        window = None
        with span("detect.stage") as sp:
            tail32 = np.ascontiguousarray(tail, np.float32)
            sp.set_metadata(bytes=0 if tail32 is tail else tail32.nbytes)
        # the exact re-decision must see the caller's values, not the f32
        # staging — only a genuinely-f32 tail may reuse the staged copy
        patch = tail32 if tail.dtype == np.float32 else tail
    else:
        # the device window stages only the columns it puts
        tail32 = patch = tail
    return _detect_tail_launch(
        tail32, patch[:, bn:], patch[:, :bn], int(wn), int(bn),
        float(threshold), float(persistence), bool(use_kernel),
        bool(exact), device=device, moments=moments, window=window,
        tick_end=tick_end)
