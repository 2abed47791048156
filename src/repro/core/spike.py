"""3-sigma spike detection (paper §2.2, Layer 2).

    S_L = max_{t in W} (L(t) - mu_L) / sigma_L ,   spike iff S_L > 3

where (mu_L, sigma_L) come from a baseline window W_b preceding the
observation window W.  All functions are numpy (the per-host engine runs on
the host CPU, exactly as the paper's agent does); the batched fleet-scale
versions live in :mod:`repro.kernels.detect` (Layer 2) and
:mod:`repro.kernels.fused` (Layer 3).
"""
from __future__ import annotations

from typing import Optional, Tuple

import numpy as np

DEFAULT_THRESHOLD = 3.0
#: floor on sigma, relative to |mu| — a perfectly flat baseline must not turn
#: numerical dust into spikes (sigma=0 would make any deviation infinite).
SIGMA_FLOOR_REL = 1e-3
SIGMA_FLOOR_ABS = 1e-9
#: f32 -inf surrogate the kernels use to mask padded lanes out of max/argmax
#: reductions — one definition so every kernel/ref pair stays in sync.
MASK_NEG = -3.4e38
#: evaluation ticks per ``detect_sweep`` chunk — bounds the (#ticks, wn)
#: z materialization at streaming cadence (a 10-sample-tick sweep over a
#: long trial would otherwise allocate the full matrix at once); chunking
#: is bitwise-invisible because every tick's decision is independent.
SWEEP_TICK_CHUNK = 1024


def baseline_stats(baseline: np.ndarray) -> Tuple[float, float]:
    """(mu, sigma) over the baseline window, with a sigma floor."""
    x = np.asarray(baseline, dtype=np.float64)
    if x.size == 0:
        return 0.0, SIGMA_FLOOR_ABS
    mu = float(np.mean(x))
    sigma = float(np.std(x))
    floor = max(SIGMA_FLOOR_ABS, SIGMA_FLOOR_REL * abs(mu))
    return mu, max(sigma, floor)


def spike_score(window: np.ndarray, mu: float, sigma: float) -> float:
    """S = max_t (x(t) - mu)/sigma.  One-sided: spikes are increases.

    (For metrics where the anomaly is a *drop* — e.g. dev_clock under
    power-cap throttling — callers pass the negated series; see
    `engine._oriented`.)"""
    x = np.asarray(window, dtype=np.float64)
    if x.size == 0:
        return 0.0
    return float(np.max((x - mu) / sigma))


def detect(window: np.ndarray, baseline: np.ndarray,
           threshold: float = DEFAULT_THRESHOLD,
           persistence: float = 0.0,
           ) -> Tuple[bool, float, Optional[int]]:
    """Full Layer-2 check.

    ``persistence`` is the fraction of window samples that must exceed the
    threshold before a spike is declared.  0 reproduces the bare max-score
    rule; the production default (engine) uses 0.4 so a single noise sample
    cannot fire the detector — this is also what gives the paper's ~5 s
    detection latency with a 5 s window: the anomaly must *fill* a good part
    of the window before the boundary evaluation trips.

    Returns ``(is_spike, score, onset_index)`` where ``onset_index`` is the
    first sample in ``window`` whose z-score exceeds the threshold (the
    engine converts it to an onset timestamp).  When no sample crosses,
    ``onset_index`` is ``None`` — the streaming engine has nothing to
    timestamp.  This deliberately differs from :func:`detect_rows`, whose
    fleet-monitor convention falls back to the arg-max-z sample so marginal
    hosts still carry a timestamp estimate; the batched sweep kernels
    (:mod:`repro.kernels.sweep`) expose both conventions behind an explicit
    flag so neither caller can drift.
    """
    mu, sigma = baseline_stats(baseline)
    x = np.asarray(window, dtype=np.float64)
    if x.size == 0:
        return False, 0.0, None
    z = (x - mu) / sigma
    score = float(np.max(z))
    hot = z > threshold
    frac = float(np.mean(hot))
    if score > threshold and frac >= persistence:
        onset = int(np.argmax(hot))
        return True, score, onset
    return False, score, None


def sliding_baseline_stats(x: np.ndarray, starts: np.ndarray, n: int,
                           ) -> Tuple[np.ndarray, np.ndarray]:
    """(mu, sigma) of ``x[s:s+n]`` for every start in ``starts`` — O(T + #starts).

    One prefix-sum pass replaces per-tick ``np.mean``/``np.std`` recomputation.
    The series is shifted by its global mean before the squared pass so the
    sum-of-squares difference does not cancel catastrophically for large-mean
    channels (byte counters); this is the rolling-moment analogue of the
    Welford kernel's chunk merge.  Applies the same sigma floor as
    :func:`baseline_stats`.
    """
    x = np.asarray(x, dtype=np.float64)
    starts = np.asarray(starts, dtype=np.intp)
    n = int(n)
    if n <= 0 or (starts.size and (starts.min() < 0 or starts.max() + n > x.size)):
        raise ValueError(f"invalid baseline spans: n={n}, x.size={x.size}")
    shift = float(x.mean()) if x.size else 0.0
    y = x - shift
    c1 = np.concatenate(([0.0], np.cumsum(y)))
    c2 = np.concatenate(([0.0], np.cumsum(y * y)))
    m = (c1[starts + n] - c1[starts]) / n
    var = np.maximum((c2[starts + n] - c2[starts]) / n - m * m, 0.0)
    mu = m + shift
    sigma = np.sqrt(var)
    floor = np.maximum(SIGMA_FLOOR_ABS, SIGMA_FLOOR_REL * np.abs(mu))
    return mu, np.maximum(sigma, floor)


def detect_sweep(x: np.ndarray, window_n: int, baseline_n: int,
                 ticks: np.ndarray, threshold: float = DEFAULT_THRESHOLD,
                 persistence: float = 0.0,
                 ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Vectorized :func:`detect` over many evaluation ticks at once.

    For every tick ``t`` the decision is over ``window = x[t-wn:t]`` against
    ``baseline = x[t-wn-bn:t-wn]`` — exactly the scalar rule, but baseline
    moments come from one prefix-sum pass and the window reductions from a
    strided view, so a full-trial sweep costs O(T + #ticks * wn) instead of
    re-slicing the baseline at every tick.

    Returns ``(is_spike, score, onset)`` arrays over ticks; ``onset`` is the
    first window index whose z exceeds the threshold (-1 where none does).
    """
    x = np.asarray(x, dtype=np.float64)
    ticks = np.asarray(ticks, dtype=np.intp)
    wn, bn = int(window_n), int(baseline_n)
    nt = ticks.size
    if nt == 0:
        e = np.empty(0)
        return e.astype(bool), e, e.astype(np.intp)
    if ticks.min() < wn + bn or ticks.max() > x.size:
        raise ValueError(f"ticks must lie in [{wn + bn}, {x.size}]")
    if bn > 0:
        mu, sigma = sliding_baseline_stats(x, ticks - wn - bn, bn)
    else:  # empty baseline: scalar baseline_stats() convention
        mu = np.zeros(nt)
        sigma = np.full(nt, SIGMA_FLOOR_ABS)
    # strided view: row i is the observation window ending at ticks[i];
    # z is materialized so comparisons round exactly like the scalar path,
    # but only SWEEP_TICK_CHUNK ticks at a time — per-tick decisions are
    # independent, so chunking bounds peak memory without changing a bit
    Wall = np.lib.stride_tricks.sliding_window_view(x, wn)
    fire = np.empty(nt, bool)
    score = np.empty(nt)
    onset = np.empty(nt, np.intp)
    for lo in range(0, nt, SWEEP_TICK_CHUNK):
        sl = slice(lo, min(lo + SWEEP_TICK_CHUNK, nt))
        z = (Wall[ticks[sl] - wn] - mu[sl, None]) / sigma[sl, None]
        score[sl] = z.max(axis=1)
        hot = z > threshold
        frac = hot.mean(axis=1)
        fire[sl] = (score[sl] > threshold) & (frac >= persistence)
        onset[sl] = np.where(hot.any(axis=1), hot.argmax(axis=1), -1)
    return fire, score, onset


def detect_sweep_at(x: np.ndarray, window_n: int, ticks: np.ndarray,
                    mu: np.ndarray, sigma: np.ndarray,
                    threshold: float = DEFAULT_THRESHOLD,
                    persistence: float = 0.0,
                    ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """:func:`detect_sweep`'s per-tick decision at given ticks against
    *given* baseline moments — bitwise the same z / score / fire / onset
    math, without re-running the prefix-sum pass.

    The batched slab sweep uses this to re-decide its epsilon-marginal
    ticks and to stamp exact f64 scores at detection ticks: the rolling
    (mu, sigma) are already computed once for the whole slab
    (``kernels.sweep.ops.rolling_moments``), so an exactness fix-up
    costs O(#ticks * wn), not another O(T) pass per row.
    """
    x = np.asarray(x, dtype=np.float64)
    ticks = np.asarray(ticks, dtype=np.intp)
    wn = int(window_n)
    W = np.lib.stride_tricks.sliding_window_view(x, wn)[ticks - wn]
    z = (W - np.asarray(mu)[:, None]) / np.asarray(sigma)[:, None]
    score = z.max(axis=1)
    hot = z > threshold
    frac = hot.mean(axis=1)
    fire = (score > threshold) & (frac >= persistence)
    onset = np.where(hot.any(axis=1), hot.argmax(axis=1), -1)
    return fire, score, onset.astype(np.intp)


def detect_rows(windows: np.ndarray, baselines: np.ndarray,
                threshold: float = DEFAULT_THRESHOLD,
                persistence: float = 0.0,
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Row-batched :func:`detect`: one decision per (window, baseline) row.

    ``windows`` (H, Nw) vs ``baselines`` (H, Nb); returns ``(fire, score,
    onset)`` arrays of length H under exactly the scalar rule (sigma floor,
    max-z, persistence fraction).  ``onset`` is the first above-threshold
    sample, falling back to the arg-max z when no sample crosses — the
    fleet monitor wants a timestamp estimate even for marginal rows.

    The fallback is a *deliberate divergence* from :func:`detect`, which
    returns ``None`` when nothing crosses (the streaming engine only
    timestamps real detections; a fleet operator triaging a near-threshold
    host wants the most-suspicious instant regardless).  The sweep kernels
    (:mod:`repro.kernels.sweep`) reproduce whichever convention the caller
    selects via ``argmax_fallback`` — pinned by tests so neither this
    function nor the kernels can drift against :func:`detect`.
    """
    w = np.asarray(windows, dtype=np.float64)
    b = np.asarray(baselines, dtype=np.float64)
    if w.ndim != 2 or b.ndim != 2 or w.shape[0] != b.shape[0]:
        raise ValueError(f"shape mismatch: windows {w.shape} baselines {b.shape}")
    mu = b.mean(axis=1)
    sigma = np.maximum(b.std(axis=1),
                       np.maximum(SIGMA_FLOOR_ABS,
                                  SIGMA_FLOOR_REL * np.abs(mu)))
    z = (w - mu[:, None]) / sigma[:, None]
    score = z.max(axis=1)
    hot = z > threshold
    fire = (score > threshold) & (hot.mean(axis=1) >= persistence)
    onset = np.where(hot.any(axis=1), hot.argmax(axis=1), z.argmax(axis=1))
    return fire, score, onset.astype(np.intp)


# ---------------------------------------------------------------------------
# Validity-masked detection (chaos hardening)
#
# Same Layer-2 rule, but every cell carries a validity bit (from
# repro.core.sanitize or the fleet aggregator's staging mask).  Invalid
# cells contribute to NOTHING: not the baseline moments, not the max-z
# score, not the persistence fraction's numerator, not the onset.  The
# persistence denominator stays the FULL window length — an anomaly must
# still fill 35% of real time before firing, so corruption can only make
# the detector more conservative, never less.  Ticks whose baseline has
# fewer than MIN_VALID_BASELINE_N valid samples are refused outright
# (fire=False, score=0, onset=-1): a baseline you cannot estimate is not
# a baseline you may fire against.
# ---------------------------------------------------------------------------

#: minimum valid baseline samples before a masked tick may fire — mirrors
#: the engine's MIN_BASELINE_N warm-up gate (kept separate to avoid a
#: core -> engine import cycle; test-pinned equal).
MIN_VALID_BASELINE_N = 32


def masked_sliding_baseline_stats(x: np.ndarray, valid: np.ndarray,
                                  starts: np.ndarray, n: int,
                                  ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Masked :func:`sliding_baseline_stats`: ``(mu, sigma, n_valid)`` of the
    valid cells of ``x[s:s+n]`` for every start.

    Invalid cells are zeroed out of the prefix sums and the count prefix
    divides per-span, so a NaN/frozen cell shifts no moment.  The global
    shift is the mean of the valid cells (same cancellation guard as the
    unmasked path).  Spans with zero valid cells return (0, floor, 0).
    """
    x = np.asarray(x, dtype=np.float64)
    v = np.asarray(valid, dtype=bool)
    if v.shape != x.shape:
        raise ValueError(f"valid shape {v.shape} != x shape {x.shape}")
    starts = np.asarray(starts, dtype=np.intp)
    n = int(n)
    if n <= 0 or (starts.size and (starts.min() < 0 or starts.max() + n > x.size)):
        raise ValueError(f"invalid baseline spans: n={n}, x.size={x.size}")
    vf = v.astype(np.float64)
    y = np.where(v, x, 0.0)
    tot = vf.sum()
    shift = float(y.sum() / tot) if tot > 0 else 0.0
    yc = np.where(v, x - shift, 0.0)
    c0 = np.concatenate(([0.0], np.cumsum(vf)))
    c1 = np.concatenate(([0.0], np.cumsum(yc)))
    c2 = np.concatenate(([0.0], np.cumsum(yc * yc)))
    cnt = c0[starts + n] - c0[starts]
    denom = np.maximum(cnt, 1.0)
    m = (c1[starts + n] - c1[starts]) / denom
    var = np.maximum((c2[starts + n] - c2[starts]) / denom - m * m, 0.0)
    mu = np.where(cnt > 0, m + shift, 0.0)
    sigma = np.sqrt(var)
    floor = np.maximum(SIGMA_FLOOR_ABS, SIGMA_FLOOR_REL * np.abs(mu))
    return mu, np.maximum(sigma, floor), cnt.astype(np.intp)


def detect_sweep_at_masked(x: np.ndarray, valid: np.ndarray, window_n: int,
                           ticks: np.ndarray, mu: np.ndarray, sigma: np.ndarray,
                           threshold: float = DEFAULT_THRESHOLD,
                           persistence: float = 0.0,
                           baseline_count: Optional[np.ndarray] = None,
                           min_baseline_n: int = MIN_VALID_BASELINE_N,
                           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Masked :func:`detect_sweep_at`: per-tick decisions against given
    baseline moments, with invalid window cells pinned to -inf z.

    ``baseline_count`` (when given) gates each tick on
    ``>= min_baseline_n`` valid baseline samples; gated or all-invalid
    ticks report ``(False, 0.0, -1)``.
    """
    x = np.asarray(x, dtype=np.float64)
    v = np.asarray(valid, dtype=bool)
    ticks = np.asarray(ticks, dtype=np.intp)
    wn = int(window_n)
    W = np.lib.stride_tricks.sliding_window_view(x, wn)[ticks - wn]
    V = np.lib.stride_tricks.sliding_window_view(v, wn)[ticks - wn]
    mu = np.asarray(mu, dtype=np.float64)
    sigma = np.asarray(sigma, dtype=np.float64)
    with np.errstate(invalid="ignore"):
        z = np.where(V, (np.where(V, W, 0.0) - mu[:, None]) / sigma[:, None],
                     -np.inf)
    score = z.max(axis=1)
    hot = z > threshold
    # full-window denominator: invalid cells can never count as hot, so
    # corruption only lowers the fraction
    frac = hot.sum(axis=1) / float(wn)
    ok = V.any(axis=1)
    if baseline_count is not None:
        ok &= np.asarray(baseline_count) >= int(min_baseline_n)
    fire = ok & (score > threshold) & (frac >= persistence)
    score = np.where(ok, score, 0.0)
    onset = np.where(ok & hot.any(axis=1), hot.argmax(axis=1), -1)
    return fire, score, onset.astype(np.intp)


def detect_sweep_masked(x: np.ndarray, valid: np.ndarray, window_n: int,
                        baseline_n: int, ticks: np.ndarray,
                        threshold: float = DEFAULT_THRESHOLD,
                        persistence: float = 0.0,
                        min_baseline_n: int = MIN_VALID_BASELINE_N,
                        ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Masked :func:`detect_sweep` — the poisoned-input detection oracle.

    All three engine eval paths route corrupted latency rows through this
    one function, which is what keeps their verdict streams bitwise
    identical under chaos.  With an all-true mask the *decisions* match
    :func:`detect_sweep` (scores of non-firing all-valid ticks too).
    """
    x = np.asarray(x, dtype=np.float64)
    v = np.asarray(valid, dtype=bool)
    ticks = np.asarray(ticks, dtype=np.intp)
    wn, bn = int(window_n), int(baseline_n)
    nt = ticks.size
    if nt == 0:
        e = np.empty(0)
        return e.astype(bool), e, e.astype(np.intp)
    if ticks.min() < wn + bn or ticks.max() > x.size:
        raise ValueError(f"ticks must lie in [{wn + bn}, {x.size}]")
    if bn > 0:
        mu, sigma, cnt = masked_sliding_baseline_stats(x, v, ticks - wn - bn, bn)
    else:
        mu = np.zeros(nt)
        sigma = np.full(nt, SIGMA_FLOOR_ABS)
        cnt = np.full(nt, np.iinfo(np.intp).max, np.intp)
    fire = np.empty(nt, bool)
    score = np.empty(nt)
    onset = np.empty(nt, np.intp)
    for lo in range(0, nt, SWEEP_TICK_CHUNK):
        sl = slice(lo, min(lo + SWEEP_TICK_CHUNK, nt))
        fire[sl], score[sl], onset[sl] = detect_sweep_at_masked(
            x, v, wn, ticks[sl], mu[sl], sigma[sl], threshold, persistence,
            baseline_count=cnt[sl], min_baseline_n=min_baseline_n)
    return fire, score, onset


def detect_masked(window: np.ndarray, baseline: np.ndarray,
                  window_valid: np.ndarray, baseline_valid: np.ndarray,
                  threshold: float = DEFAULT_THRESHOLD,
                  persistence: float = 0.0,
                  min_baseline_n: int = MIN_VALID_BASELINE_N,
                  ) -> Tuple[bool, float, Optional[int]]:
    """Masked scalar :func:`detect` (the per-tick slow-path oracle).

    Same decision rule as one tick of :func:`detect_sweep_masked`; baseline
    moments are computed directly (no prefix shift), so scores can differ
    in the last ulp from the sweep — decisions agree, as on the unmasked
    fast/slow pair.
    """
    b = np.asarray(baseline, dtype=np.float64)
    bv = np.asarray(baseline_valid, dtype=bool)
    x = np.asarray(window, dtype=np.float64)
    wv = np.asarray(window_valid, dtype=bool)
    nb = int(bv.sum())
    if x.size == 0 or nb < int(min_baseline_n) or not wv.any():
        return False, 0.0, None
    bb = b[bv]
    mu = float(np.mean(bb))
    sigma = float(np.std(bb))
    sigma = max(sigma, max(SIGMA_FLOOR_ABS, SIGMA_FLOOR_REL * abs(mu)))
    with np.errstate(invalid="ignore"):
        z = np.where(wv, (np.where(wv, x, 0.0) - mu) / sigma, -np.inf)
    score = float(np.max(z))
    hot = z > threshold
    frac = float(hot.sum()) / float(x.size)
    if score > threshold and frac >= persistence:
        return True, score, int(np.argmax(hot))
    return False, score, None


def detect_rows_masked(windows: np.ndarray, baselines: np.ndarray,
                       window_valid: np.ndarray, baseline_valid: np.ndarray,
                       threshold: float = DEFAULT_THRESHOLD,
                       persistence: float = 0.0,
                       min_baseline_n: int = MIN_VALID_BASELINE_N,
                       ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Masked :func:`detect_rows` (fleet-monitor convention: argmax-z onset
    fallback over *valid* cells; rows failing the baseline gate or with no
    valid window cell report ``(False, 0.0, 0)``)."""
    w = np.asarray(windows, dtype=np.float64)
    b = np.asarray(baselines, dtype=np.float64)
    wv = np.asarray(window_valid, dtype=bool)
    bv = np.asarray(baseline_valid, dtype=bool)
    if w.ndim != 2 or b.ndim != 2 or w.shape[0] != b.shape[0]:
        raise ValueError(f"shape mismatch: windows {w.shape} baselines {b.shape}")
    cnt = bv.sum(axis=1)
    denom = np.maximum(cnt, 1)
    bz = np.where(bv, b, 0.0)
    mu = bz.sum(axis=1) / denom
    var = np.where(bv, (bz - mu[:, None]) ** 2, 0.0).sum(axis=1) / denom
    mu = np.where(cnt > 0, mu, 0.0)
    sigma = np.maximum(np.sqrt(var),
                       np.maximum(SIGMA_FLOOR_ABS,
                                  SIGMA_FLOOR_REL * np.abs(mu)))
    with np.errstate(invalid="ignore"):
        z = np.where(wv, (np.where(wv, w, 0.0) - mu[:, None]) / sigma[:, None],
                     -np.inf)
    score = z.max(axis=1)
    hot = z > threshold
    frac = hot.sum(axis=1) / float(w.shape[1])
    ok = wv.any(axis=1) & (cnt >= int(min_baseline_n))
    fire = ok & (score > threshold) & (frac >= persistence)
    score = np.where(ok, score, 0.0)
    onset = np.where(hot.any(axis=1), hot.argmax(axis=1), z.argmax(axis=1))
    onset = np.where(ok, onset, 0)
    return fire, score, onset.astype(np.intp)


def spike_scores_matrix(windows: np.ndarray, baselines: np.ndarray) -> np.ndarray:
    """Per-row spike scores for a (M, N) window matrix vs (M, Nb) baselines.

    Used by Layer 3 to score every host metric M_i alongside the latency
    channel.  Vectorized numpy; the fused Layer-3 kernel (kernels/fused)
    computes the same scores for (hosts x metrics) batches.
    """
    w = np.asarray(windows, dtype=np.float64)
    b = np.asarray(baselines, dtype=np.float64)
    if w.ndim != 2 or b.ndim != 2 or w.shape[0] != b.shape[0]:
        raise ValueError(f"shape mismatch: windows {w.shape} baselines {b.shape}")
    mu = b.mean(axis=1)
    sigma = b.std(axis=1)
    floor = np.maximum(SIGMA_FLOOR_ABS, SIGMA_FLOOR_REL * np.abs(mu))
    sigma = np.maximum(sigma, floor)
    return ((w - mu[:, None]) / sigma[:, None]).max(axis=1)
