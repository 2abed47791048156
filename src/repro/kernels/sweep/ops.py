"""Jit'd public wrapper for the batched Layer-2 sweep.

This is the suite-scale eval hot path: ONE dispatch over the f32
(rows, T) latency slab yields per-tick ``(fire, score, onset)`` decisions
for every row — the per-trial loop ran ``spike.detect_sweep`` row by row
with per-row f64 conversion and a fully materialized (#ticks, wn)
z-matrix.

Exactness contract.  The f32 sweep is built to agree with the f64 per-row
oracle *decision for decision*:

  * rolling baseline moments are computed here, host-side, in exact f64
    with the same prefix-sum pass as the oracle
    (:func:`rolling_moments` — ``spike.sliding_baseline_stats`` per row
    tile, bitwise-identical) and only then downcast to f32 for the
    kernel's z,
  * the persistence gate compares an integer sample count
    (:func:`persistence_count`, decided once in exact f64),
  * every tick whose window holds a z within ``SWEEP_GUARD_EPS`` of the
    threshold — the only ticks f32 rounding could flip — is flagged
    ``marginal``; callers (``CorrelationEngine.detect_events_store``)
    re-decide exactly those ticks through the f64 oracle.

Typical slabs flag well under a few percent of ticks, so the guard costs
~nothing while making the slab path byte-exact by construction instead of
byte-exact by luck.
"""
from __future__ import annotations

import contextlib
import functools
from typing import Dict, Optional, Tuple

import jax
import jax.numpy as jnp
import numpy as np

from repro.core import spike as spike_mod
from repro.core.spans import span
from repro.kernels import tuning
from repro.kernels.backend import interpret_mode
from repro.kernels.sweep.ref import sweep_rows_ref
from repro.kernels.sweep.sweep import sweep_rows_pallas

#: f32-vs-f64 decision guard band on |z - threshold| (see module docstring).
#: Generous: the observed f32 error with exact-f64 moments is ~1e-5 on the
#: hottest mean/sigma ratios, so 5e-3 leaves two orders of margin and still
#: flags only the rare genuinely-marginal tick.
SWEEP_GUARD_EPS = 5e-3


def persistence_count(n: int, persistence: float) -> int:
    """Smallest integer c with ``c / n >= persistence`` in f64.

    The scalar rule (:func:`repro.core.spike.detect`) gates on
    ``hot.mean() >= persistence`` computed in f64; comparing an f32
    fraction against the f64 threshold can flip exactly at the boundary
    count, so the kernels gate on the integer count instead — decided
    here, once, in exact f64.
    """
    n = int(n)
    if n <= 0 or persistence <= 0.0:
        return 0
    c = min(int(np.ceil(persistence * n)), n)
    while c > 0 and (c - 1) / n >= persistence:
        c -= 1
    while c <= n and c / n < persistence:
        c += 1
    return c


_STATIC = ("wn", "threshold", "min_hot", "eps", "argmax_fallback",
           "use_kernel", "interpret", "block_t")


@functools.partial(jax.jit, static_argnames=_STATIC)
def _sweep_jit(x, mu, sd, ticks, valid_n, wn, threshold, min_hot, eps,
               argmax_fallback, use_kernel, interpret, block_t):
    if use_kernel:
        return sweep_rows_pallas(x, mu, sd, ticks, valid_n, wn, threshold,
                                 min_hot, eps, argmax_fallback,
                                 block_t=block_t, interpret=interpret)
    return sweep_rows_ref(x, mu, sd, ticks, valid_n, wn, threshold,
                          min_hot, eps, argmax_fallback, block_t)


def _window_ticks(x, wn):
    """A device window's sweep: one tick at ``wn``, every row valid."""
    return (jnp.full((1,), wn, jnp.int32),
            jnp.full((x.shape[0],), wn, jnp.int32))


@functools.partial(jax.jit, static_argnames=_STATIC, donate_argnums=0)
def _advance_sweep_jit(old, packed, wn, threshold, min_hot, eps,
                       argmax_fallback, use_kernel, interpret, block_t):
    """Slide a held window (``old``, donated) by the new columns and sweep
    it, in one dispatch: ``(window, results)``.  ``packed`` is one put
    of (rows, 2 + d) f32: mu, sd, then the d new columns."""
    x = jnp.concatenate([old[:, packed.shape[1] - 2:], packed[:, 2:]],
                        axis=1)
    return x, _sweep_jit(x, packed[:, :1], packed[:, 1:2],
                         *_window_ticks(x, wn), wn, threshold, min_hot, eps,
                         argmax_fallback, use_kernel, interpret, block_t)


@functools.partial(jax.jit, static_argnames=_STATIC)
def _proof_sweep_jit(old, x, d, mu, sd, wn, threshold, min_hot, eps,
                     argmax_fallback, use_kernel, interpret, block_t):
    """Sweep the window ``x`` and prove, bit for bit, that the held
    window ``old`` slid by ``d`` columns holds the same values on the
    columns the two share: ``(same, results)``.  ``old`` is ``x`` and
    ``d`` 0 where nothing is to be proved."""
    bits = functools.partial(jax.lax.bitcast_convert_type,
                             new_dtype=jnp.int32)
    col = jax.lax.broadcasted_iota(jnp.int32, x.shape, 1)
    same = jnp.all((bits(jnp.roll(old, -d, axis=1)) == bits(x))
                   | (col >= x.shape[1] - d))
    return same, _sweep_jit(x, mu, sd, *_window_ticks(x, wn), wn,
                            threshold, min_hot, eps, argmax_fallback,
                            use_kernel, interpret, block_t)


def rolling_moments(lat64: np.ndarray, ticks: np.ndarray, wn: int, bn: int,
                    valid_n: Optional[np.ndarray] = None,
                    ) -> Tuple[np.ndarray, np.ndarray]:
    """Exact-f64 rolling baseline moments for every (row, tick).

    Bitwise-identical to what ``spike.detect_sweep`` computes — it IS the
    same prefix-sum pass (``spike.sliding_baseline_stats``), run per row
    tile so the O(T) rolling arrays stay cache-resident (a flat batched
    pass over the whole slab is measurably slower than 48 L2-sized row
    passes); same shift, same sigma floor, and the bn=0 empty-baseline
    convention of ``baseline_stats`` — mu 0, sigma at the absolute floor.
    Ragged rows (``valid_n``) use their own truncated series, exactly as
    the oracle sweeping ``x[:valid]`` would; their out-of-range ticks get
    placeholder (0, 1) moments that the sweep masks anyway.
    """
    lat64 = np.asarray(lat64, np.float64)
    R = lat64.shape[0]
    nt = ticks.size
    if bn <= 0:
        return (np.zeros((R, nt)),
                np.full((R, nt), spike_mod.SIGMA_FLOOR_ABS))
    starts = ticks - wn - bn
    mu = np.zeros((R, nt))
    sd = np.ones((R, nt))
    for r in range(R):
        nv = lat64.shape[1] if valid_n is None else int(valid_n[r])
        k = int(np.searchsorted(ticks, nv, side="right"))
        if k == 0:
            continue
        mu[r, :k], sd[r, :k] = spike_mod.sliding_baseline_stats(
            lat64[r, :nv], starts[:k], bn)
    return mu, sd


def rolling_moments_masked(lat64: np.ndarray, valid: np.ndarray,
                           ticks: np.ndarray, wn: int, bn: int,
                           valid_n: Optional[np.ndarray] = None,
                           ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """Validity-masked :func:`rolling_moments`: ``(mu, sd, n_valid)``.

    Same per-row prefix-sum pass as the masked oracle
    (``spike.masked_sliding_baseline_stats`` — bitwise identical), so a
    kernel dispatch staged on these moments agrees with
    ``spike.detect_sweep_masked`` decision for decision.  ``n_valid`` is
    the per-(row, tick) valid baseline sample count the caller gates on.
    """
    lat64 = np.asarray(lat64, np.float64)
    v = np.asarray(valid, bool)
    R = lat64.shape[0]
    nt = ticks.size
    if bn <= 0:
        return (np.zeros((R, nt)),
                np.full((R, nt), spike_mod.SIGMA_FLOOR_ABS),
                np.full((R, nt), np.iinfo(np.intp).max, np.intp))
    starts = ticks - wn - bn
    mu = np.zeros((R, nt))
    sd = np.ones((R, nt))
    cnt = np.zeros((R, nt), np.intp)
    for r in range(R):
        nv = lat64.shape[1] if valid_n is None else int(valid_n[r])
        k = int(np.searchsorted(ticks, nv, side="right"))
        if k == 0:
            continue
        mu[r, :k], sd[r, :k], cnt[r, :k] = \
            spike_mod.masked_sliding_baseline_stats(
                lat64[r, :nv], v[r, :nv], starts[:k], bn)
    return mu, sd, cnt


def sweep_rows_exact(lat, wn: int, bn: int, ticks: np.ndarray,
                     threshold: float = 3.0, persistence: float = 0.0,
                     valid_n: Optional[np.ndarray] = None,
                     moments: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                     chunk: int = 4096,
                     valid: Optional[np.ndarray] = None,
                     ) -> Tuple[np.ndarray, np.ndarray, np.ndarray]:
    """The batched sweep's exact-f64 CPU path: score-screened, no guard.

    Bitwise-identical FIRE decisions — and, at every fired tick, bitwise
    scores and onsets — vs running :func:`repro.core.spike.detect_sweep`
    row by row, but the (rows, #ticks, wn) z-tensor is never formed for
    ticks that provably cannot fire.  The screen is a *sound upper bound*
    on the hot-sample count from fixed 64-sample block maxima: rounding
    is monotone, so a block whose max-z stays at or below the threshold
    holds no hot sample, and ``64 * (#hot blocks overlapping the
    window)`` bounds the count.  Ambient windows — whose max-z routinely
    pokes over 3 sigma (the expected max of ~500 correlated samples sits
    right there) but whose hot count is a handful — are rejected without
    ever gathering the window; only the surviving (row, tick) pairs get
    the oracle's exact rule evaluated, in one fancy-index batch chunked
    at ``chunk`` pairs so peak memory stays bounded.

    Returns ``(fire, score, onset)`` of shape (rows, #ticks).  ``fire``
    is exact everywhere.  ``score`` and ``onset`` are exact wherever the
    screen let the tick through — in particular at every fired tick,
    which is all the event resolve ever reads (the oracle's
    ``detect_events`` consumes score/onset only for fired ticks);
    screened-out ticks report score 0 / onset -1, as do masked ragged
    ticks (``valid_n``).

    ``valid`` (rows, T) bool adds per-tick validity (chaos hardening):
    invalid cells enter neither moments nor the screen (they are staged
    -inf, so no block containing only poison can look hot), survivors are
    re-decided through ``spike.detect_sweep_at_masked``, and ticks with
    under ``MIN_VALID_BASELINE_N`` valid baseline samples are refused —
    the exact path then matches ``spike.detect_sweep_masked`` fire for
    fire.  An all-true mask is dropped, keeping the clean path
    byte-identical.
    """
    lat64 = np.asarray(lat, np.float64)
    R, T = lat64.shape
    wn, bn = int(wn), int(bn)
    ticks = np.asarray(ticks, dtype=np.int64)
    nt = ticks.size
    if nt == 0:
        e = np.empty((R, 0))
        return e.astype(bool), e, e.astype(np.intp)
    if ticks.min() < wn + bn or ticks.max() > T:
        raise ValueError(f"ticks must lie in [{wn + bn}, {T}]")
    vn = (np.full(R, T, np.int64) if valid_n is None
          else np.asarray(valid_n, np.int64))
    vmask = None
    if valid is not None:
        vmask = np.asarray(valid, bool)
        if vmask.shape != (R, T):
            raise ValueError(f"valid {vmask.shape} vs lat {lat64.shape}")
        if vmask.all():
            vmask = None
    bcnt = None
    if moments is None:
        if vmask is None:
            moments = rolling_moments(lat64, ticks, wn, bn,
                                      None if valid_n is None else vn)
        else:
            mm, ss, bcnt = rolling_moments_masked(
                lat64, vmask, ticks, wn, bn,
                None if valid_n is None else vn)
            moments = (mm, ss)
    mu, sd = moments
    tick_ok = ticks[None, :] <= vn[:, None]
    score = np.zeros((R, nt))
    fire = np.zeros((R, nt), bool)
    onset = np.full((R, nt), -1, np.intp)
    # block-max screen (see docstring): a tick survives only if enough
    # g-sample blocks overlapping its window contain a hot sample
    g = 64
    nB = -(-T // g)
    Bpad = np.full((R, nB * g), -np.inf)
    Bpad[:, :T] = lat64 if vmask is None else np.where(vmask, lat64, -np.inf)
    Bmax = Bpad.reshape(R, nB, g).max(axis=2)              # (R, nB)
    m = wn // g + 2
    k0 = (ticks - wn) // g
    cols = k0[:, None] + np.arange(m)[None, :]              # (nt, m)
    inwin = cols <= ((ticks - 1) // g)[:, None]
    zb = (Bmax[:, np.clip(cols, 0, nB - 1)]
          - mu[..., None]) / sd[..., None]                  # (R, nt, m)
    bound = g * ((zb > threshold) & inwin[None, :, :]).sum(axis=2)
    min_hot = persistence_count(wn, persistence)
    cand_mask = (bound >= max(min_hot, 1)) & tick_ok
    if bcnt is not None:
        cand_mask &= bcnt >= spike_mod.MIN_VALID_BASELINE_N
    # surviving ticks: the oracle's exact rule, per row so the window
    # gather is a strided view of an L2-resident series
    for r in np.flatnonzero(cand_mask.any(axis=1)):
        ci = np.flatnonzero(cand_mask[r])
        row = lat64[r, :int(vn[r])] if valid_n is not None else lat64[r]
        for lo in range(0, ci.size, chunk):
            sl = ci[lo:lo + chunk]
            if vmask is None:
                f, s, o = spike_mod.detect_sweep_at(
                    row, wn, ticks[sl], mu[r, sl], sd[r, sl],
                    threshold, persistence)
            else:
                vrow = vmask[r, :int(vn[r])] if valid_n is not None \
                    else vmask[r]
                f, s, o = spike_mod.detect_sweep_at_masked(
                    row, vrow, wn, ticks[sl], mu[r, sl], sd[r, sl],
                    threshold, persistence,
                    baseline_count=None if bcnt is None else bcnt[r, sl])
            fire[r, sl], score[r, sl], onset[r, sl] = f, s, o
    return fire, score, onset


class Resolved:
    """A launch whose result is already on the host (nothing was
    dispatched): :meth:`collect` hands it back."""

    __slots__ = ("_value",)

    def __init__(self, value) -> None:
        self._value = value

    def collect(self, in_flight: int = 1):
        return self._value


class SweepPending:
    """A sweep dispatched to its device and not yet pulled: the four
    result arrays on the device, the chip they live on, what the
    host-side validity gate needs, and a device window's coherence
    proof with the :class:`DeviceWindows` that counts it."""

    __slots__ = ("_out", "chip", "_gate", "_proof")

    def __init__(self, out, chip: int, gate, proof=None) -> None:
        self._out, self.chip, self._gate = out, chip, gate
        self._proof = proof

    def collect(self, in_flight: int = 1,
                ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
        """Pull ``(fire, score, onset, marginal)`` (blocking on the
        dispatch) and apply the validity gate.  ``in_flight`` is how many
        launched sweeps were still unpulled when this pull began,
        recorded on the ``sweep.pull`` span."""
        with span("detect.sweep", chip=self.chip), \
                span("sweep.pull", in_flight=in_flight, chip=self.chip):
            fire, score, onset, marg = self._out
            fire = np.asarray(fire).astype(bool)
            score = np.array(score, np.float64)
            onset = np.asarray(onset).astype(np.intp)
            marg = np.asarray(marg).astype(bool)
            if self._proof is not None:
                same, owner = self._proof
                owner.proofs += 1
                owner.parity_failures += not bool(same)
        self._out = self._proof = None
        if self._gate is not None:
            # host-side validity gate: a baseline you cannot estimate (or a
            # window with zero valid cells) may never fire, whatever the
            # staged sentinel z came out to
            vmask, bcnt, ticks, wn = self._gate
            R = vmask.shape[0]
            cv = np.concatenate([np.zeros((R, 1)),
                                 np.cumsum(vmask, axis=1)], axis=1)
            wcnt = cv[:, ticks] - cv[:, ticks - wn]
            ok = wcnt > 0
            if bcnt is not None:
                ok &= bcnt >= spike_mod.MIN_VALID_BASELINE_N
            fire &= ok
            score = np.where(ok, score, 0.0)
            onset = np.where(ok, onset, -1)
        return fire, score, onset, marg


#: ``put`` on the launch half of ``detect.sweep``: the host put the
#: whole window, or only the columns that slid in (a number, as all span
#: metadata are)
PUT_FULL, PUT_DELTA = 0, 1


class DeviceWindow:
    """One slab's (rows, wn) f32 latency window as last swept, held on
    its device between rounds, with the exclusive absolute tick its last
    column ends before.  :func:`window_launch` advances it by the round's
    new columns only; its :class:`DeviceWindows` keeps it."""

    __slots__ = ("owner", "x", "tick_end", "device", "advances")

    def __init__(self, owner: "DeviceWindows") -> None:
        self.owner = owner
        self.x = self.tick_end = self.device = None
        self.advances = 0

    def plan(self, rows: int, wn: int, tick_end: int,
             device) -> Tuple[int, int, bool]:
        """``(put, d, proof)`` for a sweep of the (rows, wn) window that
        ends before ``tick_end`` on ``device``: ``d`` columns slid since
        the last sweep, and whether this full put re-proves the carried
        window.  A first sweep, other dims or device, or a slide outside
        ``(0, wn)`` starts the window afresh from a full put — a slide of
        0 too: the same ticks may come again with other values (a
        snapshot diagnosed twice), which only a full put can see."""
        d = 0
        if self.x is not None and self.x.shape == (rows, wn) \
                and self.device == device:
            d = int(tick_end) - self.tick_end
        if not 0 < d < wn:
            self.x, self.advances = None, 0
            return PUT_FULL, 0, False
        self.advances += 1
        every = self.owner.reanchor_every
        if every > 0 and self.advances % every == 0:
            return PUT_FULL, d, True
        return PUT_DELTA, d, False


class DeviceWindows:
    """A monitor's device windows, one per slab of rows ``[base, base +
    rows)``, and the counts of their puts and proofs.

    A window is trusted as the incremental moments are
    (:mod:`repro.core.rolling`): a slab's ticks, once seen, do not
    change.  Every ``reanchor_every``-th advance of a window
    (``REPRO_REANCHOR_ROUNDS``) takes a full put and proves on the device
    that the carried window agrees with it bit for bit; a mismatch counts
    in :attr:`parity_failures`.  Whatever drops the moments of rows drops
    the windows holding them (:meth:`drop`, :meth:`clear`; see
    ``FleetMonitor.invalidate_rows``)."""

    def __init__(self) -> None:
        self.reanchor_every = tuning.reanchor_rounds()
        self._held: Dict[Tuple[int, int], DeviceWindow] = {}
        #: launches by ``put`` code (PUT_FULL, PUT_DELTA)
        self.puts = [0, 0]
        self.proofs = 0
        self.parity_failures = 0

    def get(self, base: int, rows: int) -> DeviceWindow:
        """The window of rows ``[base, base + rows)``; a new one drops
        every other window that overlaps those rows."""
        key = (int(base), int(rows))
        w = self._held.get(key)
        if w is None:
            self.drop(np.arange(base, base + rows))
            w = self._held[key] = DeviceWindow(self)
        return w

    def drop(self, rows: np.ndarray) -> None:
        """Forget every window that holds one of ``rows``."""
        for key in [k for k in self._held
                    if np.any((rows >= k[0]) & (rows < sum(k)))]:
            del self._held[key]

    def clear(self) -> None:
        """Forget every window."""
        self._held.clear()

    def stats(self) -> Dict[str, int]:
        """Counters snapshot, merged into the monitor's
        ``incremental_stats()``."""
        return {"window_delta_puts": self.puts[PUT_DELTA],
                "window_full_puts": self.puts[PUT_FULL],
                "window_proofs": self.proofs,
                "window_parity_failures": self.parity_failures}


def _static(wn: int, threshold: float, persistence: float, eps: float,
            argmax_fallback: bool, use_kernel: bool, device,
            block_t: Optional[int]) -> Dict[str, object]:
    """The static arguments of the sweep dispatches."""
    return dict(wn=wn, threshold=float(threshold),
                min_hot=int(persistence_count(wn, persistence)),
                eps=float(eps), argmax_fallback=bool(argmax_fallback),
                use_kernel=bool(use_kernel),
                interpret=bool(use_kernel) and interpret_mode(device),
                block_t=tuning.sweep_block_t(block_t))


def _launch(rows: int, h2d: int, put: int, device, put_args,
            dispatch) -> Tuple[tuple, int]:
    """The launch half of the ``detect.sweep`` span, for both launches:
    ``put_args()`` puts the arrays (``sweep.put``), ``dispatch(args)``
    starts the sweep (``sweep.dispatch``), on ``device``, and its
    results start back to the host.  Returns ``(results, chip)``; the
    collect opens the span again around the pull."""
    with span("detect.sweep", rows=rows, h2d_bytes=h2d, put=put) as sp, \
            (contextlib.nullcontext() if device is None
             else jax.default_device(device)):
        with span("sweep.put"):
            args = put_args()
        chip = next(iter(args[0].devices())).id
        sp.set_metadata(chip=chip)
        with span("sweep.dispatch"):
            out = dispatch(args)
            # the results start back to the host as soon as the kernel
            # ends, side by side, whether or not the host is still busy
            # launching other sweeps
            for x in out:
                x.copy_to_host_async()
    return out, chip


def window_launch(window: DeviceWindow, lat: np.ndarray, tick_end: int,
                  mu: np.ndarray, sd: np.ndarray, threshold: float,
                  persistence: float, argmax_fallback: bool,
                  use_kernel: bool, device,
                  eps: float = SWEEP_GUARD_EPS) -> SweepPending:
    """:func:`sweep_launch` of the (rows, wn) window ``lat`` — any dtype,
    ending before absolute tick ``tick_end``, swept at one tick ``wn``
    with the (rows, 1) moments ``mu``, ``sd`` — through ``window``, held
    on ``device``: stage and put only the columns that slid in since its
    last sweep, with mu and sd, as one array (or the whole window; ``put``
    on the ``detect.sweep`` span), then slide and sweep on the device in
    one dispatch."""
    R, wn = lat.shape
    static = _static(wn, threshold, persistence, eps, argmax_fallback,
                     use_kernel, device, None)
    put, d, proof = window.plan(R, wn, tick_end, device)
    with span("detect.stage") as sp:
        if put == PUT_DELTA:
            new = np.empty((R, 2 + d), np.float32)
            new[:, 0], new[:, 1] = mu.reshape(R), sd.reshape(R)
            new[:, 2:] = lat[:, wn - d:]
            h2d = new.nbytes
        else:
            new = np.ascontiguousarray(lat, np.float32)
            h2d = new.nbytes + 8 * R
        sp.set_metadata(bytes=new.nbytes)
    window.owner.puts[put] += 1

    def put_args():
        if put == PUT_DELTA:
            return (jnp.asarray(new),)
        return (jnp.asarray(new), jnp.asarray(np.asarray(mu, np.float32)),
                jnp.asarray(np.asarray(sd, np.float32)))

    def dispatch(args):
        if put == PUT_DELTA:
            window.x, out = _advance_sweep_jit(window.x, *args, **static)
            return out
        x = args[0]
        same, out = _proof_sweep_jit(window.x if proof else x, x,
                                     np.int32(d), *args[1:], **static)
        window.x = x
        return out + ((same,) if proof else ())
    out, chip = _launch(R, h2d, put, device, put_args, dispatch)
    window.tick_end, window.device = int(tick_end), device
    return SweepPending(out[:4], chip, None,
                        (out[4], window.owner) if proof else None)


def sweep_rows(lat: np.ndarray, wn: int, bn: int, ticks: np.ndarray,
               threshold: float = 3.0, persistence: float = 0.0,
               valid_n: Optional[np.ndarray] = None,
               moments: Optional[Tuple[np.ndarray, np.ndarray]] = None,
               argmax_fallback: bool = False, eps: float = SWEEP_GUARD_EPS,
               use_kernel: bool = False,
               block_t: Optional[int] = None,
               valid: Optional[np.ndarray] = None,
               device=None,
               ) -> Tuple[np.ndarray, np.ndarray, np.ndarray, np.ndarray]:
    """Batched :func:`repro.core.spike.detect_sweep` over a latency slab:
    :func:`sweep_launch` and its ``collect()`` back to back.

    ``lat`` (rows, T) — any dtype, staged to f32 for the dispatch; every
    row is evaluated at the shared ``ticks`` (each in ``[wn + bn, T]``)
    against its own rolling baseline, in ONE jit dispatch (masked-XLA ref
    by default; ``use_kernel=True`` for the Pallas kernel, compiled on a
    TPU and interpreted on the CPU — see ``kernels.backend``).  Returns
    ``(fire, score, onset, marginal)`` numpy arrays of shape (rows,
    #ticks):

      fire      bool, the full scalar detect rule per (row, tick);
      score     f32 max-z (0 where the tick is masked);
      onset     first above-threshold window index; -1 when nothing
                crosses, or the arg-max-z sample with
                ``argmax_fallback=True`` (the ``detect_rows`` fleet
                convention — see core.spike);
      marginal  bool, some window z within ``eps`` of the threshold —
                or, under ``argmax_fallback``, a no-hot-sample tick whose
                top two z values near-tie (the f32 arg-max could swap) —
                the ticks an exactness-seeking caller re-decides in f64.

    ``valid_n`` gives ragged per-row valid lengths (rows are only
    evaluated at ticks ``<= valid_n[row]``; masked ticks report fire
    False / onset -1).  ``moments`` overrides the exact-f64 rolling
    (mu, sd) prep — the fleet detect path passes ``detect_rows``-style
    direct moments so the single-tick decision matches its oracle.

    ``valid`` (rows, T) bool adds per-tick validity (chaos hardening):
    invalid cells are staged as ``MASK_NEG`` — the same sentinel the
    kernels already use for padded lanes — so their z is astronomically
    negative and they can neither look hot nor win the max/argmax;
    rolling moments come from the masked prefix pass, and ticks whose
    baseline holds fewer than ``MIN_VALID_BASELINE_N`` valid samples (or
    whose window holds no valid cell) are forced quiet host-side after
    the dispatch.  An all-true mask is dropped before staging, so the
    clean path is byte-identical to ``valid=None``.

    ``device`` pins the jit dispatch to one ``jax.Device`` (sharded fleet
    monitoring places each shard's sweep on its own mesh device); None
    keeps JAX's default placement.  Placement never changes the decision
    — moments are exact f64 host-side and marginal ticks re-decide
    through the f64 oracle regardless of where the f32 sweep ran.
    """
    return sweep_launch(lat, wn, bn, ticks, threshold, persistence,
                        valid_n=valid_n, moments=moments,
                        argmax_fallback=argmax_fallback, eps=eps,
                        use_kernel=use_kernel, block_t=block_t,
                        valid=valid, device=device).collect()


def sweep_launch(lat: np.ndarray, wn: int, bn: int, ticks: np.ndarray,
                 threshold: float = 3.0, persistence: float = 0.0,
                 valid_n: Optional[np.ndarray] = None,
                 moments: Optional[Tuple[np.ndarray, np.ndarray]] = None,
                 argmax_fallback: bool = False,
                 eps: float = SWEEP_GUARD_EPS, use_kernel: bool = False,
                 block_t: Optional[int] = None,
                 valid: Optional[np.ndarray] = None,
                 device=None):
    """The first half of :func:`sweep_rows` (same arguments): f32
    staging, the host->device put, the asynchronous dispatch and the
    start of the results' copies back to the host.  Returns
    a :class:`SweepPending` whose ``collect()`` pulls the results, so the
    host can prepare and launch other sweeps — on other devices — while
    this one runs.  A window held on its device between sweeps goes
    through :func:`window_launch` instead."""
    lat = np.asarray(lat)
    if lat.ndim != 2:
        raise ValueError(f"lat must be (rows, T), got {lat.shape}")
    R, T = lat.shape
    wn, bn = int(wn), int(bn)
    ticks = np.asarray(ticks, dtype=np.int64)
    nt = ticks.size
    if nt == 0:
        e = np.empty((R, 0))
        return Resolved((e.astype(bool), e.astype(np.float64),
                         e.astype(np.intp), e.astype(bool)))
    if ticks.min() < wn + bn or ticks.max() > T:
        raise ValueError(f"ticks must lie in [{wn + bn}, {T}]")
    if valid_n is None:
        vn = np.full(R, T, np.int64)
    else:
        vn = np.asarray(valid_n, np.int64)
        if vn.shape != (R,):
            raise ValueError(f"valid_n {vn.shape} vs rows {R}")
    vmask = None
    if valid is not None:
        vmask = np.asarray(valid, bool)
        if vmask.shape != (R, T):
            raise ValueError(f"valid {vmask.shape} vs lat {lat.shape}")
        if vmask.all():
            vmask = None
    bcnt = None
    if moments is None:
        if vmask is None:
            moments = rolling_moments(np.asarray(lat, np.float64), ticks,
                                      wn, bn,
                                      None if valid_n is None else vn)
        else:
            mm, ss, bcnt = rolling_moments_masked(
                np.asarray(lat, np.float64), vmask, ticks, wn, bn,
                None if valid_n is None else vn)
            moments = (mm, ss)
    mu, sd = moments
    static = _static(wn, threshold, persistence, eps, argmax_fallback,
                     use_kernel, device, block_t)
    lat32 = np.ascontiguousarray(lat, np.float32)
    if vmask is not None:
        lat32 = np.where(vmask, lat32, np.float32(spike_mod.MASK_NEG))
    h2d = lat32.nbytes + 4 * (np.size(mu) + np.size(sd) + nt + R)
    out, chip = _launch(
        R, h2d, PUT_FULL, device,
        lambda: (jnp.asarray(lat32),
                 jnp.asarray(np.asarray(mu, np.float32)),
                 jnp.asarray(np.asarray(sd, np.float32)),
                 jnp.asarray(ticks, jnp.int32), jnp.asarray(vn, jnp.int32)),
        lambda args: _sweep_jit(*args, **static))
    return SweepPending(out, chip, None if vmask is None
                        else (vmask, bcnt, ticks, wn))
