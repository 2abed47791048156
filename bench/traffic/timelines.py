"""Periodic per-host telemetry timelines, the one generator every traffic
mix drives.

A mix is a JSON file of parameters (``bench/traffic/<name>.json``).  From
it and ``--seed`` this module builds the stream a cell replays:

* a fixed pool of unique host traces, one period long, made by the frozen
  ``make_trial`` copy from ``pool_seed`` (quiet traces, and faulted traces
  when the mix has ``fault_every``);
* every row of the stream is one pool trace rotated by a phase: a periodic
  signal, so the stream never runs out, and any window of ``window_n``
  ticks is a zero-copy slice of a row stored ``period + window_n`` long;
* faulted rows take the pool's faulted traces at evenly staggered phases
  (onsets spread over the whole period), quiet rows random traces at
  random phases.  ``--seed`` decides which row gets which trace and phase;
  every seed gets the same multiset of faulted (trace, phase) pairs, so
  the work a round does is the same for every seed, in another order.
"""
from __future__ import annotations

import dataclasses
from typing import List

import numpy as np

from bench.traffic.generator import make_trial

#: offset between the quiet and the faulted pool seeds
_FAULT_SEED_OFFSET = 100_000


@dataclasses.dataclass
class Stream:
    """``rows[d]`` is distinct row ``d``: (C, period + window_n) f32, its
    column ``t`` the value at absolute tick ``t mod period`` for every
    ``t`` in ``[0, period + window_n)``."""

    rows: np.ndarray
    channels: List[str]
    period: int
    window_n: int
    #: (n_rows,) bool, row carries the mix's fault
    faulted: np.ndarray

    def offset(self, tick_end: int) -> int:
        """Column of the first tick of the window ending at ``tick_end``
        (exclusive absolute tick)."""
        return (int(tick_end) - self.window_n) % self.period


def _pool(traffic: dict, n: int, seed0: int, intensity: float, rate_hz: float):
    period_s = float(traffic["period_s"])
    out = []
    channels = None
    for u in range(n):
        _, data, channels = make_trial(
            seed0 + u, traffic["disturbance"], duration_s=period_s,
            rate_hz=rate_hz, t_on=float(traffic["t_on_s"]),
            intensity=intensity,
            confuser_prob=float(traffic["confuser_prob"]))
        out.append(data.astype(np.float32))
    return out, channels


def build(traffic: dict, faulted: np.ndarray, seed: int, rate_hz: float,
          window_n: int) -> Stream:
    """The stream for ``faulted.size`` distinct rows (``faulted[d]`` says
    whether row ``d`` carries the mix's fault)."""
    faulted = np.asarray(faulted, bool)
    if not traffic.get("fault_every"):
        faulted = np.zeros_like(faulted)
    period = int(round(float(traffic["period_s"]) * rate_hz))
    rng = np.random.default_rng(int(seed))
    quiet, channels = _pool(traffic, int(traffic["quiet_pool"]),
                            int(traffic["pool_seed"]), 0.0, rate_hz)
    bad = []
    if faulted.any():
        bad, _ = _pool(traffic, int(traffic["fault_pool"]),
                       int(traffic["pool_seed"]) + _FAULT_SEED_OFFSET,
                       float(traffic["intensity"]), rate_hz)
    # each pool trace two periods and a window long: a rotation by any
    # phase in [0, period), then period + window_n ticks, is one slice
    ext = [np.concatenate([p, p, p[:, :window_n]], axis=1)
           for p in quiet + bad]
    n = faulted.size
    trace = rng.integers(0, len(quiet), n)
    phase = rng.integers(0, period, n)
    fidx = np.flatnonzero(faulted)
    if fidx.size:
        # the same (trace, phase) multiset for every seed, in another order
        j = rng.permutation(fidx.size)
        trace[fidx] = len(quiet) + j % len(bad)
        phase[fidx] = (j * period) // fidx.size
    width = period + window_n
    rows = np.empty((n, len(channels), width), np.float32)
    for d in range(n):
        p = int(phase[d])
        rows[d] = ext[int(trace[d])][:, p:p + width]
    return Stream(rows=rows, channels=list(channels), period=period,
                  window_n=int(window_n), faulted=faulted)
