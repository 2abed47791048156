"""Frozen copy of the trial generator the benchmark's traffic is made from.

Provenance: copied from the program's simulator at the commit that
introduced this benchmark — ``repro.sim.scenario.make_trial`` and
``finalize_trial_channels``, ``repro.sim.hostmodel`` (``ChannelModel``,
``DEFAULT_CHANNELS``, ``HostSignalModel``), ``repro.sim.disturbances``
(envelopes, ``DISTURBANCES``, ``apply_disturbance``, ``inject_confuser``,
``PRIMARY_CHANNELS``) and ``repro.sim.workload`` (``MESSAGE_SIZES``,
``AllReduceWorkload``).  Only the data path was kept: cause classes are
plain strings, and ``make_trial`` returns ``(ts, data, channels)``.  The
arithmetic and the order of random draws are unchanged, so for the same
arguments the output is byte-equal to the original
(``bench/tests/test_generator_copy.py`` checks it while the original
exists).  The benchmark imports this copy, never the program's
simulator, so a change to the program cannot change the traffic.
"""
from __future__ import annotations

import dataclasses
from typing import Callable, Dict, List, Optional, Sequence, Tuple

import numpy as np

# ------------------------------------------------------------ host model


@dataclasses.dataclass(frozen=True)
class ChannelModel:
    base: float
    sd: float
    ar_rho: float = 0.9
    nonneg: bool = True
    burst_rate_hz: float = 0.0
    burst_amp: float = 0.0
    burst_dur_s: float = 1.0


DEFAULT_CHANNELS: Dict[str, ChannelModel] = {
    "net_rx_softirq":   ChannelModel(2000.0, 300.0, 0.9, True, 1 / 40.0, 7.0, 0.8),
    "net_tx_softirq":   ChannelModel(1500.0, 250.0, 0.9, True, 1 / 50.0, 6.0, 0.8),
    "nic_rx_bytes":     ChannelModel(5e6, 1.5e6, 0.92, True, 1 / 40.0, 8.0, 1.0),
    "nic_tx_bytes":     ChannelModel(4e6, 1.2e6, 0.92, True, 1 / 50.0, 8.0, 1.0),
    "nic_rx_drops":     ChannelModel(0.5, 0.4, 0.5, True, 1 / 120.0, 6.0, 0.5),
    "sched_switch_rate": ChannelModel(9000.0, 900.0, 0.9, True, 1 / 45.0, 6.0, 1.2),
    "runqueue_len":      ChannelModel(2.0, 0.7, 0.85, True, 1 / 60.0, 5.0, 1.5),
    "involuntary_ctx":   ChannelModel(60.0, 20.0, 0.8, True, 1 / 60.0, 6.0, 1.0),
    "cpu_util_other":    ChannelModel(0.12, 0.03, 0.93, True, 1 / 50.0, 5.0, 2.0),
    "blkio_read_bytes":  ChannelModel(2e6, 8e5, 0.88, True, 1 / 35.0, 9.0, 1.0),
    "blkio_write_bytes": ChannelModel(3e6, 1e6, 0.88, True, 1 / 30.0, 9.0, 1.2),
    "blkio_inflight":    ChannelModel(1.0, 0.5, 0.8, True, 1 / 40.0, 6.0, 1.0),
    "iowait_frac":       ChannelModel(0.01, 0.004, 0.9, True, 1 / 45.0, 6.0, 1.0),
    "pcie_h2d_bytes":    ChannelModel(8e9, 6e8, 0.9, True, 1 / 70.0, 4.0, 1.0),
    "pcie_d2h_bytes":    ChannelModel(1e9, 1e8, 0.9, True, 1 / 70.0, 4.0, 1.0),
    "dev_util":      ChannelModel(0.93, 0.015, 0.95, True, 0.0, 0.0, 0.0),
    "dev_mem_used":  ChannelModel(62e9, 2e8, 0.98, True, 0.0, 0.0, 0.0),
    "dev_power":     ChannelModel(385.0, 6.0, 0.95, True, 1 / 90.0, 3.0, 1.5),
    "dev_temp":      ChannelModel(64.0, 0.6, 0.99, True, 0.0, 0.0, 0.0),
    "dev_clock":     ChannelModel(1410.0, 8.0, 0.9, True, 1 / 90.0, 3.0, 1.0),
}


class HostSignalModel:
    def __init__(self, rate_hz: float = 100.0):
        self.models = dict(DEFAULT_CHANNELS)
        self.rate_hz = float(rate_hz)

    def _ar1(self, rng: np.random.Generator, T: int, rho: float) -> np.ndarray:
        eps = rng.standard_normal(T)
        out = np.empty(T)
        acc = 0.0
        c = np.sqrt(max(1.0 - rho * rho, 1e-12))
        for t in range(T):
            acc = rho * acc + c * eps[t]
            out[t] = acc
        return out

    def _bursts(self, rng: np.random.Generator, T: int,
                m: ChannelModel) -> np.ndarray:
        out = np.zeros(T)
        if m.burst_rate_hz <= 0 or m.burst_amp <= 0:
            return out
        n_expected = m.burst_rate_hz * T / self.rate_hz
        n = rng.poisson(n_expected)
        for _ in range(n):
            t0 = rng.integers(0, T)
            dur = max(1, int(rng.exponential(m.burst_dur_s) * self.rate_hz))
            amp = m.sd * m.burst_amp * rng.lognormal(0.0, 0.5)
            t1 = min(T, t0 + dur)
            env = np.sin(np.linspace(0, np.pi, t1 - t0))
            out[t0:t1] += amp * env
        return out

    def generate(self, rng: np.random.Generator, T: int,
                 ) -> Tuple[List[str], np.ndarray]:
        names = list(self.models)
        data = np.empty((len(names), T), dtype=np.float64)
        for i, name in enumerate(names):
            m = self.models[name]
            x = m.base + m.sd * self._ar1(rng, T, m.ar_rho) + self._bursts(rng, T, m)
            if m.nonneg:
                np.maximum(x, 0.0, out=x)
            data[i] = x
        return names, data


# ---------------------------------------------------------- disturbances


def _smoothstep(x: np.ndarray) -> np.ndarray:
    x = np.clip(x, 0.0, 1.0)
    return x * x * (3 - 2 * x)


def env_sustained(rng, T, rate, t_on, dur, rise_s=0.6):
    t = np.arange(T) / rate
    up = _smoothstep((t - t_on) / rise_s)
    down = _smoothstep((t_on + dur - t) / rise_s)
    return np.minimum(up, down)


def env_ramp(rng, T, rate, t_on, dur, ramp_s=4.5):
    t = np.arange(T) / rate
    up = _smoothstep((t - t_on) / ramp_s)
    down = _smoothstep((t_on + dur - t) / 0.8)
    return np.minimum(up, down)


def env_bursty(rng, T, rate, t_on, dur, period_s=None, duty=None):
    if period_s is None:
        period_s = float(rng.uniform(1.2, 2.6))
    if duty is None:
        duty = float(rng.uniform(0.32, 0.55))
    base = env_sustained(rng, T, rate, t_on, dur, rise_s=0.3)
    t = np.arange(T) / rate
    phase = rng.uniform(0, period_s)
    cyc = ((t + phase) % period_s) / period_s
    gate = (cyc < duty).astype(np.float64)
    k = max(1, int(0.05 * rate))
    kernel = np.ones(k) / k
    gate = np.convolve(gate, kernel, mode="same")
    return base * gate


ENVELOPES: Dict[str, Callable] = {
    "sustained": env_sustained,
    "ramp": env_ramp,
    "bursty": env_bursty,
}


@dataclasses.dataclass(frozen=True)
class ChannelEffect:
    channel: str
    amp: float
    mode: str = "add"
    lag_s: float = 0.0


@dataclasses.dataclass(frozen=True)
class Disturbance:
    kind: str
    name: str
    envelope: str
    effects: Tuple[ChannelEffect, ...]
    latency_amp: float
    latency_lag_s: float
    dur_s: Tuple[float, float]
    intensity_sigma: float = 0.35


DISTURBANCES: Dict[str, Disturbance] = {
    "io": Disturbance(
        kind="io_pressure", name="D1-io-pressure", envelope="sustained",
        effects=(
            ChannelEffect("blkio_read_bytes", 1.1e9),
            ChannelEffect("blkio_write_bytes", 1.4e9),
            ChannelEffect("blkio_inflight", 48.0),
            ChannelEffect("iowait_frac", 0.35),
            ChannelEffect("pcie_h2d_bytes", -2.5e9, lag_s=0.03),
            ChannelEffect("pcie_d2h_bytes", -2.0e8, lag_s=0.03),
            ChannelEffect("sched_switch_rate", 2500.0),
            ChannelEffect("runqueue_len", 1.0),
            ChannelEffect("cpu_util_other", 0.06),
            ChannelEffect("dev_util", -0.08, lag_s=0.08),
        ),
        latency_amp=0.55, latency_lag_s=0.08, dur_s=(18.0, 30.0)),
    "cpu": Disturbance(
        kind="cpu_contention", name="D2-cpu-contention", envelope="sustained",
        effects=(
            ChannelEffect("cpu_util_other", 0.72),
            ChannelEffect("runqueue_len", 9.0),
            ChannelEffect("involuntary_ctx", 1800.0),
            ChannelEffect("sched_switch_rate", 14000.0),
            ChannelEffect("net_rx_softirq", 500.0, lag_s=0.05),
            ChannelEffect("dev_util", -0.12, lag_s=0.06),
        ),
        latency_amp=0.65, latency_lag_s=0.05, dur_s=(18.0, 30.0)),
    "nic": Disturbance(
        kind="nic_contention", name="D3-nic-burst", envelope="bursty",
        effects=(
            ChannelEffect("net_rx_softirq", 55000.0),
            ChannelEffect("net_tx_softirq", 9000.0),
            ChannelEffect("nic_rx_bytes", 1.15e9),
            ChannelEffect("nic_tx_bytes", 2.5e8),
            ChannelEffect("nic_rx_drops", 900.0, lag_s=0.04),
            ChannelEffect("sched_switch_rate", 6000.0, lag_s=0.02),
            ChannelEffect("cpu_util_other", 0.12, lag_s=0.02),
            ChannelEffect("runqueue_len", 1.5, lag_s=0.02),
            ChannelEffect("dev_util", -0.07, lag_s=0.08),
        ),
        latency_amp=1.1, latency_lag_s=0.06, dur_s=(15.0, 25.0)),
    "gpu": Disturbance(
        kind="gpu_throttling", name="D4-gpu-throttle", envelope="ramp",
        effects=(
            ChannelEffect("dev_power", -140.0, mode="add"),
            ChannelEffect("dev_clock", -430.0, mode="add"),
            ChannelEffect("dev_temp", -6.0, lag_s=2.0),
            ChannelEffect("dev_util", 0.04),
        ),
        latency_amp=0.5, latency_lag_s=0.10, dur_s=(20.0, 32.0)),
}

CLASS_ORDER: Sequence[str] = ("io", "cpu", "nic", "gpu")


def _shift(env: np.ndarray, lag_s: float, rate: float) -> np.ndarray:
    k = int(round(lag_s * rate))
    if k == 0:
        return env
    out = np.zeros_like(env)
    if k > 0:
        out[k:] = env[:-k]
    else:
        out[:k] = env[-k:]
    return out


def apply_disturbance(rng: np.random.Generator, channels: List[str],
                      data: np.ndarray, dist: Disturbance, rate: float,
                      t_on: float, dur: float, intensity: float,
                      ) -> np.ndarray:
    """Mutates ``data`` in place; returns the latency multiplier series."""
    T = data.shape[1]
    env_fn = ENVELOPES[dist.envelope]
    env = env_fn(rng, T, rate, t_on, dur)
    chan_env = env
    if rng.uniform() < 0.30:
        pre_t = t_on - float(rng.uniform(8.0, 16.0))
        pre_dur = float(rng.uniform(3.0, 6.0))
        pre = env_sustained(rng, T, rate, pre_t, pre_dur, rise_s=0.5)
        chan_env = np.maximum(env, float(rng.uniform(0.15, 0.30)) * pre)
    idx = {c: i for i, c in enumerate(channels)}
    for eff in dist.effects:
        i = idx.get(eff.channel)
        if i is None:
            continue
        e = _shift(chan_env, eff.lag_s + rng.normal(0.0, 0.01), rate)
        wobble = float(rng.lognormal(0.0, 0.25))
        data[i] += eff.amp * intensity * wobble * e
        np.maximum(data[i], 0.0, out=data[i])
    lag = dist.latency_lag_s + rng.normal(0.0, 0.02)
    lag2 = lag + float(rng.uniform(0.25, 0.6))
    lenv = 0.65 * _shift(env, lag, rate) + 0.35 * _shift(env, lag2, rate)
    wob = np.convolve(rng.standard_normal(T), np.ones(int(rate)) / rate,
                      mode="same")
    sd = float(np.std(wob)) + 1e-12
    lenv = lenv * np.clip(1.0 + 0.25 * wob / sd, 0.3, 1.9)
    return 1.0 + dist.latency_amp * intensity * lenv


PRIMARY_CHANNELS: Dict[str, Tuple[str, ...]] = {
    "io": ("blkio_read_bytes", "blkio_write_bytes", "blkio_inflight",
           "iowait_frac"),
    "cpu": ("cpu_util_other", "runqueue_len", "involuntary_ctx",
            "sched_switch_rate"),
    "nic": ("net_rx_softirq", "net_tx_softirq", "nic_rx_bytes",
            "nic_tx_bytes"),
    "gpu": ("dev_power", "dev_clock"),
}


def inject_confuser(rng: np.random.Generator, channels: List[str],
                    data: np.ndarray, cls: str, rate: float,
                    t_near: float, scale: float) -> None:
    dist = DISTURBANCES[cls]
    T = data.shape[1]
    dur = float(rng.uniform(8.0, 18.0))
    t0 = t_near + float(rng.uniform(-1.0, 1.5))
    env_fn = ENVELOPES["bursty"] if rng.uniform() < 0.35 else env_sustained
    env = env_fn(rng, T, rate, t0, dur)
    idx = {c: i for i, c in enumerate(channels)}
    primaries = PRIMARY_CHANNELS[cls]
    for eff in dist.effects:
        if eff.channel not in primaries:
            continue
        i = idx.get(eff.channel)
        if i is None:
            continue
        e = _shift(env, rng.normal(0.0, 0.03), rate)
        data[i] += eff.amp * scale * float(rng.lognormal(0.0, 0.3)) * e
        np.maximum(data[i], 0.0, out=data[i])


# ------------------------------------------------------------- workload

MESSAGE_SIZES = [2 ** p for p in range(10, 27)]


@dataclasses.dataclass
class AllReduceWorkload:
    n_devices: int = 4
    msg_bytes: int = 16 * 2 ** 20
    link_bw: float = 220e9
    alpha_us: float = 6.0
    jitter_cv: float = 0.06
    ar_rho: float = 0.85

    @property
    def base_latency_ms(self) -> float:
        n, s = self.n_devices, float(self.msg_bytes)
        hops = 2 * (n - 1)
        bw_term = hops / n * s / self.link_bw
        return self.alpha_us * hops * 1e-3 + bw_term * 1e3

    def latency_series(self, rng: np.random.Generator, T: int,
                       multiplier: Optional[np.ndarray] = None) -> np.ndarray:
        sigma = np.sqrt(np.log(1.0 + self.jitter_cv ** 2))
        eps = rng.standard_normal(T)
        ar = np.empty(T)
        acc = 0.0
        c = np.sqrt(1.0 - self.ar_rho ** 2)
        for t in range(T):
            acc = self.ar_rho * acc + c * eps[t]
            ar[t] = acc
        jitter = np.exp(sigma * ar - 0.5 * sigma ** 2)
        L = self.base_latency_ms * jitter
        if multiplier is not None:
            L = L * np.asarray(multiplier, dtype=np.float64)
        return L


# ---------------------------------------------------------------- trial

LATENCY_CH = "coll_allreduce_ms"
STEP_CH = "step_latency_ms"


def finalize_trial_channels(rng: np.random.Generator, channels: List[str],
                            data: np.ndarray, mult: np.ndarray,
                            rate_hz: float,
                            msg_bytes: Optional[int] = None,
                            ) -> Tuple[List[str], np.ndarray, int]:
    T = data.shape[1]
    for i, name in enumerate(channels):
        if name.startswith("dev_"):
            k = int(rate_hz // 10)
            data[i] = np.repeat(data[i][::k], k)[: data.shape[1]]
    msg = int(msg_bytes if msg_bytes is not None
              else MESSAGE_SIZES[rng.integers(8, len(MESSAGE_SIZES))])
    wl = AllReduceWorkload(msg_bytes=msg)
    L = wl.latency_series(rng, T, multiplier=mult)
    compute_ms = 18.0 * (1.0 + 0.03 * rng.standard_normal(T))
    step = L + np.maximum(compute_ms, 0.0)
    channels = channels + [LATENCY_CH, STEP_CH]
    data = np.vstack([data, L[None, :], step[None, :]]).astype(np.float64)
    return channels, data, msg


def make_trial(seed: int, disturbance: str, *, duration_s: float = 90.0,
               rate_hz: float = 100.0, t_on: Optional[float] = None,
               intensity: Optional[float] = None,
               msg_bytes: Optional[int] = None,
               confuser_prob: float = 0.6,
               ) -> Tuple[np.ndarray, np.ndarray, List[str]]:
    """``(ts, data (C, T) f64, channels)`` of one injected-disturbance
    trial; byte-equal to the original's ``Trial.ts/data/channels``."""
    rng = np.random.default_rng(seed)
    dist = DISTURBANCES[disturbance]
    T = int(duration_s * rate_hz)
    ts = np.arange(T) / rate_hz
    hm = HostSignalModel(rate_hz=rate_hz)
    channels, data = hm.generate(rng, T)
    if t_on is None:
        t_on = float(rng.uniform(32.0, 48.0))
    dur = float(rng.uniform(*dist.dur_s))
    if intensity is None:
        intensity = float(np.clip(rng.lognormal(-0.1, 0.5), 0.33, 3.0))
    mult = apply_disturbance(rng, channels, data, dist, rate_hz,
                             t_on, dur, intensity)
    if rng.uniform() < confuser_prob:
        others = [c for c in CLASS_ORDER if c != disturbance]
        cls = others[int(rng.integers(0, len(others)))]
        inject_confuser(rng, channels, data, cls, rate_hz, t_on,
                        scale=float(rng.uniform(0.6, 1.4)))
    channels, data, _ = finalize_trial_channels(rng, channels, data, mult,
                                                rate_hz, msg_bytes)
    return ts, data, channels
