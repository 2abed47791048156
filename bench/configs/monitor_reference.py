"""Plain reference of the fleet monitor's round, for both configurations.

Straight numpy, written from the paper's description (arXiv:2510.16946,
§2.2 and §5.1) and the configuration files; it imports nothing of the
program.  One round over a (hosts, C, T) window:

* Layer 2: per host, baseline moments over the ``bn`` ticks before the
  ``wn``-tick detection window (sigma floored at max(1e-9, 1e-3 |mu|)),
  max z-score over the window, a host fires when the score tops the
  threshold and at least ``persistence`` of the window is above it; the
  onset is the first hot sample, else the arg-max z sample.
* order: flagged hosts by score descending, host id ascending on ties;
  the first ``rca_top_k`` (all when None) get RCA, the rest are deferred.
* Layer 3, per RCA'd host: the evidence block is the latency channel and
  every channel with a cause, over the RCA span (2.5 s before the window,
  the window, 2 s after it, clipped to the snapshot) and up to ``bn``
  baseline ticks before that span; each evidence channel is oriented
  about its baseline mean (a rise, a drop, or either way), scored by its
  max z over the span, and correlated with the latency channel at lags
  -K..K (overlap-only products, whole-span norms).  Confidence is
  ``alpha * S / (S + 3) + (1 - alpha) * max|rho|``; a cause takes its
  best channel, causes rank by confidence.  Co-causes: runners-up whose
  symptom channel deviates by at least its floor (two-sided raw z of the
  span mean) and whose confidence is within the cause's gap of the top.
* lifecycle: a host's strikes count its consecutive flagged rounds; a
  flagged host with three strikes is excluded and rescaled, a deferred
  one gets no action yet, an RCA'd one the action of its top cause.

``precision`` "float64" is the reference; "bfloat16" rounds the window to
bfloat16 and computes in float32 (the control).
"""
from __future__ import annotations

from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

#: evidence channels and the cause each is evidence for (the paper's
#: probe groups: NET -> NIC, SCHED -> CPU, block I/O and DMA -> I/O,
#: device throttle indicators -> GPU; device utilisation and memory and
#: the latency series itself are not evidence)
CAUSE: Dict[str, str] = {
    "net_rx_softirq": "nic_contention", "net_tx_softirq": "nic_contention",
    "nic_rx_bytes": "nic_contention", "nic_tx_bytes": "nic_contention",
    "nic_rx_drops": "nic_contention",
    "sched_switch_rate": "cpu_contention", "runqueue_len": "cpu_contention",
    "involuntary_ctx": "cpu_contention", "cpu_util_other": "cpu_contention",
    "blkio_read_bytes": "io_pressure", "blkio_write_bytes": "io_pressure",
    "blkio_inflight": "io_pressure", "iowait_frac": "io_pressure",
    "pcie_h2d_bytes": "io_pressure", "pcie_d2h_bytes": "io_pressure",
    "dev_power": "gpu_throttling", "dev_temp": "gpu_throttling",
    "dev_clock": "gpu_throttling",
}
#: +1 a rise is anomalous, -1 a drop, 0 either way
ORIENT: Dict[str, float] = {"dev_clock": -1.0, "dev_power": -1.0,
                            "pcie_h2d_bytes": 0.0, "pcie_d2h_bytes": 0.0}
#: symptom channels and their corroboration floors (two-sided raw z)
SYMPTOMS: Tuple[Tuple[str, float], ...] = (
    ("nic_rx_drops", 1.5), ("involuntary_ctx", 6.0),
    ("pcie_h2d_bytes", 1.0), ("pcie_d2h_bytes", 1.0), ("dev_temp", 2.0))
#: confidence gap within which a corroborated runner-up is a co-cause
CO_GAP: Dict[str, float] = {"io_pressure": 0.30, "nic_contention": 0.15,
                            "gpu_throttling": 0.12, "cpu_contention": 0.08}
ACTION: Dict[str, str] = {
    "io_pressure": "rebalance_input_pipeline",
    "cpu_contention": "repin_or_isolate_cpu",
    "nic_contention": "fallback_hierarchical_allreduce",
    "gpu_throttling": "review_power_thermal_policy",
}
EXCLUDE = "checkpoint_exclude_host_rescale"
NO_ACTION = "none"
LATENCY = "coll_allreduce_ms"
MIN_BASELINE_N = 32


def _cast(x: np.ndarray, precision: str) -> np.ndarray:
    if precision == "float64":
        return np.asarray(x, np.float64)
    if precision == "bfloat16":
        import ml_dtypes
        return np.asarray(x, np.float32).astype(ml_dtypes.bfloat16).astype(
            np.float32)
    raise ValueError(f"unknown precision {precision!r}")


class Geometry:
    """Window sizes of one configuration, from its ``monitor`` block."""

    def __init__(self, mon: dict, rate_hz: float, T: int,
                 channels: Sequence[str]):
        self.wn = min(int(mon["window_s"] * rate_hz), T // 2)
        self.bn = min(int(mon["baseline_s"] * rate_hz), T - self.wn)
        self.thr = float(mon["threshold"])
        self.pers = float(mon["persistence"])
        self.K = int(mon["max_lag"])
        self.alpha = float(mon["alpha"])
        self.cocause = int(mon["max_hypotheses"]) > 1
        self.strikes_to_exclude = int(mon["persistent_threshold"])
        pre = int(mon["pre_onset_s"] * rate_hz)
        post = int(mon["rca_extra_s"] * rate_hz)
        self.rn = min(T, pre + self.wn + post)
        nb = min(self.bn, T - self.rn)
        self.nb = nb if nb >= MIN_BASELINE_N else 0
        self.channels = list(channels)
        self.li = self.channels.index(LATENCY)
        self.ev = [i for i, c in enumerate(self.channels)
                   if c in CAUSE and c != LATENCY]
        self.ev_names = [self.channels[i] for i in self.ev]


def detect(tail: np.ndarray, g: Geometry, precision: str = "float64"):
    """(fire, score, onset) per row of the (H, bn + wn) latency tail;
    onset relative to the detection window."""
    x = _cast(tail, precision)
    b, w = x[:, :g.bn], x[:, g.bn:]
    mu = b.mean(axis=1)
    sd = np.maximum(b.std(axis=1), np.maximum(1e-9, 1e-3 * np.abs(mu)))
    z = (w - mu[:, None]) / sd[:, None]
    score = z.max(axis=1)
    hot = z > g.thr
    fire = (score > g.thr) & (hot.mean(axis=1) >= g.pers)
    onset = np.where(hot.any(axis=1), hot.argmax(axis=1), z.argmax(axis=1))
    return fire, score.astype(np.float64), onset


def rca(block: np.ndarray, g: Geometry, precision: str = "float64",
        ) -> List[dict]:
    """Layer 3 for a (B, C, nb + rn) stack of RCA'd hosts' trailing
    columns: per host ``{"conf": {cause: c}, "ranked": [causes],
    "causes": [primary, co-causes...], "gap": {...}}``."""
    x = _cast(block, precision)
    nb, rn = g.nb, g.rn
    b_sl = slice(0, nb) if nb > 0 else slice(0, nb + rn)
    L = x[:, g.li, nb:]
    Xm = x[:, g.ev, :]
    o = np.array([ORIENT.get(c, 1.0) for c in g.ev_names],
                 x.dtype).reshape(-1, 1)
    mb = Xm[..., b_sl].mean(axis=-1, keepdims=True)
    dev = Xm - mb
    XO = mb + np.where(o == 0.0, np.abs(dev), o * dev)
    W, B = XO[..., nb:], XO[..., b_sl]
    mu = B.mean(axis=-1)
    sd = np.maximum(B.std(axis=-1), np.maximum(1e-9, 1e-3 * np.abs(mu)))
    S = ((W - mu[..., None]) / sd[..., None]).max(axis=-1)      # (H, M)
    Lc = L - L.mean(axis=-1, keepdims=True)
    Mc = W - W.mean(axis=-1, keepdims=True)
    Ln = np.sqrt((Lc * Lc).sum(axis=-1)) + 1e-12
    Mn = np.sqrt((Mc * Mc).sum(axis=-1)) + 1e-12
    N, K = W.shape[-1], g.K
    rho = np.empty(S.shape + (2 * K + 1,), x.dtype)
    for j, k in enumerate(range(-K, K + 1)):
        if k >= 0:
            rho[..., j] = np.einsum("ht,hmt->hm", Lc[:, k:], Mc[..., :N - k])
        else:
            rho[..., j] = np.einsum("ht,hmt->hm", Lc[:, :N + k], Mc[..., -k:])
    rho = rho / (Mn[..., None] * Ln[:, None, None])
    c = np.abs(rho).max(axis=-1)
    s = np.maximum(np.asarray(S, np.float64), 0.0)
    conf = (g.alpha * s / (s + 3.0)
            + (1.0 - g.alpha) * np.clip(np.asarray(c, np.float64), 0.0, 1.0))
    causes: List[str] = []
    for n in g.ev_names:
        if CAUSE[n] not in causes:
            causes.append(CAUSE[n])
    sym_z = {}
    for name, floor in SYMPTOMS:
        if name not in g.channels:
            continue
        seg = x[:, g.channels.index(name), :]
        Bs, Ws = seg[:, b_sl], seg[:, nb:]
        m = Bs.mean(axis=1)
        sdv = np.maximum(Bs.std(axis=1), np.maximum(1e-3 * np.abs(m), 1e-9))
        sym_z.setdefault(CAUSE[name], []).append(
            (np.abs(Ws.mean(axis=1) - m) / sdv, floor))
    out = []
    for h in range(conf.shape[0]):
        best = {}
        for cause in causes:
            cols = [j for j, n in enumerate(g.ev_names) if CAUSE[n] == cause]
            best[cause] = float(max(conf[h, j] for j in cols))
        ranked = sorted(causes, key=lambda cz: -best[cz])
        top = best[ranked[0]]
        chosen = [ranked[0]]
        margins = {}
        if g.cocause:
            for cz in ranked[1:]:
                zs = sym_z.get(cz, [])
                ok = any(z[h] >= f for z, f in zs)
                margins[cz] = min([abs(z[h] - f) / max(f, 1.0) for z, f in zs]
                                  + [abs(top - best[cz] - CO_GAP[cz])])
                if ok and top - best[cz] <= CO_GAP[cz]:
                    chosen.append(cz)
        out.append({"conf": best, "ranked": ranked, "causes": chosen,
                    "margins": margins})
    return out


def replay_lifecycle(flag_rounds: Sequence[np.ndarray],
                     score_rounds: Sequence[np.ndarray],
                     top_k: Optional[int], g: Geometry) -> List[dict]:
    """Per round: ``{"flagged": ordered ids, "rca": ids, "deferred": ids,
    "strikes": {host: n}}`` from each round's fire mask and scores."""
    strikes: Dict[int, int] = {}
    out = []
    for fire, score in zip(flag_rounds, score_rounds):
        cand = np.flatnonzero(fire)
        order = np.argsort(-score[cand], kind="stable")
        flagged = [int(h) for h in cand[order]]
        keep = set(flagged)
        strikes = {h: n for h, n in strikes.items() if h in keep}
        for h in flagged:
            strikes[h] = strikes.get(h, 0) + 1
        k = len(flagged) if top_k is None else min(int(top_k), len(flagged))
        out.append({"flagged": flagged, "rca": flagged[:k],
                    "deferred": flagged[k:], "strikes": dict(strikes)})
    return out


def action(cause: Optional[str], strikes: int, deferred: bool,
           g: Geometry) -> str:
    """The mitigation a flagged host gets this round."""
    if strikes >= g.strikes_to_exclude:
        return EXCLUDE
    if deferred or cause is None:
        return NO_ACTION
    return ACTION.get(cause, NO_ACTION)
