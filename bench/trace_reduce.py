"""Reduction of a JAX profiler trace to the benchmark's device numbers.

Reads the ``.xplane.pb`` the profiler writes (``jax.profiler.ProfileData``,
nothing but JAX).  The window is the span from the start of the first
harness ``round`` span to the end of the last one.  Device planes are the
``/device:TPU:<n>`` planes; their operations are the events of the
``XLA Ops`` line, named by their HLO instruction (``%_sweep_jit.1 = ...``
is ``_sweep_jit``: the text before `` = ``, without ``%`` and the
instruction's ``.<n>`` suffix).  Busy time is the union of operation intervals inside
the window, averaged over the device planes that ran any.  Idle time is
attributed to what the host was doing: the window is cut at every harness
span's (``TraceAnnotation``) start and end, each piece is labelled with the
innermost span that covers it, and the piece's idle device time is added
to that label.

``bench/tests/test_trace_reduce.py`` checks this against a small trace
recorded on a TPU v5 lite (``bench/testdata/``).
"""
from __future__ import annotations

import dataclasses
import glob
import os
import re
from typing import Dict, Iterable, List, Tuple

import numpy as np

OPS_LINE = "XLA Ops"
WINDOW_SPAN = "round"
_SUFFIX = re.compile(r"\.\d+$")


def op_name(event_name: str) -> str:
    """Short op name of a device event (see module docstring)."""
    return _SUFFIX.sub("", event_name.split(" = ", 1)[0].lstrip("%"))


@dataclasses.dataclass
class TraceSummary:
    #: device-busy seconds in the window, averaged over the busy devices
    busy_s: float
    #: window length in seconds
    window_s: float
    #: op name -> (count, device seconds inside the window)
    ops: Dict[str, Tuple[int, float]]
    #: harness span (or "outside spans") -> idle device seconds under it
    idle: Dict[str, float]
    #: devices that ran an operation in the window
    n_devices: int

    def op_seconds(self, match) -> Tuple[int, float]:
        """(count, seconds) summed over ops whose name satisfies
        ``match`` (a predicate on the name)."""
        n, s = 0, 0.0
        for name, (c, sec) in self.ops.items():
            if match(name):
                n += c
                s += sec
        return n, s

    def breakdown(self) -> dict:
        top = sorted(self.ops.items(), key=lambda kv: -kv[1][1])[:10]
        idle = sorted(self.idle.items(), key=lambda kv: -kv[1])[:10]
        return {"device_ops": [[n, float(sec) / max(self.n_devices, 1)]
                               for n, (_, sec) in top],
                "idle_gaps": [[n, float(s)] for n, s in idle]}


def _union(iv: np.ndarray) -> np.ndarray:
    """Merged, sorted (k, 2) intervals."""
    if not len(iv):
        return np.zeros((0, 2))
    iv = iv[np.argsort(iv[:, 0], kind="stable")]
    out = [list(iv[0])]
    for a, b in iv[1:]:
        if a <= out[-1][1]:
            out[-1][1] = max(out[-1][1], b)
        else:
            out.append([a, b])
    return np.array(out)


def find_xplane(trace_dir) -> str:
    paths = sorted(glob.glob(os.path.join(str(trace_dir), "**",
                                          "*.xplane.pb"), recursive=True))
    if not paths:
        raise FileNotFoundError(f"no .xplane.pb under {trace_dir}")
    return paths[-1]


def reduce_dir(trace_dir, spans: Iterable[str]) -> TraceSummary:
    from jax.profiler import ProfileData
    return reduce(ProfileData.from_file(find_xplane(trace_dir)), spans)


def reduce(pd, spans: Iterable[str]) -> TraceSummary:
    spans = set(spans)
    host: List[Tuple[str, float, float]] = []
    dev_ops: List[List[Tuple[str, float, float]]] = []
    for plane in pd.planes:
        if plane.name.startswith("/device:TPU:"):
            ops = []
            for line in plane.lines:
                if line.name != OPS_LINE:
                    continue
                for e in line.events:
                    ops.append((op_name(e.name), float(e.start_ns),
                                float(e.end_ns)))
            dev_ops.append(ops)
        elif plane.name.startswith("/host:"):
            for line in plane.lines:
                for e in line.events:
                    if e.name in spans:
                        host.append((e.name, float(e.start_ns),
                                     float(e.end_ns)))
    rounds = [(a, b) for n, a, b in host if n == WINDOW_SPAN]
    if not rounds:
        raise ValueError("trace holds no harness 'round' span")
    w0 = min(a for a, _ in rounds)
    w1 = max(b for _, b in rounds)
    window = (w1 - w0) * 1e-9
    ops: Dict[str, List[float]] = {}
    busy, idle_all = [], {}
    for dops in dev_ops:
        iv = []
        for name, a, b in dops:
            a, b = max(a, w0), min(b, w1)
            if b <= a:
                continue
            iv.append((a, b))
            c = ops.setdefault(name, [0, 0.0])
            c[0] += 1
            c[1] += (b - a) * 1e-9
        if not iv:
            continue
        u = _union(np.array(iv))
        busy.append(float((u[:, 1] - u[:, 0]).sum()) * 1e-9)
        # busy time before t, for any t: piecewise linear over the union
        cum = np.concatenate([[0.0], np.cumsum(u[:, 1] - u[:, 0])])
        xs = np.concatenate([[w0], u.ravel(), [w1]])
        ys = np.concatenate([[0.0], np.repeat(cum[:-1], 2)
                             + np.tile([0.0, 1.0], len(u))
                             * np.repeat(u[:, 1] - u[:, 0], 2), [cum[-1]]])
        cuts = np.unique(np.clip(np.array(
            [w0, w1] + [t for _, a, b in host for t in (a, b)]), w0, w1))
        lo, hi = cuts[:-1], cuts[1:]
        mid = 0.5 * (lo + hi)
        best = np.full(mid.size, np.inf)
        label = np.full(mid.size, -1)
        for j, (name, a, b) in enumerate(host):
            hit = (a <= mid) & (mid <= b) & (b - a < best)
            best[hit] = b - a
            label[hit] = j
        idle = (hi - lo) - (np.interp(hi, xs, ys) - np.interp(lo, xs, ys))
        for sec, j in zip(idle * 1e-9, label):
            if sec <= 0:
                continue
            lab = host[j][0] if j >= 0 else "outside spans"
            idle_all[lab] = idle_all.get(lab, 0.0) + sec
    n = len(busy)
    return TraceSummary(
        busy_s=float(np.mean(busy)) if busy else 0.0, window_s=window,
        ops={k: (int(v[0]), float(v[1])) for k, v in ops.items()},
        idle={k: v / max(n, 1) for k, v in idle_all.items()}, n_devices=n)
