"""The program's own spans in the traced window (``--trace 1``).

The program opens a ``jax.profiler.TraceAnnotation`` at each step of a
round (``repro.core.spans``; names such as ``monitor.detect`` and
``detect.sweep``, listed in ``docs/OPERATIONS.md``).  This helper loads
the window's ``.xplane.pb`` once per file, keeps the program spans that lie
inside the harness ``round`` spans, and gives per span name its count,
total time, self time (its duration minus what its child program spans on
the same thread cover) and summed metadata.  A program without such spans
yields an empty table, and every reader then returns None.
"""
from __future__ import annotations

import dataclasses
import os
from typing import Dict, Iterable, List, Optional, Tuple

from bench import trace_reduce

#: name prefixes of the program's spans (the harness's are bare words)
PROGRAM = ("aggregator.", "assemble.", "monitor.", "shard.", "detect.",
           "sweep.", "finish.", "rca.")

_CACHE: Dict[Tuple[str, float], "Window"] = {}


@dataclasses.dataclass
class SpanStat:
    #: spans of this name in the window
    n: int = 0
    #: their summed duration, seconds
    total_s: float = 0.0
    #: summed duration minus what their child program spans cover
    self_s: float = 0.0
    #: metadata summed over the spans, by key
    meta: Dict[str, float] = dataclasses.field(default_factory=dict)


@dataclasses.dataclass
class Window:
    #: (start_ns, end_ns, thread) of each harness ``round`` span
    rounds: List[Tuple[float, float, int]]
    #: program spans inside the window: (name, start_ns, end_ns, thread,
    #: meta)
    spans: List[Tuple[str, float, float, int, Dict[str, float]]]

    @property
    def n_rounds(self) -> int:
        return len(self.rounds)

    def stats(self) -> Dict[str, SpanStat]:
        """Per span name: count, total, self time and summed meta."""
        out: Dict[str, SpanStat] = {}
        for name, a, b, _, meta, child_s in _with_children(self.spans):
            st = out.setdefault(name, SpanStat())
            st.n += 1
            st.total_s += (b - a) * 1e-9
            st.self_s += (b - a - child_s) * 1e-9
            for k, v in meta.items():
                st.meta[k] = st.meta.get(k, 0.0) + v
        return out

    def untraced_s(self, exclude: Iterable[str]) -> float:
        """Summed round time that no program span covers, other than the
        spans named in ``exclude`` (the round's outer spans)."""
        exclude = set(exclude)
        by_thread: Dict[int, List[Tuple[float, float]]] = {}
        for name, a, b, th, _ in self.spans:
            if name not in exclude:
                by_thread.setdefault(th, []).append((a, b))
        tot = 0.0
        for r0, r1, th in self.rounds:
            iv = sorted((max(a, r0), min(b, r1))
                        for a, b in by_thread.get(th, ()) if b > r0 and a < r1)
            covered, end = 0.0, r0
            for a, b in iv:
                if b > end:
                    covered += b - max(a, end)
                    end = b
            tot += r1 - r0 - covered
        return tot * 1e-9


def _with_children(spans):
    """Each span with the summed duration of its direct child spans on
    the same thread (spans on one thread nest: a child starts and ends
    inside its parent)."""
    child = [0.0] * len(spans)
    order = sorted(range(len(spans)),
                   key=lambda i: (spans[i][3], spans[i][1], -spans[i][2]))
    stack: List[int] = []
    for i in order:
        _, a, b, th, _ = spans[i]
        while stack and (spans[stack[-1]][3] != th
                         or spans[stack[-1]][2] < b):
            stack.pop()
        if stack:
            child[stack[-1]] += b - a
        stack.append(i)
    return [s + (c,) for s, c in zip(spans, child)]


def window(pd) -> Window:
    """The program spans of a ``jax.profiler.ProfileData`` inside its
    harness ``round`` spans."""
    rounds, spans = [], []
    th = 0
    for plane in pd.planes:
        if not plane.name.startswith("/host:"):
            continue
        for line in plane.lines:
            th += 1
            for e in line.events:
                if e.name == trace_reduce.WINDOW_SPAN:
                    rounds.append((float(e.start_ns), float(e.end_ns), th))
                elif e.name.startswith(PROGRAM):
                    spans.append((e.name, float(e.start_ns),
                                  float(e.end_ns), th,
                                  {k: float(v) for k, v in e.stats}))
    if rounds:
        w0 = min(a for a, _, _ in rounds)
        w1 = max(b for _, b, _ in rounds)
        spans = [s for s in spans if s[1] >= w0 and s[2] <= w1]
    else:
        spans = []
    return Window(rounds=rounds, spans=spans)


def load(run) -> Optional[Window]:
    """The traced window of ``run`` (None without a trace or without any
    program span in it), loaded once per trace file."""
    try:
        path = trace_reduce.find_xplane(run.bench_dir / ".out" / "trace")
    except FileNotFoundError:
        return None
    key = (path, os.path.getmtime(path))
    if key not in _CACHE:
        from jax.profiler import ProfileData
        _CACHE.clear()
        _CACHE[key] = window(ProfileData.from_file(path))
    w = _CACHE[key]
    return w if w.spans and w.rounds else None


def ms_per_round(run, name: str) -> Optional[float]:
    """Mean milliseconds per round spent in spans named ``name``."""
    w = load(run)
    if w is None:
        return None
    st = w.stats().get(name)
    return None if st is None else 1e3 * st.total_s / w.n_rounds
