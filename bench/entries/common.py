"""What every entry shares: the configuration's sizes, the round
geometry on the absolute 100 Hz grid, and the set-up that warms the
fused-RCA batch sizes the traffic can produce."""
from __future__ import annotations

import contextlib
from typing import Callable, List

import numpy as np

from bench.configs import monitor_reference as ref


class EntryBase:
    """Drives one configuration's entry point.  Subclasses implement
    ``setup``, ``prepare(k)`` (generator work before round ``k``, off the
    round's clock), ``round(k)`` (the timed call), ``ref_tail(k, hosts)``
    and ``ref_block(k, hosts, g)`` (the window round ``k`` saw, for the
    reference)."""

    #: per round, the hosts each sweep dispatch covers (set by subclasses)
    shard_rows: List[int]

    def __init__(self, cfg: dict, traffic: dict, seed: int,
                 span: Callable = lambda name: contextlib.nullcontext()):
        self.cfg, self.traffic, self.seed = cfg, traffic, int(seed)
        self.span = span
        self.rate = float(cfg["rate_hz"])
        self.T = int(round(float(cfg["staged_window_s"]) * self.rate))
        self.step = int(round(float(cfg["round_cadence_s"]) * self.rate))
        self.hosts = int(cfg["hosts"])
        mon = cfg["monitor"]
        self.wn = min(int(mon["window_s"] * self.rate), self.T // 2)
        self.bn = min(int(mon["baseline_s"] * self.rate), self.T - self.wn)

    def engine_config(self):
        from repro.core.engine import EngineConfig
        m = self.cfg["monitor"]
        return EngineConfig(
            rate_hz=self.rate, window_s=m["window_s"],
            baseline_s=m["baseline_s"], threshold=m["threshold"],
            persistence=m["persistence"], pre_onset_s=m["pre_onset_s"],
            max_lag=m["max_lag"], alpha=m["alpha"],
            rca_extra_s=m["rca_extra_s"],
            max_hypotheses=m["max_hypotheses"])

    def monitor_kwargs(self) -> dict:
        return {"persistent_threshold":
                int(self.cfg["monitor"]["persistent_threshold"])}

    def tick_end(self, k: int) -> int:
        """Exclusive absolute tick that round ``k``'s window ends at."""
        return self.T + self.step * int(k)

    def geometry(self, channels) -> ref.Geometry:
        return ref.Geometry(self.cfg["monitor"], self.rate, self.T, channels)

    def uses(self, d: int) -> int:
        """How many hosts replay distinct row ``d``."""
        return 1

    def fused_batches(self) -> List[int]:
        """Every fused-RCA batch size the traffic produces: the flagged
        count at each phase of the stream's period, capped at
        ``rca_top_k``.  Only faulted rows can flag (quiet rows carry no
        latency disturbance), so only they are scanned."""
        st = self.stream
        fidx = np.flatnonzero(st.faulted)
        if not fidx.size:
            return []
        g = ref.Geometry(self.cfg["monitor"], self.rate, self.T, st.channels)
        w = np.array([self.uses(int(d)) for d in fidx])
        top = self.cfg["rca_top_k"]
        out = set()
        for o in range(0, st.period, self.step):
            lo = o + self.T - self.wn - self.bn
            f, _, _ = ref.detect(st.rows[fidx, g.li, lo:o + self.T], g)
            n = int(w[f].sum())
            if n:
                out.add(n if top is None else min(int(top), n))
        return sorted(out)

    def warm_fused(self, sizes: List[int]) -> None:
        """Compile the fused RCA dispatch at each batch size, with the
        shapes and dtypes the monitor hands it."""
        from repro.kernels.fused import ops as fused_ops
        g = self.geometry(self.stream.channels)
        M, K = len(g.ev), g.K
        for B in sizes:
            out = fused_ops.fused_rca_max(
                np.zeros((B, g.rn), np.float32),
                np.zeros((B, M, g.rn), np.float32),
                np.ones((B, M, g.nb), np.float32), max_lag=K,
                use_kernel=True)
            [np.asarray(x) for x in out]

    def counters(self) -> dict:
        return {}

    def round_extra(self) -> dict:
        return {}

    def staging_diff(self):
        return None

    def release(self) -> None:
        """Drop the program's state before the reference runs."""
        self.monitor = None
