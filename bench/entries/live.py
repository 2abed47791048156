"""Entry ``live``: ``FleetAggregator.diagnose`` over one telemetry agent
per rank, on the virtual clock, into a single-slab ``FleetMonitor``.

Each agent samples a replay collector over its rank's periodic timeline
through the agent's columnar ``run_virtual`` (program code: agent, ring
buffer).  Before round ``k`` the generator pushes the round's ``step``
new ticks into every agent's ring, off the round's clock; the round is
the aggregator's staging plus the monitor's diagnosis.  The harness times
the aggregator's ``assemble`` with a wrapper on the instance.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, Sequence

import numpy as np

from bench.entries.common import EntryBase
from bench.traffic import timelines


def _collector_class():
    from repro.telemetry.collectors import Collector
    from repro.telemetry.schema import METRIC_REGISTRY

    class ReplayCollector(Collector):
        """Replays one rank's periodic (C, period + window) timeline:
        the value at virtual time ``t`` is column ``round(t * rate) mod
        period``.  Values are gauges/rates, so every channel is declared
        a non-counter."""

        def __init__(self, names: Sequence[str], row: np.ndarray,
                     period: int, rate: float):
            self.names = list(names)
            self.metrics = [dataclasses.replace(METRIC_REGISTRY[c],
                                                monotonic_counter=False)
                            for c in self.names]
            self._row, self._period, self._rate = row, int(period), rate

        def sample(self, now: float) -> Dict[str, float]:
            i = int(round(now * self._rate)) % self._period
            return {c: float(self._row[j, i])
                    for j, c in enumerate(self.names)}

        def sample_block(self, grid: np.ndarray) -> Dict[str, np.ndarray]:
            idx = np.rint(np.asarray(grid, np.float64) * self._rate
                          ).astype(np.int64) % self._period
            blk = self._row[:, idx]
            return {c: blk[j] for j, c in enumerate(self.names)}

    return ReplayCollector


class Entry(EntryBase):
    def setup(self) -> None:
        from repro.monitor.aggregator import FleetAggregator
        from repro.monitor.fleet import FleetMonitor
        from repro.telemetry.agent import TelemetryAgent
        fe = int(self.traffic.get("fault_every") or 0)
        faulted = np.array([fe > 0 and h % fe == 0
                            for h in range(self.hosts)])
        self.stream = st = timelines.build(self.traffic, faulted, self.seed,
                                           self.rate, self.T)
        Replay = _collector_class()
        hist = float(self.cfg["agent_history_s"])
        agents = [TelemetryAgent([Replay(st.channels, st.rows[h], st.period,
                                         self.rate)],
                                 rate_hz=self.rate, history_s=hist)
                  for h in range(self.hosts)]
        self.agg = FleetAggregator(agents, window_s=self.T / self.rate)
        self.agg.run_virtual(0.0, self.T / self.rate)
        self.channels = list(self.agg.channels)
        self.perm = np.array([st.channels.index(c) for c in self.channels])
        self.monitor = FleetMonitor(config=self.engine_config(),
                                    rca_top_k=self.cfg["rca_top_k"],
                                    **self.monitor_kwargs())
        self.shard_rows = [self.hosts]
        self.assemble_s = []
        assemble, span = self.agg.assemble, self.span

        def timed_assemble():
            with span("assemble"):
                t0 = time.perf_counter()
                snap = assemble()
                self.assemble_s.append(time.perf_counter() - t0)
                return snap
        self.agg.assemble = timed_assemble

    def prepare(self, k: int) -> None:
        if k > 0:
            e = self.tick_end(k)
            with self.span("generator_push"):
                self.agg.run_virtual((e - self.step) / self.rate,
                                     e / self.rate)

    def round(self, k: int):
        return self.agg.diagnose(self.monitor)

    def round_extra(self) -> dict:
        return {"staging_s": self.assemble_s[-1]} if self.assemble_s else {}

    def counters(self) -> dict:
        return dataclasses.asdict(self.agg.stats)

    def ref_tail(self, k: int, hosts=None) -> np.ndarray:
        o = self.stream.offset(self.tick_end(k))
        li = self.stream.channels.index("coll_allreduce_ms")
        rows = self.stream.rows if hosts is None else \
            self.stream.rows[np.asarray(hosts, np.int64)]
        return rows[:, li, o + self.T - self.wn - self.bn:o + self.T]

    def ref_block(self, k: int, hosts, g) -> np.ndarray:
        o = self.stream.offset(self.tick_end(k))
        blk = self.stream.rows[np.asarray(hosts, np.int64), :,
                               o + self.T - g.nb - g.rn:o + self.T]
        return blk[:, self.perm]

    def staging_diff(self) -> int:
        """Cells of the last staged slab unequal to the window the agents
        were pushed (channels in the aggregator's order)."""
        snap = self.agg.last_snapshot
        o = self.stream.offset(self.tick_end(self.last_round))
        bad = 0
        for h in range(0, self.hosts, 128):
            want = self.stream.rows[h:h + 128][:, self.perm, o:o + self.T]
            bad += int((snap.slab[h:h + 128] != want).sum())
        return bad

    def release(self) -> None:
        self.monitor = None
        self.agg = None
