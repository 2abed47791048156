"""Entry ``sharded``: ``ShardedFleetMonitor.diagnose_sharded`` fed by a
provider that hands out zero-copy views of per-shard timelines.

Shard ``s`` replays distinct timeline ``s mod distinct_timelines``; round
``k`` diagnoses the window ending at absolute tick ``T + k * step`` on the
100 Hz grid, so every round streams ``step`` new ticks through the
incremental-moment path.  Building the round's views and timestamps is
generator work done before the round's clock starts; the provider the
monitor calls inside the round only indexes a list.
"""
from __future__ import annotations

import numpy as np

from bench.entries.common import EntryBase
from bench.traffic import timelines


class Entry(EntryBase):
    def setup(self) -> None:
        from repro.monitor.shard import ShardedFleetMonitor, ShardPlan
        cfg = self.cfg
        self.sh = int(cfg["shard_hosts"])
        self.n_tl = int(cfg["distinct_timelines"])
        self.plan = ShardPlan.for_fleet(self.hosts, shard_hosts=self.sh,
                                        rack_shards=int(cfg["rack_shards"]))
        fe = int(self.traffic.get("fault_every") or 0)
        faulted = np.array([fe > 0 and (j * self.sh + r) % fe == 0
                            for j in range(self.n_tl)
                            for r in range(self.sh)])
        self.stream = timelines.build(self.traffic, faulted, self.seed,
                                      self.rate, self.T)
        self.channels = list(self.stream.channels)
        self.monitor = ShardedFleetMonitor(
            self.plan, config=self.engine_config(),
            rca_top_k=cfg["rca_top_k"], **self.monitor_kwargs())
        self.shard_rows = [b - a for a, b in self.plan.bounds]

    def uses(self, d: int) -> int:
        """How many shards replay distinct row ``d``."""
        j, r = divmod(d, self.sh)
        return sum(1 for s, (a, b) in enumerate(self.plan.bounds)
                   if s % self.n_tl == j and r < b - a)

    def prepare(self, k: int) -> None:
        e = self.tick_end(k)
        self._ts = (np.arange(self.T) + (e - self.T)) / self.rate
        o = self.stream.offset(e)
        views = [self.stream.rows[j * self.sh:(j + 1) * self.sh, :,
                                  o:o + self.T] for j in range(self.n_tl)]
        bounds, n_tl, span = self.plan.bounds, self.n_tl, self.span

        def provider(s: int):
            with span("provider"):
                a, b = bounds[s]
                return views[s % n_tl][:b - a], None
        self._provider = provider

    def round(self, k: int):
        return self.monitor.diagnose_sharded(self._ts, self._provider,
                                             self.channels)

    def _rows(self, hosts):
        """Distinct-row index of each absolute host id."""
        hosts = np.asarray(hosts, np.int64)
        s = hosts // self.sh
        return (s % self.n_tl) * self.sh + (hosts - s * self.sh)

    def ref_tail(self, k: int, hosts=None) -> np.ndarray:
        o = self.stream.offset(self.tick_end(k))
        li = self.channels.index("coll_allreduce_ms")
        d = self._rows(np.arange(self.hosts) if hosts is None else hosts)
        lo = o + self.T - self.wn - self.bn
        return self.stream.rows[d, li, lo:o + self.T]

    def ref_block(self, k: int, hosts, g) -> np.ndarray:
        o = self.stream.offset(self.tick_end(k))
        d = self._rows(hosts)
        return self.stream.rows[d, :, o + self.T - g.nb - g.rn:o + self.T]
