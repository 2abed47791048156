"""Program spans of a monitor round, read back from a real profiler trace
(CPU, tiny sizes): the span tree and its parents, the counts in the
spans' metadata, no span inside a per-host loop, ``stage_seconds`` keys,
and the self-time arithmetic of the benchmark's span reader
(``bench/metrics/_spans.py``)."""
import types
from collections import Counter

import jax
import numpy as np
import pytest
from jax.profiler import ProfileData

from bench import trace_reduce
from bench.metrics import _spans
from repro.kernels.sweep import ops as sweep_ops
from repro.monitor import (
    FleetAggregator, FleetMonitor, ShardedFleetMonitor, ShardPlan,
)
from repro.sim.scenario import make_trial
from repro.telemetry.agent import TelemetryAgent
from repro.telemetry.collectors import SimCollector

#: span -> the spans it may sit directly under (None: outermost program
#: span), as docs/OPERATIONS.md "Tracing a round" draws the tree
PARENTS = {
    "aggregator.diagnose": {None},
    "aggregator.assemble": {"aggregator.diagnose"},
    "assemble.probe": {"aggregator.assemble"},
    "assemble.copy": {"aggregator.assemble"},
    "monitor.round": {None, "aggregator.diagnose"},
    "monitor.validity": {"monitor.round"},
    "shard.visit": {"monitor.round"},
    "shard.provider": {"shard.visit"},
    "shard.finish": {"monitor.round"},
    "monitor.detect": {"monitor.round", "shard.visit", "shard.finish"},
    "detect.quarantine": {"monitor.detect"},
    "detect.moments": {"monitor.detect"},
    "detect.stage": {"monitor.detect"},
    "detect.sweep": {"monitor.detect"},
    "sweep.put": {"detect.sweep"},
    "sweep.dispatch": {"detect.sweep"},
    "sweep.pull": {"detect.sweep"},
    "detect.redecide": {"monitor.detect"},
    "monitor.gather": {"shard.finish", "monitor.finish"},
    "shard.reduce": {"monitor.round"},
    "monitor.finish": {"monitor.round"},
    "finish.lifecycle": {"monitor.finish"},
    "rca.orient": {"monitor.finish"},
    "rca.kernel": {"monitor.finish"},
    "rca.rank": {"monitor.finish"},
    "rca.assemble": {"monitor.finish"},
}
#: the live path's windows hold no invalid cell, so the aggregator passes
#: no validity mask and the monitor has none to scan
LIVE = set(PARENTS) - {"shard.visit", "shard.provider", "shard.finish",
                       "shard.reduce", "monitor.validity"}
SHARDED = set(PARENTS) - {"aggregator.diagnose", "aggregator.assemble",
                          "assemble.probe", "assemble.copy",
                          "monitor.validity"}
STAGES = {"detect", "gather", "kernel", "rank", "assemble"}

T, STEP = 3400, 50          # 34 s staged window, 0.5 s cadence at 100 Hz


def _trials():
    """16 distinct ranks: every fourth carries a NIC fault from 40 s."""
    return [make_trial(900 + u, "nic", intensity=2.0 if u % 4 == 0 else 0.0,
                       t_on=40.0, confuser_prob=0.0) for u in range(16)]


@pytest.fixture(scope="module")
def trials():
    return _trials()


@pytest.fixture
def forced_redecide(monkeypatch):
    """Widen the sweep's guard band so that rounds re-decide rows through
    the f64 oracle; records the marginal rows each sweep returned."""
    real, marginal = sweep_ops.sweep_launch, []

    def spy(*a, **k):
        pending = real(*a, **dict(k, eps=0.5))
        collect = pending.collect

        def recorded(*ca, **ck):
            out = collect(*ca, **ck)
            marginal.append(int(out[3].sum()))
            return out
        return types.SimpleNamespace(collect=recorded)
    monkeypatch.setattr(sweep_ops, "sweep_launch", spy)
    return marginal


def _traced(tmp_path, rounds, n):
    """Run ``rounds(k)`` for k < n, each inside a harness-style ``round``
    span, under the profiler; returns the trace's program-span window."""
    jax.profiler.start_trace(str(tmp_path))
    try:
        for k in range(n):
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                rounds(k)
    finally:
        jax.profiler.stop_trace()
    pd = ProfileData.from_file(trace_reduce.find_xplane(tmp_path))
    return _spans.window(pd)


def _live(trials, hosts):
    agents = [TelemetryAgent([SimCollector(t.channels, t.ts, t.data)],
                             rate_hz=100.0, history_s=40.0)
              for t in (trials[h % len(trials)] for h in range(hosts))]
    agg = FleetAggregator(agents, window_s=T / 100.0)
    agg.run_virtual(0.0, 44.0)
    mon = FleetMonitor(use_kernels=False)
    out = []

    def rounds(k):
        t = 44.0 + k * STEP / 100.0
        agg.run_virtual(t, t + STEP / 100.0)
        out.append(agg.diagnose(mon))
    rounds.agg, rounds.out = agg, out
    return rounds


def _sharded(trials, hosts):
    data = np.stack([trials[h % len(trials)].data for h in range(hosts)])
    channels = trials[0].channels
    mon = ShardedFleetMonitor(
        ShardPlan.for_fleet(hosts, hosts // 2, 1), use_kernels=False)
    out = []

    def rounds(k):
        o = 1000 + k * STEP
        ts = (np.arange(T) + o) / 100.0
        out.append(mon.diagnose_sharded(
            ts, lambda s: (data[s * hosts // 2:(s + 1) * hosts // 2, :,
                                o:o + T], None), channels))
    rounds.out = out
    return rounds


def _parents(w):
    """span index -> the innermost program span around it (same thread)."""
    par = {}
    for i, (_, a, b, th, _) in enumerate(w.spans):
        best = None
        for j, (_, a2, b2, th2, _) in enumerate(w.spans):
            if j != i and th2 == th and a2 <= a and b <= b2 and (
                    best is None or b2 - a2 < w.spans[best][2]
                    - w.spans[best][1]):
                best = j
        par[i] = None if best is None else w.spans[best][0]
    return par


def _per_round(w):
    """Program spans in each harness round, by name."""
    return [Counter(n for n, a, b, th, _ in w.spans if r0 <= a and b <= r1)
            for r0, r1, _ in w.rounds]


@pytest.mark.parametrize("path", ["live", "sharded"])
def test_span_tree_and_counts(tmp_path, trials, forced_redecide, path):
    rounds = (_live if path == "live" else _sharded)(trials, 16)
    staged0 = rounds.agg.stats.staged_bytes if path == "live" else 0
    w = _traced(tmp_path, rounds, 3)
    names = {s[0] for s in w.spans}
    assert names == (LIVE if path == "live" else SHARDED)
    for i, parent in _parents(w).items():
        assert parent in PARENTS[w.spans[i][0]], (w.spans[i][0], parent)
    st = w.stats()
    # the oracle re-decides exactly the rows the sweep flagged marginal
    assert st["detect.redecide"].meta["rows"] == sum(forced_redecide) > 0
    assert st["detect.sweep"].meta["rows"] == 16 * 3
    # one device: each pull finds only its own sweep in flight, on chip 0
    pull = st["sweep.pull"]
    assert pull.meta["in_flight"] == pull.n > 0
    assert pull.meta["chip"] == st["detect.sweep"].meta["chip"] == 0
    if path == "sharded":
        assert st["shard.finish"].n == st["shard.visit"].n == 2 * 3
    if path == "live":
        copied = st["assemble.copy"].meta
        assert copied["bytes"] == (rounds.agg.stats.staged_bytes - staged0)
        # round 0 restages every row and its mirror; rounds 1-2 read,
        # validate and mirror only the STEP new ticks of each row
        tick = len(rounds.agg.channels) * (4 + 1) + 8
        assert copied["full_restages"] == 16 and copied["delta_reads"] == 32
        assert copied["bytes"] == 16 * 2 * T * tick + 32 * 2 * STEP * tick
        want = STAGES
    else:
        want = STAGES | {"reduce"}
    for fd in rounds.out:
        assert fd.flagged_hosts and set(fd.stage_seconds) == want


def test_stage_keys_of_a_quiet_round(trials):
    quiet = [t for u, t in enumerate(trials) if u % 4]
    fd = _live(quiet, 8)
    fd(0)
    fs = _sharded(quiet, 8)
    fs(0)
    assert not fd.out[0].flagged_hosts and not fs.out[0].flagged_hosts
    assert set(fd.out[0].stage_seconds) == {"detect"}
    assert set(fs.out[0].stage_seconds) == {"detect", "reduce"}


@pytest.mark.parametrize("path", ["live", "sharded"])
def test_spans_per_round_do_not_grow_with_hosts(tmp_path, trials, path):
    make = _live if path == "live" else _sharded
    counts = []
    for hosts in (64, 256):
        w = _traced(tmp_path / str(hosts), make(trials, hosts), 3)
        counts.append(_per_round(w))
    assert counts[0] == counts[1]
    # live: one detect around both halves; sharded: a launch and a
    # finish half for each of the two shards
    assert all(c["monitor.detect"] == (1 if path == "live" else 4)
               for c in counts[0])


def test_self_time_and_untraced_arithmetic():
    """Self time subtracts direct children only, on the same thread;
    untraced time is round time outside every non-excluded span."""
    w = _spans.Window(
        rounds=[(0.0, 100.0, 1), (200.0, 300.0, 1)],
        spans=[("monitor.round", 5.0, 95.0, 1, {"hosts": 4.0}),
               ("monitor.detect", 10.0, 50.0, 1, {}),
               ("detect.sweep", 20.0, 30.0, 1, {"rows": 4.0}),
               ("detect.stage", 30.0, 35.0, 1, {}),
               ("monitor.finish", 60.0, 90.0, 1, {}),
               ("monitor.round", 205.0, 295.0, 1, {"hosts": 4.0}),
               ("monitor.detect", 210.0, 260.0, 1, {}),
               ("detect.sweep", 212.0, 240.0, 2, {"rows": 2.0})])
    st = w.stats()
    assert st["monitor.round"].n == 2
    assert st["monitor.round"].total_s == pytest.approx(180e-9)
    assert st["monitor.round"].self_s == pytest.approx((90 - 70 + 90 - 50)
                                                       * 1e-9)
    assert st["monitor.detect"].self_s == pytest.approx((40 - 15 + 50)
                                                        * 1e-9)
    assert st["detect.sweep"].meta == {"rows": 6.0}
    assert st["monitor.round"].meta == {"hosts": 8.0}
    # round 1: 100 - 40 (detect) - 30 (finish); round 2: 100 - 50 (detect)
    assert w.untraced_s(["monitor.round"]) == pytest.approx(80e-9)
    assert w.untraced_s([]) == pytest.approx(20e-9)


def test_self_time_on_a_recorded_trace(tmp_path, trials):
    """On a recorded round, a span's self time is its total minus its
    direct children's totals, and the round's spans cover all but the
    untraced rest."""
    w = _traced(tmp_path, _live(trials, 8), 2)
    st = w.stats()
    par = _parents(w)
    kids = Counter()
    for i, (name, a, b, _, _) in enumerate(w.spans):
        if par[i] is not None:
            kids[par[i]] += (b - a) * 1e-9
    for name, s in st.items():
        assert s.self_s == pytest.approx(s.total_s - kids[name], abs=1e-9)
        assert 0.0 <= s.self_s <= s.total_s + 1e-12
    outer = ("aggregator.diagnose", "monitor.round")
    round_s = sum(r1 - r0 for r0, r1, _ in w.rounds) * 1e-9
    covered = sum((b - a) * 1e-9 for i, (name, a, b, _, _)
                  in enumerate(w.spans)
                  if name not in outer and par[i] in (None,) + outer)
    assert w.untraced_s(outer) == pytest.approx(round_s - covered, abs=1e-6)
