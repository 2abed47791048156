"""Sharded fleet monitor: byte-exact parity with the single-slab path
(flagged order, scores, causes, quarantine, degraded/deferred fields),
shard edge cases (ragged shards, dead shards, late joiners), provider
re-visit semantics, traffic bounds, and shard-aware checkpointing."""
import json
import os
import subprocess
import sys
import textwrap

import numpy as np
import pytest

from benchmarks.fleetbench import _make_fleet
from repro.monitor import (
    FleetAggregator, FleetMonitor, Mitigation, ShardPlan,
    ShardedFleetMonitor, verdict_fingerprint,
)
from repro.monitor.checkpoint import MonitorSession

LAT = "coll_allreduce_ms"   # EngineConfig.latency_metric


def _plan(hosts=48):
    """Deliberately ragged: 20 + 20 + (hosts-40), two shards per rack."""
    return ShardPlan.from_bounds([(0, 20), (20, 40), (40, hosts)],
                                 rack_shards=2)


def _pair(hosts=48, **kw):
    """(single-slab monitor, sharded monitor) with identical knobs."""
    return (FleetMonitor(use_kernels=False, **kw),
            ShardedFleetMonitor(_plan(hosts), use_kernels=False, **kw))


def _state_no_plan(mon):
    d = dict(mon.state_dict())
    d.pop("shard_plan", None)
    return d


# --------------------------------------------------------------- ShardPlan

def test_plan_validates_contiguous_tiling_and_rack_partition():
    with pytest.raises(ValueError):
        ShardPlan(bounds=((0, 4), (5, 8)), racks=((0, 1),))    # gap
    with pytest.raises(ValueError):
        ShardPlan(bounds=((0, 4), (3, 8)), racks=((0, 1),))    # overlap
    with pytest.raises(ValueError):
        ShardPlan(bounds=((1, 4),), racks=((0,),))             # not from 0
    with pytest.raises(ValueError):
        ShardPlan(bounds=((0, 4), (4, 4)), racks=((0, 1),))    # empty shard
    with pytest.raises(ValueError):
        ShardPlan(bounds=((0, 4), (4, 8)), racks=((0,),))      # shard 1 lost
    with pytest.raises(ValueError):
        ShardPlan(bounds=((0, 4),), racks=((0, 0),))           # duplicate


def test_plan_helpers_and_dict_round_trip():
    p = ShardPlan.for_fleet(10, shard_hosts=4, rack_shards=2)
    assert p.bounds == ((0, 4), (4, 8), (8, 10))   # ragged tail shard
    assert p.racks == ((0, 1), (2,))
    assert (p.hosts, p.n_shards, p.n_racks) == (10, 3, 2)
    assert [p.shard_of(h) for h in (0, 3, 4, 9)] == [0, 0, 1, 2]
    with pytest.raises(ValueError):
        p.shard_of(10)
    assert ShardPlan.from_dict(p.to_dict()) == p


def test_aggregator_shard_plan_covers_fleet():
    agg = FleetAggregator.__new__(FleetAggregator)
    agg.agents = list(range(7))
    p = agg.shard_plan(shard_hosts=3, rack_shards=2)
    assert p.hosts == 7 and p.bounds == ((0, 3), (3, 6), (6, 7))


# ----------------------------------------------------------------- parity

def test_clean_round_parity_ragged_shards():
    """One straggler on a ragged 3-shard plan: identical fingerprint,
    identical monitor state after the round, bounded traffic."""
    ts, data, channels = _make_fleet(48, bad_host=5, seed=0)
    mono, shard = _pair()
    a = mono.diagnose_fleet(ts, data, channels)
    b = shard.diagnose_fleet(ts, data, channels)
    assert a.flagged_hosts == [5]
    assert verdict_fingerprint(a) == verdict_fingerprint(b)
    assert _state_no_plan(shard) == mono.state_dict()
    tr = shard.last_traffic
    assert tr is not None and tr.raw_bytes > 0
    assert tr.total_bytes < tr.raw_bytes


def test_multi_round_strike_escalation_parity():
    """Strike history lives on absolute host ids: escalation to
    EXCLUDE_AND_RESCALE happens on the same round on both paths."""
    ts, data, channels = _make_fleet(48, bad_host=41, seed=7)  # last shard
    mono, shard = _pair(persistent_threshold=2)
    for rnd in range(2):
        a = mono.diagnose_fleet(ts, data, channels)
        b = shard.diagnose_fleet(ts, data, channels)
        assert verdict_fingerprint(a) == verdict_fingerprint(b), rnd
    assert b.mitigation == Mitigation.EXCLUDE_AND_RESCALE
    assert _state_no_plan(shard) == mono.state_dict()


def test_incident_storm_topk_parity_and_deferral():
    """Storm (every 6th host injected) with a fleet-level RCA cap: the
    fleet fetches exactly the evidence its selection needs, and the
    overflow lands in deferred_hosts on both paths."""
    ts, data, channels = _make_fleet(48, bad_host=5, seed=11, bad_every=6)
    mono, shard = _pair(rca_top_k=3)
    a = mono.diagnose_fleet(ts, data, channels)
    b = shard.diagnose_fleet(ts, data, channels)
    assert len(a.flagged_hosts) > 3
    assert a.deferred_hosts and len(a.diagnoses) <= 3
    assert verdict_fingerprint(a) == verdict_fingerprint(b)
    # the cap also bounds evidence traffic: one block per RCA'd host
    assert shard.last_traffic.n_evidence == len(b.diagnoses)


def _rca_spy(mon):
    """Record the evidence slab and hosts of each Layer-3 dispatch."""
    seen = []
    real = mon._rca_from_evidence

    def spy(ts, X, geom, flagged, *a, **k):
        seen.append((X.copy(), np.array(flagged), geom))
        return real(ts, X, geom, flagged, *a, **k)
    mon._rca_from_evidence = spy
    return seen


def test_storm_topk_fetch_exact_over_streaming_rounds():
    """Storm with ``rca_top_k`` below the flagged count, shard 1 fed
    shard 0's rows (equal scores, ordered by host id across shards):
    over streaming rounds the sharded round matches the single slab bit
    for bit, fetches once per owning shard in ascending order after the
    visits, one block per RCA'd host, each equal to the whole-slab
    gather's row."""
    ts, data, channels = _make_fleet(48, bad_host=5, seed=11, bad_every=6)
    data = data.copy()
    data[20:40] = data[0:20]
    T = data.shape[2]
    mono, shard = _pair(rca_top_k=5)
    seen = _rca_spy(shard)
    spread, tie_cut = False, False
    for tk in (T - 240, T - 160, T - 80, T):
        d, calls = data[:, :, :tk], []

        def provider(s):
            calls.append(s)
            a, b = shard.plan.bounds[s]
            return d[a:b], None

        a = mono.diagnose_fleet(ts[:tk], d, channels)
        b = shard.diagnose_sharded(ts[:tk], provider, channels)
        assert verdict_fingerprint(a) == verdict_fingerprint(b), tk
        assert len(b.flagged_hosts) > 5 and len(b.diagnoses) == 5
        assert shard.last_traffic.n_evidence == len(b.diagnoses)
        owners = sorted({shard.plan.shard_of(h) for h in b.diagnoses})
        assert calls == [0, 1, 2] + owners, tk
        spread |= len(owners) >= 3
        rca = {float(b.per_host_scores[h]) for h in b.diagnoses}
        tie_cut |= any(float(b.per_host_scores[h]) in rca
                       for h in b.deferred_hosts)
        X, hosts, geom = seen[-1]
        assert sorted(hosts.tolist()) == sorted(b.diagnoses)
        whole = shard._gather_evidence(d, hosts, geom)
        assert X.dtype == np.float32 and X.tobytes() == whole.tobytes()
    assert spread and tie_cut
    assert _state_no_plan(shard) == mono.state_dict()


def test_masked_round_fetch_uses_the_revisit_mask():
    """Corruption surfaces on the last shard only, while shard 0 masks
    evidence cells of its straggler: the fetch reads shard 0's mask
    (evidence NaN'd and forward-filled) and the round equals the
    single-slab masked round, blocks included."""
    ts, data, channels = _make_fleet(48, bad_host=5, seed=31)
    li = channels.index(LAT)
    valid = np.ones(data.shape, bool)
    valid[44, li, -200:] = False           # shard 2 only, below quarantine
    valid[5, :, -300:-100] = False         # shard 0: the straggler's tail
    valid[5, li] = True                    # ... latency stays valid
    mono, shard = _pair()
    seen_m, seen_s = _rca_spy(mono), _rca_spy(shard)
    calls = []

    def provider(s):
        calls.append(s)
        a, b = shard.plan.bounds[s]
        return data[a:b], valid[a:b]

    fd = shard.diagnose_sharded(ts, provider, channels)
    ref = mono.diagnose_fleet(ts, data, channels, valid=valid)
    assert verdict_fingerprint(fd) == verdict_fingerprint(ref)
    assert sorted(fd.diagnoses) == [5]
    assert calls == [0, 1, 2, 0, 1, 0]
    (Xs, hs, geom), (Xm, hm, _) = seen_s[-1], seen_m[-1]
    assert hs.tolist() == hm.tolist() == [5]
    assert Xs.tobytes() == Xm.tobytes()
    unmasked = shard._gather_evidence(data, hs, geom)
    assert Xs.tobytes() != unmasked.tobytes()


def test_quarantine_entry_parity_under_corruption():
    """A host whose latency channel is mostly invalid enters quarantine
    after ``enter_rounds`` rounds — same round, same fingerprint, on both
    paths (the corrupt cell routes every shard through the f64 oracle)."""
    ts, data, channels = _make_fleet(48, bad_host=5, seed=3)
    li = channels.index(LAT)
    valid = np.ones(data.shape, bool)
    valid[44, li, -1200:] = False          # last shard, ~39% of the tail
    mono, shard = _pair()
    for rnd in range(2):
        a = mono.diagnose_fleet(ts, data, channels, valid=valid)
        b = shard.diagnose_fleet(ts, data, channels, valid=valid)
        assert verdict_fingerprint(a) == verdict_fingerprint(b), rnd
    assert a.quarantined == [44]
    assert a.mitigations[44] == Mitigation.RESTART_TELEMETRY
    assert 44 not in a.flagged_hosts


def test_whole_dead_shard_quarantined_parity():
    """Every host of shard 2 reports all-invalid telemetry: the whole
    shard quarantines, nothing in it is ever named straggler, and the
    live shards' verdicts match the single-slab round bit for bit."""
    ts, data, channels = _make_fleet(48, bad_host=5, seed=13)
    valid = np.ones(data.shape, bool)
    valid[40:48] = False
    mono, shard = _pair()
    for _ in range(2):
        a = mono.diagnose_fleet(ts, data, channels, valid=valid)
        b = shard.diagnose_fleet(ts, data, channels, valid=valid)
        assert verdict_fingerprint(a) == verdict_fingerprint(b)
    assert a.quarantined == list(range(40, 48))
    assert a.straggler_host == 5


def test_degraded_mode_parity():
    """Deadline-degraded rounds (budget always blown): shed/deferral and
    the strike-priority selection agree across paths every round.  The
    storm widens AFTER degradation engages, so the new stragglers have no
    strike history and must be deferred — while the original one, already
    carrying a strike, still gets full RCA."""
    ts, calm, channels = _make_fleet(48, bad_host=5, seed=17)
    _, storm, _ = _make_fleet(48, bad_host=5, seed=17, bad_every=6)
    mono, shard = _pair(budget_s=1e-6, shed_after=1)
    rounds = []
    for rnd, data in enumerate((calm, storm, storm)):
        a = mono.diagnose_fleet(ts, data, channels)
        b = shard.diagnose_fleet(ts, data, channels)
        assert verdict_fingerprint(a) == verdict_fingerprint(b), rnd
        rounds.append(a)
    first_storm = rounds[1]     # degraded, and the new stragglers are fresh
    assert first_storm.degraded and first_storm.deferred_hosts
    assert 5 in first_storm.diagnoses
    assert 5 not in first_storm.deferred_hosts
    # by the next round the deferred hosts carry strikes and get full RCA
    assert rounds[2].deferred_hosts == []
    assert set(first_storm.deferred_hosts) <= set(rounds[2].diagnoses)
    assert shard.shed_rounds == mono.shed_rounds
    assert shard.deferred_rca == mono.deferred_rca


def test_short_window_quiet_parity():
    ts, data, channels = _make_fleet(48, bad_host=5, seed=19)
    mono, shard = _pair()
    a = mono.diagnose_fleet(ts[:40], data[:, :, :40], channels)
    b = shard.diagnose_fleet(ts[:40], data[:, :, :40], channels)
    assert a.flagged_hosts == [] and "short_baseline_skip" in a.stage_seconds
    assert verdict_fingerprint(a) == verdict_fingerprint(b)
    assert shard.last_traffic.total_bytes == 0


def test_host_count_mismatch_rejected():
    ts, data, channels = _make_fleet(48, bad_host=5, seed=23)
    shard = ShardedFleetMonitor(_plan(), use_kernels=False)
    with pytest.raises(ValueError, match="plan covers"):
        shard.diagnose_fleet(ts, data[:40], channels)


# ----------------------------------------------------------- provider API

def test_provider_clean_round_visits_each_shard_once():
    """A clean round visits shards 0-2 once each, then fetches the RCA
    set's evidence: bad host 5 lives on shard 0, one more call."""
    ts, data, channels = _make_fleet(48, bad_host=5, seed=29)
    mono, shard = _pair()
    plan, calls = shard.plan, []

    def provider(s):
        calls.append(s)
        a, b = plan.bounds[s]
        return data[a:b], None

    fd = shard.diagnose_sharded(ts, provider, channels)
    assert sorted(fd.diagnoses) == [5]
    assert calls == [0, 1, 2, 0]
    fp = verdict_fingerprint(fd)
    assert fp == verdict_fingerprint(mono.diagnose_fleet(ts, data, channels))


def test_provider_revisits_fast_path_shards_on_late_corruption():
    """Corruption first surfaces on the LAST shard: the earlier shards
    already took the fast path, so the round must re-visit exactly them
    through the masked oracle — and still match the single-slab masked
    round, which takes the oracle for every host."""
    ts, data, channels = _make_fleet(48, bad_host=5, seed=31)
    li = channels.index(LAT)
    valid = np.ones(data.shape, bool)
    valid[44, li, -200:] = False           # shard 2 only, below quarantine
    mono, shard = _pair()
    plan, calls = shard.plan, []

    def provider(s):
        calls.append(s)
        a, b = plan.bounds[s]
        return data[a:b], valid[a:b]

    fd = shard.diagnose_sharded(ts, provider, channels)
    # shard 2 already ran the oracle; then the fetch of host 5's evidence
    assert sorted(fd.diagnoses) == [5]
    assert calls == [0, 1, 2, 0, 1, 0]
    ref = mono.diagnose_fleet(ts, data, channels, valid=valid)
    assert verdict_fingerprint(fd) == verdict_fingerprint(ref)
    assert _state_no_plan(shard) == mono.state_dict()


def test_provider_short_window_refuses_before_any_state_advances():
    ts, data, channels = _make_fleet(48, bad_host=5, seed=37)
    shard = ShardedFleetMonitor(_plan(), use_kernels=False)
    calls = []

    def provider(s):
        calls.append(s)
        a, b = shard.plan.bounds[s]
        return data[a:b, :, :40], None

    fd = shard.diagnose_sharded(ts[:40], provider, channels)
    assert calls == [0]                    # refused on the first shard
    assert fd.flagged_hosts == []
    assert "short_baseline_skip" in fd.stage_seconds


def test_provider_shape_mismatch_rejected():
    ts, data, channels = _make_fleet(48, bad_host=5, seed=41)
    shard = ShardedFleetMonitor(_plan(), use_kernels=False)
    with pytest.raises(ValueError, match="bounds"):
        shard.diagnose_sharded(ts, lambda s: (data[:4], None), channels)


# ------------------------------------------------------------- aggregator

def _agents(n_hosts, bad_host, seed=840):
    from repro.sim.scenario import make_trial
    from repro.telemetry.agent import TelemetryAgent
    from repro.telemetry.collectors import SimCollector
    agents = []
    for h in range(n_hosts):
        t = make_trial(seed + h, "nic",
                       intensity=(2.0 if h == bad_host else 0.0),
                       t_on=40.0, confuser_prob=0.0)
        agents.append(TelemetryAgent(
            [SimCollector(t.channels, t.ts, t.data)],
            rate_hz=100.0, history_s=60.0))
    return agents


def test_aggregator_late_joiner_on_nonzero_shard_parity():
    """A host that restarted 3 s ago sits on the LAST shard: the
    aggregator masks it quiet, the sharded round neither flags it nor
    lets its backfilled head poison its shard, and the verdict matches
    the single-slab monitor on the same staged slab."""
    agents = _agents(6, bad_host=1)
    agg = FleetAggregator(agents, window_s=30.0)
    for a in agents[:5]:
        a.run_virtual(0.0, 46.0)
    agents[5].run_virtual(43.0, 46.0)      # young host on shard 2
    plan = agg.shard_plan(shard_hosts=2, rack_shards=2)
    assert plan.shard_of(5) == 2
    shard = ShardedFleetMonitor(plan, use_kernels=False)
    fd = agg.diagnose(shard, min_valid_s=10.0)
    assert fd is not None
    assert fd.straggler_host == 1
    assert 5 not in fd.flagged_hosts
    assert agg.last_snapshot.masked == [5]
    ref = agg.diagnose(FleetMonitor(use_kernels=False), min_valid_s=10.0)
    assert verdict_fingerprint(fd) == verdict_fingerprint(ref)


# ------------------------------------------------------------- checkpoint

def test_state_dict_round_trip_partitioned_state():
    """Strike + quarantine maps built across shard boundaries survive a
    state_dict round trip into a fresh sharded monitor: the next round is
    fingerprint-identical to the monitor that lived through."""
    ts, data, channels = _make_fleet(48, bad_host=41, seed=43)
    li = channels.index(LAT)
    valid = np.ones(data.shape, bool)
    valid[3, li, -1200:] = False           # quarantine path on shard 0
    mono, shard = _pair(persistent_threshold=2)
    for _ in range(2):
        mono.diagnose_fleet(ts, data, channels, valid=valid)
        shard.diagnose_fleet(ts, data, channels, valid=valid)
    fresh = ShardedFleetMonitor(_plan(), use_kernels=False,
                                persistent_threshold=2)
    fresh.load_state_dict(shard.state_dict())
    a = shard.diagnose_fleet(ts, data, channels, valid=valid)
    b = fresh.diagnose_fleet(ts, data, channels, valid=valid)
    assert a.quarantined == [3]
    assert verdict_fingerprint(a) == verdict_fingerprint(b)
    # and the single-slab monitor adopts the same payload (absolute host
    # ids make the state shard-agnostic; the plan key is ignored)
    single = FleetMonitor(use_kernels=False, persistent_threshold=2)
    single.load_state_dict(shard.state_dict())
    c = single.diagnose_fleet(ts, data, channels, valid=valid)
    assert verdict_fingerprint(a) == verdict_fingerprint(c)


def test_plan_mismatch_rejected():
    shard = ShardedFleetMonitor(_plan(), use_kernels=False)
    other = ShardedFleetMonitor(
        ShardPlan.from_bounds([(0, 24), (24, 48)], rack_shards=2),
        use_kernels=False)
    with pytest.raises(ValueError, match="shard plan"):
        other.load_state_dict(shard.state_dict())


def test_session_restore_plan_mismatch_is_counted_cold_start(tmp_path):
    """Resharding between runs must not misattribute strike/quarantine
    state across new boundaries: the session rejects the checkpoint
    loudly and cold-starts."""
    ts, data, channels = _make_fleet(48, bad_host=5, seed=47)
    path = str(tmp_path / "mon.ckpt")
    sess = MonitorSession(ShardedFleetMonitor(_plan(), use_kernels=False),
                          channels)
    sess.tick(ts, data)
    sess.save(path)
    # same plan -> warm restore
    warm = MonitorSession(ShardedFleetMonitor(_plan(), use_kernels=False),
                          channels)
    assert warm.restore(path) is True
    # different plan -> counted cold start, state untouched
    cold = MonitorSession(
        ShardedFleetMonitor(ShardPlan.from_bounds([(0, 48)]),
                            use_kernels=False), channels)
    with pytest.warns(RuntimeWarning, match="cold start"):
        assert cold.restore(path) is False
    assert cold.stats.checkpoints_rejected == 1
    assert cold.monitor._strikes == {}


# ----------------------------------------- incremental streaming moments

def test_incremental_sharded_parity_growing_rounds():
    """Per-shard incremental state keyed by absolute host id (base=
    offsets): fingerprints match the single-slab incremental monitor
    round for round across appended-delta rounds, a masked chaos round
    (forced invalidation + oracle), and the rebuild round after it."""
    ts, data, channels = _make_fleet(48, bad_host=5, seed=3)
    li = list(channels).index(LAT)
    T = data.shape[2]
    mono, shard = _pair()
    assert shard._inc is not None     # incremental on by default
    for rnd, tk in enumerate((T - 240, T - 160, T - 80, T)):
        vmask = None
        if rnd == 2:
            vmask = np.ones((48, len(channels), tk), bool)
            vmask[27, li, -100:] = False     # corruption in shard 1
        a = mono.diagnose_fleet(ts[:tk], data[:, :, :tk], channels,
                                valid=vmask)
        b = shard.diagnose_fleet(ts[:tk], data[:, :, :tk], channels,
                                 valid=vmask)
        assert verdict_fingerprint(a) == verdict_fingerprint(b), rnd
    assert _state_no_plan(shard) == mono.state_dict()
    st = shard.incremental_stats()
    assert st["forced_invalidations"] >= 48      # chaos dropped all rows
    assert st["parity"] == 1.0


def test_incremental_sharded_provider_revisit_invalidates():
    """Provider path with late-surfacing corruption: fast-path shards
    are re-visited through the oracle, which must invalidate (not
    advance) their incremental rows — the next clean round rebuilds."""
    ts, data, channels = _make_fleet(48, bad_host=5, seed=3)
    li = list(channels).index(LAT)
    _, shard = _pair()

    def provider_clean(s):
        a, b = shard.plan.bounds[s]
        return data[a:b], None

    def provider_corrupt(s):
        a, b = shard.plan.bounds[s]
        v = np.ones_like(data[a:b], bool)
        if s == 2:                    # last shard reports corruption
            v[1, li, -100:] = False
        return data[a:b], v

    shard.diagnose_sharded(ts, provider_clean, channels)
    assert shard._inc.rounds == shard.plan.n_shards
    shard.diagnose_sharded(ts, provider_corrupt, channels)
    # shards that ran the fast path before the corruption surfaced may
    # have advanced, but the oracle re-visit must wipe every row — no
    # stale state can survive a round whose verdicts came from the oracle
    assert (shard._inc._bid[:48] == -1).all()
    assert shard._inc.forced_invalidations >= 48
    after = shard._inc.rounds
    shard.diagnose_sharded(ts, provider_clean, channels)
    assert shard._inc.rounds == after + shard.plan.n_shards
    assert shard._inc.parity == 1.0


# ------------------------------------------------ placement over chips

_PLACEMENT = textwrap.dedent("""
    import json, os, sys, types
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = {paths!r}
    import jax
    import numpy as np
    from benchmarks.fleetbench import _make_fleet
    from repro.kernels.sweep import ops as sweep_ops
    from repro.monitor import (
        FleetMonitor, ShardPlan, ShardedFleetMonitor, verdict_fingerprint)

    HOSTS, SHARD, W, STEP = 256, 32, 3000, 50
    ts, data, channels = _make_fleet(HOSTS, bad_host=5, seed=11, bad_every=6)
    li = channels.index("coll_allreduce_ms")
    ends = [4350 + STEP * k for k in range(6)]
    CORRUPT, OVER = 5, 3        # late corruption; over budget -> degraded
    events = []                 # ("P", shard) / ("L", chips) / ("F", n)

    def spy(real):
        def launch(*a, **k):
            p = real(*a, **k)
            events.append(("L", sorted({{d.id for x in p._out
                                        for d in x.devices()}})))

            def collect(in_flight=1):
                events.append(("F", in_flight))
                return p.collect(in_flight)
            return types.SimpleNamespace(collect=collect)
        return launch
    sweep_ops.sweep_launch = spy(sweep_ops.sweep_launch)
    sweep_ops.window_launch = spy(sweep_ops.window_launch)

    def valid_for(k):
        if k != CORRUPT:
            return None
        v = np.ones((HOSTS, len(channels), W), bool)
        v[250, li, -100:] = False          # last shard, below quarantine
        return v

    def run(mon):
        out, log = [], []
        for k, e in enumerate(ends):
            t, d, v = ts[e - W:e], data[:, :, e - W:e], valid_for(k)
            cost = 100.0 if k == OVER else 0.0
            if mon is None:
                fd = single.diagnose_fleet(t, d, channels, valid=v,
                                           extra_cost_s=cost)
            else:
                del events[:]

                def provider(s):
                    events.append(("P", s))
                    a, b = mon.plan.bounds[s]
                    return d[a:b], None if v is None else v[a:b]
                fd = mon.diagnose_sharded(t, provider, channels,
                                          extra_cost_s=cost)
                log.append(list(events))
            out.append(json.dumps(verdict_fingerprint(fd), sort_keys=True))
            out[-1] = [out[-1], bool(fd.degraded), fd.flagged_hosts]
        return out, log

    kw = dict(use_kernels=False, rca_top_k=4, budget_s=30.0, shed_after=1)
    plan = ShardPlan.for_fleet(HOSTS, shard_hosts=SHARD, rack_shards=4)
    single = FleetMonitor(**kw)
    res = {{"single": run(None)[0]}}
    for n in (1, 4):
        mon = ShardedFleetMonitor(plan, devices=jax.devices()[:n], **kw)
        fps, log = run(mon)
        res[str(n)] = {{"fps": fps, "log": log,
                        "chips": [d.id for d in mon.devices]}}
    print("RESULT " + json.dumps(res))
""")


@pytest.fixture(scope="module")
def placement(tmp_path_factory):
    """Six streaming storm rounds of 8 shards x 32 hosts (round 3 over
    budget so round 4 is degraded, round 5 with corruption surfacing on
    the last shard), on one device, on four and through the single-slab
    monitor, in a subprocess with four host devices.  Per sharded round:
    the sequence of provider calls (P), sweep launches with the devices
    their arrays live on (L) and sweep pulls with ``in_flight`` (F)."""
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    script = tmp_path_factory.mktemp("placement") / "probe.py"
    script.write_text(_PLACEMENT.format(
        paths=[os.path.join(root, "src"), root]))
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_placement_verdicts_identical_on_one_and_four_devices(placement):
    single = placement["single"]
    assert any(d for _, d, _ in single) and all(f for _, _, f in single)
    assert placement["1"]["fps"] == single
    assert placement["4"]["fps"] == single


def test_placement_provider_order_and_oracle_revisit(placement):
    for n in ("1", "4"):
        for k, log in enumerate(placement[n]["log"]):
            calls = [v for e, v in log if e == "P"]
            rca = json.loads(placement[n]["fps"][k][0])["diagnoses"]
            owners = sorted({int(h) // 32 for h in rca})
            assert owners, (n, k)
            # round 5: shard 7 sees the corruption and runs the oracle,
            # shards 0-6 are re-visited through it once all have finished;
            # then one fetch per shard owning an RCA'd host, ascending
            assert calls == list(range(8)) + (list(range(7)) if k == 5
                                              else []) + owners, (n, k)


def test_placement_sweeps_on_their_chips_four_in_flight(placement):
    four = placement["4"]
    assert four["chips"] == [s % 4 for s in range(8)]
    for log in four["log"]:
        shards = [v for e, v in log if e == "P"]
        launched = [v for e, v in log if e == "L"]
        assert launched == [[s % 4] for s in shards[:len(launched)]]
        # a pull starts once four shards are in flight; the last three
        # drain; round 5's shard 7 ran the oracle, with no sweep
        pulls = [v for e, v in log if e == "F"]
        assert pulls == [4, 4, 4, 4, 4, 3, 2, 1][:len(launched)]
        assert len(launched) in (7, 8)


def test_placement_one_device_finishes_each_launch_before_the_next(
        placement):
    for log in placement["1"]["log"]:
        pulls = [v for e, v in log if e == "F"]
        assert pulls and set(pulls) == {1}
        # after the provider call: its launch, then straight its pull
        seq = [e for e, _ in log]
        for i, e in enumerate(seq):
            if e == "L":
                assert seq[i + 1] == "F"
