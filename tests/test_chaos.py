"""Chaos-hardening tests: a broken probe must never read as a broken host.

Covers the chaos DSL (sim.chaos), the sanitize layer, the masked
detection paths (sweep_rows / sweep_rows_exact / kernel / slab vs the f64
oracle in core.spike), agent crash isolation + watchdog + clock/counter
guards, the bounded seqlock reader, aggregator validity staging, and the
FleetMonitor telemetry quarantine — plus the clean-path contract: with an
all-true mask every path is byte-identical to the unmasked one.
"""
import numpy as np
import pytest

from repro.core import sanitize
from repro.core import spike
from repro.core.engine import MIN_BASELINE_N, CorrelationEngine, EngineConfig
from repro.kernels.detect import ops as detect_ops
from repro.kernels.sweep import ops as sweep_ops
from repro.monitor.aggregator import FleetAggregator
from repro.monitor.fleet import FleetMonitor, Mitigation
from repro.sim import chaos
from repro.sim import scenarios as scen
from repro.sim.chaos import ChaosCollector, ChaosEvent, ChaosPolicy
from repro.telemetry.agent import TelemetryAgent
from repro.telemetry.collectors import Collector, SimCollector
from repro.telemetry.ringbuffer import MultiChannelRing
from repro.telemetry.schema import (
    LATENCY_METRIC, CauseClass, MetricSpec, SignalGroup,
)


# --------------------------------------------------------------- chaos DSL

def test_chaos_event_validation():
    with pytest.raises(ValueError):
        ChaosEvent("gremlin", 1.0, 2.0)
    ev = ChaosEvent("nan", 1.0, 2.0, channel="x")
    assert ev.t_off == 3.0
    assert ev.active(1.0) and ev.active(2.999) and not ev.active(3.0)


def test_chaos_policy_compose_and_overlap():
    a = ChaosPolicy((ChaosEvent("drop", 5.0, 1.0),))
    b = ChaosPolicy((ChaosEvent("nan", 1.0, 1.0, channel="x"),))
    p = a.compose(b)
    assert [e.t_on for e in p.events] == [1.0, 5.0]     # time-sorted
    assert p.overlaps(5.5, 7.0) and not p.overlaps(2.5, 4.5)
    assert [e.kind for e in p.active(1.5)] == ["nan"]
    assert p.active(1.5, kinds=("drop",)) == []


def test_apply_chaos_ground_truth_mask():
    rate = 10.0
    C, T = 3, 100
    data = np.full((C, T), 5.0)
    chans = ["a", "b", "c"]
    events = [
        ChaosEvent("nan", 1.0, 0.5, channel="a"),
        ChaosEvent("inf", 2.0, 0.5, channel="b", magnitude=-1.0),
        ChaosEvent("freeze", 3.0, 1.0, channel="c", magnitude=1.0),
        ChaosEvent("drop", 6.0, 0.5),
        ChaosEvent("exception", 8.0, 0.5),          # behavioral: no-op here
    ]
    hit = chaos.apply_chaos(data, chans, rate, events)
    assert np.isnan(data[0, 10:15]).all() and hit[0, 10:15].all()
    assert (data[1, 20:25] == -np.inf).all()
    assert (data[2, 30:40] == 10.0).all()           # 5 * (1 + magnitude)
    assert np.isnan(data[:, 60:65]).all() and hit[:, 60:65].all()
    assert hit[:, 80:85].sum() == 0                 # behavioral kinds ignored
    clean = ~hit
    assert np.isfinite(data[clean]).all() and (data[clean] == 5.0).all()


def test_apply_clock_jumps():
    ts = np.arange(0.0, 10.0, 1.0)
    out = chaos.apply_clock_jumps(
        ts, [ChaosEvent("clock_jump", 5.0, 0.0, magnitude=-2.0)])
    np.testing.assert_array_equal(out[:5], ts[:5])
    np.testing.assert_array_equal(out[5:], ts[5:] - 2.0)
    assert out is not ts and (np.diff(out) <= 0).any()


# ---------------------------------------------------------------- sanitize

def test_validity_mask_clean_is_none():
    x = np.random.default_rng(0).normal(10.0, 1.0, (4, 256))
    assert sanitize.validity_mask(x) is None


def test_validity_mask_flags_nonfinite_and_freeze():
    rng = np.random.default_rng(1)
    x = rng.normal(10.0, 1.0, 512)
    x[10] = np.nan
    x[20] = np.inf
    n = sanitize.FREEZE_RUN_N
    x[100:100 + n + 5] = 42.0                       # frozen run >= run_n
    x[300:300 + n // 2] = 43.0                      # short run: legitimate
    v = sanitize.validity_mask(x)
    assert v is not None
    assert not v[10] and not v[20]
    # the WHOLE run is retroactively invalid, head included — a frozen
    # baseline must not poison the sigma floor
    assert not v[100:100 + n + 5].any()
    assert v[300:300 + n // 2].all()


def test_forward_fill_contract():
    x = np.random.default_rng(2).normal(0.0, 1.0, (3, 64))
    assert sanitize.forward_fill(x) is x            # clean: same object
    y = x.copy()
    y[0, 10] = np.nan
    y[1, 0] = np.nan                                # leading hole: backfill
    y[2, :] = np.nan                                # dead row: zeros
    f = sanitize.forward_fill(y)
    assert np.isfinite(f).all()
    assert f[0, 10] == y[0, 9]
    assert f[1, 0] == y[1, 1]
    assert (f[2] == 0.0).all()


def test_min_valid_baseline_pinned_to_engine():
    # the masked oracle's baseline gate mirrors the engine's short-window
    # skip; the two constants drifting apart would let one path fire on a
    # micro-baseline the other refuses
    assert spike.MIN_VALID_BASELINE_N == MIN_BASELINE_N


# ------------------------------------------- masked sweep paths vs oracle

def _poisoned_slab(R=6, wn=64, bn=256, seed=3):
    rng = np.random.default_rng(seed)
    T = bn + 4 * wn
    lat = rng.normal(10.0, 1.0, (R, T))
    lat[2, bn + wn:bn + 2 * wn] += 8.0              # genuine spike
    lat[4, bn + 2 * wn:bn + 2 * wn + 20] += 8.0     # spike we then poison
    valid = np.ones((R, T), bool)
    hit = chaos.apply_chaos(
        lat, [f"r{i}" for i in range(R)], 1.0,
        [ChaosEvent("nan", bn + 2 * wn, 20.0, channel="r4"),
         ChaosEvent("freeze", 50.0, 100.0, channel="r1", magnitude=1.5),
         ChaosEvent("inf", float(bn), 10.0, channel="r3")])
    valid &= ~hit
    lat = np.where(valid, lat, np.nan)              # poison is non-finite
    ticks = np.arange(wn + bn, T + 1, wn)
    return lat, valid, ticks, wn, bn


def _oracle_rows(lat, valid, ticks, wn, bn, persistence=0.2):
    R = lat.shape[0]
    fire = np.zeros((R, ticks.size), bool)
    for r in range(R):
        fire[r], _, _ = spike.detect_sweep_masked(
            np.nan_to_num(lat[r]), valid[r], wn, bn, ticks,
            persistence=persistence)
    return fire


@pytest.mark.parametrize("use_kernel", [False, True])
def test_sweep_rows_masked_matches_oracle(use_kernel):
    lat, valid, ticks, wn, bn = _poisoned_slab()
    staged = np.nan_to_num(lat)
    want = _oracle_rows(staged, valid, ticks, wn, bn)
    fire, score, onset, _ = sweep_ops.sweep_rows(
        staged, wn, bn, ticks, persistence=0.2, valid=valid,
        use_kernel=use_kernel)
    np.testing.assert_array_equal(fire, want)
    assert fire[2].any()                            # clean spike still fires
    assert not fire[4].any()                        # poisoned spike quiet
    assert not fire[1].any() and not fire[3].any()


def test_sweep_rows_exact_masked_matches_oracle():
    lat, valid, ticks, wn, bn = _poisoned_slab()
    staged = np.nan_to_num(lat)
    fire, score, onset = sweep_ops.sweep_rows_exact(
        staged, wn, bn, ticks, persistence=0.2, valid=valid)
    for r in range(staged.shape[0]):
        f, s, o = spike.detect_sweep_masked(
            staged[r], valid[r], wn, bn, ticks, persistence=0.2)
        np.testing.assert_array_equal(fire[r], f)
        fired = np.flatnonzero(f)
        np.testing.assert_array_equal(score[r, fired], s[fired])
        np.testing.assert_array_equal(onset[r, fired], o[fired])


def test_sweep_rows_all_true_mask_byte_identical():
    rng = np.random.default_rng(5)
    wn, bn = 64, 256
    T = bn + 3 * wn
    lat = rng.normal(10.0, 1.0, (5, T))
    lat[1, bn + wn:bn + 2 * wn] += 8.0
    ticks = np.arange(wn + bn, T + 1, wn)
    ones = np.ones_like(lat, bool)
    a = sweep_ops.sweep_rows(lat, wn, bn, ticks, persistence=0.2)
    b = sweep_ops.sweep_rows(lat, wn, bn, ticks, persistence=0.2, valid=ones)
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)
    ea = sweep_ops.sweep_rows_exact(lat, wn, bn, ticks, persistence=0.2)
    eb = sweep_ops.sweep_rows_exact(lat, wn, bn, ticks, persistence=0.2,
                                    valid=ones)
    for x, y in zip(ea, eb):
        np.testing.assert_array_equal(x, y)


def test_masked_baseline_gate_refuses_thin_baselines():
    # with fewer than MIN_VALID_BASELINE_N valid baseline samples even a
    # monster spike stays quiet — a sigma-floored micro-baseline lies
    rng = np.random.default_rng(7)
    wn, bn = 64, 256
    T = bn + 2 * wn
    lat = rng.normal(10.0, 1.0, (1, T))
    lat[0, bn:] += 50.0
    valid = np.ones((1, T), bool)
    valid[0, :spike.MIN_VALID_BASELINE_N - 1] = False
    ticks = np.array([wn + bn])
    fire, _, _ = sweep_ops.sweep_rows_exact(lat, wn, bn, ticks, valid=valid)
    assert fire.any()                               # 31 invalid: still >= gate
    valid[0, :bn] = False
    valid[0, bn - spike.MIN_VALID_BASELINE_N + 1:bn] = True   # only 31 valid
    fire, score, _ = sweep_ops.sweep_rows_exact(lat, wn, bn, ticks,
                                                valid=valid)
    assert not fire.any() and (score == 0.0).all()


def test_detect_hosts_slab_masked_matches_rows_oracle():
    rng = np.random.default_rng(9)
    H, wn, bn = 4, 64, 256
    tail = rng.normal(10.0, 1.0, (H, bn + wn))
    tail[1, bn:] += 8.0                             # clean straggler
    tail[2, bn:] += 8.0                             # straggler, poisoned win
    valid = np.ones((H, bn + wn), bool)
    valid[2, bn:] = False
    valid[3, 100:110] = False                       # benign baseline nicks
    f, s, o = detect_ops.detect_hosts_slab(tail, wn, bn, persistence=0.2,
                                           valid=valid)
    wf, ws, wo = spike.detect_rows_masked(
        tail[:, bn:].astype(np.float64), tail[:, :bn].astype(np.float64),
        valid[:, bn:], valid[:, :bn], 3.0, 0.2)
    np.testing.assert_array_equal(f, wf)
    np.testing.assert_array_equal(s, ws)
    np.testing.assert_array_equal(o, wo)
    assert f[1] and not f[2]
    # all-true mask: dropped, byte-identical to valid=None
    a = detect_ops.detect_hosts_slab(tail, wn, bn, persistence=0.2)
    b = detect_ops.detect_hosts_slab(tail, wn, bn, persistence=0.2,
                                     valid=np.ones_like(valid))
    for x, y in zip(a, b):
        np.testing.assert_array_equal(x, y)


# --------------------------------------------------------- engine under chaos

def test_engine_zero_verdicts_on_pure_corruption():
    eng = CorrelationEngine(EngineConfig())
    for name in ("chaos_soak", "frozen_channel", "crash_restart"):
        t = scen.make_scenario(11, name)[0]
        assert t.truth == [] and t.chaos
        assert eng.process(t.ts, t.data, t.channels) == []


def test_engine_detects_fault_under_chaos_overlap():
    eng = CorrelationEngine(EngineConfig())
    t = scen.make_scenario(7, "chaos_overlap")[0]
    assert len(t.truth) == 1 and t.chaos
    diags = eng.process(t.ts, t.data, t.channels)
    assert len(diags) >= 1
    d = diags[0]
    assert d.event.t_detect - t.truth[0].t_on <= 5.0 + 1e-6


def test_chaos_classes_extend_protocol_stably():
    # chaos classes append AFTER the committed classes: fleet_nic keeps
    # index 6, so protocol_seed(seed, class_index, k) stays byte-stable
    classes = list(scen.SCENARIO_CLASSES)
    assert classes.index("fleet_nic") == 6
    assert classes[7:11] == ["chaos_soak", "chaos_overlap",
                             "frozen_channel", "crash_restart"]
    for name in classes:
        assert scen.scenario_spec(name).description


# -------------------------------------------------------- agent hardening

def _sim_collector(T=400, rate=100.0, chan=LATENCY_METRIC, base=10.0):
    ts = np.arange(T) / rate
    data = np.full((1, T), base, np.float32)
    return SimCollector([chan], ts, data), ts


class _CounterCollector(Collector):
    """Feeds an explicit cumulative-counter sequence, one value per call."""

    def __init__(self, values):
        self.metrics = [MetricSpec("chaos_test_bytes", SignalGroup.NET,
                                   "B", 100.0, monotonic_counter=True)]
        self.values = list(values)
        self.i = 0

    def sample(self, now):
        v = self.values[min(self.i, len(self.values) - 1)]
        self.i += 1
        return {"chaos_test_bytes": float(v)}


def test_agent_isolates_collector_exceptions():
    inner, _ = _sim_collector()
    policy = ChaosPolicy((ChaosEvent("exception", 0.05, 0.02),))
    agent = TelemetryAgent([ChaosCollector(inner, policy)], rate_hz=100.0,
                           history_s=4.0)
    for i in range(40):
        agent.step(now=i * 0.01)
    assert agent.stats.collector_errors >= 1
    assert agent.stats.backoff_skips >= 1
    _, data = agent.ring.window(40)
    li = agent.ring.index[LATENCY_METRIC]
    assert np.isnan(data[li]).any()                 # crash marked invalid
    assert np.isfinite(data[li, -5:]).all()         # recovered after backoff


def test_agent_watchdog_trips_on_slow_collector():
    inner, _ = _sim_collector()
    policy = ChaosPolicy((ChaosEvent("slow", 0.10, 0.011, magnitude=0.03),))
    agent = TelemetryAgent([ChaosCollector(inner, policy)], rate_hz=100.0,
                           history_s=2.0)
    for i in range(15):
        agent.step(now=i * 0.01)
    assert agent.stats.watchdog_trips >= 1
    assert agent.stats.backoff_skips >= 1           # sat out the next tick


def test_agent_counter_reset_and_clock_guards():
    agent = TelemetryAgent([_CounterCollector([100, 200, 50, 150])],
                           rate_hz=100.0, history_s=1.0)
    rows = [agent.step(now=t) for t in (0.00, 0.01, 0.02, 0.03)]
    assert rows[1]["chaos_test_bytes"] == pytest.approx(100.0 / 0.01)
    assert rows[2]["chaos_test_bytes"] == 0.0       # reset: clamp, not -inf
    assert agent.stats.counter_resets == 1
    # backward clock jump: rates are garbage over dt <= 0 — emit 0, flag
    agent2 = TelemetryAgent([_CounterCollector([0, 100, 200, 300])],
                            rate_hz=100.0, history_s=1.0)
    grid = chaos.apply_clock_jumps(
        np.array([0.0, 0.01, 0.02, 0.03]),
        [ChaosEvent("clock_jump", 0.02, 0.0, magnitude=-0.015)])
    rows = [agent2.step(now=t) for t in grid]
    assert agent2.stats.clock_anomalies >= 1
    assert all(np.isfinite(r["chaos_test_bytes"]) and
               r["chaos_test_bytes"] >= 0.0 for r in rows)


def test_chaos_collector_blocks_columnar_fallback():
    inner, ts = _sim_collector()
    cc = ChaosCollector(inner, ChaosPolicy(
        (ChaosEvent("nan", 1.0, 0.5, channel=LATENCY_METRIC),)))
    assert cc.sample_block(ts[:300]) is None        # overlap: per-tick path
    out = cc.sample_block(ts[:50])                  # pre-chaos grid passes
    assert out is not None and np.isfinite(out[LATENCY_METRIC]).all()
    assert np.isnan(cc.sample(1.2)[LATENCY_METRIC])


# -------------------------------------------------- ring + aggregator

def test_ring_read_window_bounded_giveup():
    r = MultiChannelRing(["a"], capacity=16)
    for i in range(8):
        r.push_row(i * 0.01, {"a": float(i)})
    r._write_begin()                                # writer dies mid-write
    ts, data, retries = r.read_window(4, max_retries=3)
    assert ts.size == 0 and data.shape[1] == 0
    assert retries == 3 and r.torn_giveups == 1
    r._write_end()                                  # writer resumes: reads heal
    ts, data, _ = r.read_window(4, max_retries=3)
    assert ts.size == 4


def test_aggregator_valid_mask_and_idempotent_stop():
    rate, window_s = 100.0, 2.0
    agents = []
    for h in range(2):
        inner, _ = _sim_collector(T=600, rate=rate)
        policy = ChaosPolicy(
            (ChaosEvent("nan", 1.0, 0.3, channel=LATENCY_METRIC),)
            if h == 0 else ())
        agents.append(TelemetryAgent([ChaosCollector(inner, policy)],
                                     rate_hz=rate, history_s=4.0))
    agg = FleetAggregator(agents, window_s=window_s)
    agg.run_virtual(0.0, 3.0)
    snap = agg.assemble()
    assert snap.valid_mask is not None and snap.valid_mask.dtype == bool
    li = agg.channels.index(LATENCY_METRIC)
    assert not snap.valid_mask[0, li].all()         # chaos host has holes
    assert snap.valid_mask[1].all()                 # clean host fully valid
    assert np.isnan(snap.slab[0, li][~snap.valid_mask[0, li]]).all()
    agg.stop()
    agg.stop()                                      # second stop: no-op
    assert agg.stats.hung_agents == 0


# ------------------------------------------------------- fleet quarantine

def test_quarantine_hysteresis_state_machine():
    mon = FleetMonitor(EngineConfig())
    bad = np.array([0.5])
    ok = np.array([0.0])
    assert not mon._update_quarantine(bad)[0]       # 1st bad round: candidate
    assert mon._update_quarantine(bad)[0]           # 2nd: quarantined
    assert mon._update_quarantine(ok)[0]            # clean 1/2: still held
    assert not mon._update_quarantine(ok)[0]        # clean 2/2: re-admitted
    assert not mon._update_quarantine(bad)[0]
    assert mon._update_quarantine(bad)[0]           # re-quarantined
    # backoff doubled: now needs 4 clean rounds
    for _ in range(3):
        assert mon._update_quarantine(ok)[0]
    assert not mon._update_quarantine(ok)[0]
    # a single mid-streak bad round resets the clean streak
    mon2 = FleetMonitor(EngineConfig())
    mon2._update_quarantine(bad), mon2._update_quarantine(bad)
    mon2._update_quarantine(ok)
    mon2._update_quarantine(bad)                    # streak reset
    assert mon2._update_quarantine(ok)[0]           # 1 clean again: held


def _fleet_slab(seed=13, hosts=3, T=900):
    rng = np.random.default_rng(seed)
    cfg = EngineConfig(window_s=1.0, baseline_s=5.0)
    channels = [LATENCY_METRIC, "cpu_util_other"]
    data = rng.normal(10.0, 1.0, (hosts, len(channels), T))
    data[:, 1, :] = rng.uniform(0.0, 0.2, (hosts, T))
    return cfg, channels, data, np.arange(T) / cfg.rate_hz


def test_fleet_quarantine_suppresses_verdict_and_mitigates():
    cfg, channels, data, ts = _fleet_slab()
    hosts, C, T = data.shape
    data[0, 0, -cfg.window_n:] += 9.0               # spike on the BAD host
    data[2, 0, -cfg.window_n:] += 9.0               # spike on a clean host
    valid = np.ones_like(data, bool)
    valid[0, 0, T - cfg.window_n - cfg.baseline_n:] = (
        np.arange(cfg.window_n + cfg.baseline_n) % 3 != 0)  # ~33% invalid
    mon = FleetMonitor(cfg, use_kernels=False)
    d1 = mon.diagnose_fleet(ts, data, channels, valid=valid)
    assert d1.quarantined == []                     # round 1: candidate only
    d2 = mon.diagnose_fleet(ts, data, channels, valid=valid)
    assert d2.quarantined == [0]
    assert 0 not in d2.flagged_hosts                # never a straggler
    assert d2.per_host_scores[0] == 0.0
    assert d2.mitigations[0] == Mitigation.RESTART_TELEMETRY
    assert 2 in d2.flagged_hosts                    # real fault still caught
    assert CauseClass.TELEMETRY.value == "telemetry_fault"


def test_fleet_all_true_mask_byte_identical():
    cfg, channels, data, ts = _fleet_slab(seed=17)
    data[1, 0, -cfg.window_n:] += 9.0
    a = FleetMonitor(cfg, use_kernels=False).diagnose_fleet(
        ts, data, channels)
    b = FleetMonitor(cfg, use_kernels=False).diagnose_fleet(
        ts, data, channels, valid=np.ones_like(data, bool))
    np.testing.assert_array_equal(a.per_host_scores, b.per_host_scores)
    assert a.flagged_hosts == b.flagged_hosts
    assert a.straggler_host == b.straggler_host
    assert b.quarantined == []
