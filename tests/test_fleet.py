"""Fleet-level RCA: straggler localization + mitigation mapping (paper
§5.1 extension)."""
import numpy as np
import pytest

from repro.core.taxonomy import CauseClass
from repro.monitor.fleet import FleetMonitor, Mitigation
from repro.sim.scenario import make_trial


def _fleet_data(n_hosts, bad_host, cls, seed=0):
    """Fixed onset at t=40s; quiet hosts get intensity 0 (pure ambient).
    Windows are clipped to shortly after the event so the streaming
    trailing-window monitor sees it (as it would live)."""
    trials = []
    for h in range(n_hosts):
        inten = 2.0 if h == bad_host else 0.0
        t = make_trial(seed + h, cls, intensity=inten, t_on=40.0,
                       confuser_prob=0.0)
        trials.append(t)
    # clip shortly after onset so the trailing baseline window stays clean
    t_hi = int(46.0 * trials[0].rate_hz)
    data = np.stack([t.data[:, :t_hi] for t in trials])
    return trials[0].ts[:t_hi], data, trials[0].channels, trials[bad_host]


def test_straggler_localized_and_explained():
    ts, data, channels, bad = _fleet_data(4, 2, "nic", seed=100)
    mon = FleetMonitor(use_kernels=True)
    fd = mon.diagnose_fleet(ts, data, channels)
    assert fd.straggler_host == 2
    assert fd.diagnosis is not None
    assert fd.diagnosis.top_cause == CauseClass.NIC
    assert fd.mitigation == Mitigation.HIERARCHICAL_ALLREDUCE


def test_mitigation_escalates_on_persistence():
    mon = FleetMonitor(use_kernels=False, persistent_threshold=2)
    ts, data, channels, _ = _fleet_data(3, 1, "cpu", seed=200)
    fd1 = mon.diagnose_fleet(ts, data, channels)
    fd2 = mon.diagnose_fleet(ts, data, channels)
    assert fd1.mitigation == Mitigation.REPIN_CPU
    assert fd2.mitigation == Mitigation.EXCLUDE_AND_RESCALE


def test_kernel_and_numpy_paths_agree():
    ts, data, channels, _ = _fleet_data(3, 0, "io", seed=300)
    a = FleetMonitor(use_kernels=True).diagnose_fleet(ts, data, channels)
    b = FleetMonitor(use_kernels=False).diagnose_fleet(ts, data, channels)
    assert a.straggler_host == b.straggler_host
    np.testing.assert_allclose(a.per_host_scores, b.per_host_scores,
                               rtol=1e-4, atol=1e-4)
    assert a.flagged_hosts == b.flagged_hosts
    for h in a.flagged_hosts:
        assert a.diagnoses[h].top_cause == b.diagnoses[h].top_cause


@pytest.mark.parametrize("cls", ["io", "cpu", "nic", "gpu"])
def test_batched_rca_agrees_with_per_host_engine(cls):
    """Every flagged host's batched verdict == a scalar engine.process
    replay of that host's slab — the fused dispatch changes throughput,
    not diagnoses."""
    from repro.core.engine import CorrelationEngine
    for seed in (100, 400):
        ts, data, channels, _ = _fleet_data(3, 1, cls, seed=seed)
        fd = FleetMonitor(use_kernels=False).diagnose_fleet(ts, data, channels)
        assert fd.flagged_hosts, f"{cls}/{seed}: no host flagged"
        eng = CorrelationEngine()
        for h in fd.flagged_hosts:
            diags = eng.process(ts, data[h], channels)
            assert diags, f"{cls}/{seed}: engine found nothing on host {h}"
            assert diags[0].top_cause == fd.diagnoses[h].top_cause


def test_multiple_stragglers_one_dispatch():
    """Two injected stragglers with different causes: both flagged, both
    explained from the same batched dispatch, each with its own verdict."""
    t_nic = make_trial(500, "nic", intensity=2.0, t_on=40.0, confuser_prob=0.0)
    t_io = make_trial(501, "io", intensity=2.0, t_on=40.0, confuser_prob=0.0)
    quiet = [make_trial(510 + h, "nic", intensity=0.0, t_on=40.0,
                        confuser_prob=0.0) for h in range(2)]
    t_hi = int(46.0 * t_nic.rate_hz)
    data = np.stack([t.data[:, :t_hi]
                     for t in (quiet[0], t_nic, quiet[1], t_io)])
    fd = FleetMonitor(use_kernels=False).diagnose_fleet(
        t_nic.ts[:t_hi], data, t_nic.channels)
    assert set(fd.flagged_hosts) == {1, 3}
    assert fd.diagnoses[1].top_cause == CauseClass.NIC
    assert fd.diagnoses[3].top_cause == CauseClass.IO
    assert fd.mitigations[1] == Mitigation.HIERARCHICAL_ALLREDUCE
    assert fd.mitigations[3] == Mitigation.REBALANCE_INPUT
    # the worst host leads the flagged list and fills the legacy fields
    assert fd.straggler_host == fd.flagged_hosts[0]
    assert fd.diagnosis is fd.diagnoses[fd.straggler_host]


def test_transient_glitch_does_not_outrank_persistent_straggler():
    """A single-sample latency glitch can carry the fleet's highest max-z
    but must not be named straggler over a persistent spike."""
    ts, data, channels, _ = _fleet_data(3, 1, "nic", seed=700)
    li = channels.index("coll_allreduce_ms")
    data = data.copy()
    data[0, li, -10] += 1e4                 # one-sample glitch on host 0
    fd = FleetMonitor(use_kernels=False).diagnose_fleet(ts, data, channels)
    assert fd.per_host_scores[0] > fd.per_host_scores[1]
    assert 0 not in fd.flagged_hosts
    assert fd.straggler_host == 1
    assert fd.diagnosis is fd.diagnoses[1]


def test_no_evidence_channels_degrades_gracefully():
    """Latency-only telemetry: a flagged host gets no verdict, not a crash."""
    ts, data, channels, _ = _fleet_data(2, 1, "cpu", seed=950)
    li = channels.index("coll_allreduce_ms")
    fd = FleetMonitor(use_kernels=False).diagnose_fleet(
        ts, data[:, [li], :], ["coll_allreduce_ms"])
    assert fd.flagged_hosts == [1]
    assert fd.diagnosis is None
    assert fd.mitigation == Mitigation.NONE


@pytest.mark.parametrize("T", [8, 40, 62])
def test_short_window_returns_quiet_verdict_not_spurious_stragglers(T):
    """Satellite bug: at tiny T the clamps ``wn = T//2; bn = T - wn`` can
    leave a baseline below MIN_BASELINE_N, whose sigma-floored z-scores
    flagged perfectly quiet hosts.  Short snapshots must yield an explicit
    quiet verdict with the skip marker instead."""
    ts, data, channels, _ = _fleet_data(3, 1, "cpu", seed=250)
    ts, data = ts[:T], data[:, :, :T]
    fd = FleetMonitor(use_kernels=False).diagnose_fleet(ts, data, channels)
    assert fd.flagged_hosts == []
    assert fd.diagnosis is None
    assert fd.mitigation == Mitigation.NONE
    assert np.all(fd.per_host_scores == 0.0)
    assert "short_baseline_skip" in fd.stage_seconds


def test_short_window_round_clears_strike_history():
    """The short-baseline quiet verdict is a 'not flagged this round'
    round: strike counts must reset exactly as on a quiet full window,
    or a short snapshot between two flagged rounds would let stale
    strikes escalate to EXCLUDE_AND_RESCALE."""
    mon = FleetMonitor(use_kernels=False, persistent_threshold=2)
    ts, data, channels, _ = _fleet_data(3, 1, "cpu", seed=200)
    fd1 = mon.diagnose_fleet(ts, data, channels)
    assert fd1.mitigation == Mitigation.REPIN_CPU     # strike 1
    mon.diagnose_fleet(ts[:40], data[:, :, :40], channels)  # short round
    assert mon._strikes == {}
    fd2 = mon.diagnose_fleet(ts, data, channels)
    assert fd2.mitigation == Mitigation.REPIN_CPU     # strike restarts at 1


def test_quiet_fleet_flags_nothing():
    ts, data, channels, _ = _fleet_data(4, 0, "cpu", seed=900)
    quiet = data.copy()
    # neutralize the injected host by replacing it with another quiet one
    quiet[0] = data[3]
    fd = FleetMonitor(use_kernels=False).diagnose_fleet(ts, quiet, channels)
    assert fd.flagged_hosts == []
    assert fd.diagnosis is None
    assert fd.mitigation == Mitigation.NONE
