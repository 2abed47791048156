"""Streaming fleet-detect kernel: exact parity vs the scalar-rule oracle
(`detect_rows` + `spike_scores_matrix`) and the fleet-detect edge cases —
onset at the window edge, every host flagged, single-host fleets."""
import numpy as np
import pytest

from repro.core.spike import detect_rows, spike_scores_matrix
from repro.kernels.detect import detect_hosts, persistence_count
from repro.monitor import FleetMonitor, ShardedFleetMonitor, ShardPlan
from repro.sim.scenario import make_trial


@pytest.mark.parametrize("use_kernel", [False, True])
@pytest.mark.parametrize("H,Nw,Nb", [(1, 500, 2000), (7, 128, 128),
                                     (37, 500, 1900), (64, 512, 2048)])
def test_exact_parity_vs_detect_rows(use_kernel, H, Nw, Nb):
    rng = np.random.default_rng(H * 1000 + Nw)
    w = (rng.standard_normal((H, Nw)) * 2 + 5).astype(np.float32)
    b = (rng.standard_normal((H, Nb)) * 2 + 5).astype(np.float32)
    # a mix of firing, marginal and quiet rows
    w[0, Nw // 4: 3 * Nw // 4] += 25.0
    if H > 2:
        w[2, -5:] += 40.0          # hot tail, fails persistence
    fire, score, onset = detect_hosts(w, b, 3.0, 0.35, use_kernel=use_kernel)
    f0, s0, o0 = detect_rows(w, b, 3.0, 0.35)
    np.testing.assert_array_equal(fire, f0)
    np.testing.assert_array_equal(onset, o0)
    np.testing.assert_allclose(score, s0, rtol=1e-4, atol=1e-4)
    np.testing.assert_allclose(score, spike_scores_matrix(w, b),
                               rtol=1e-4, atol=1e-4)


def test_onset_exactly_at_window_edges():
    rng = np.random.default_rng(0)
    w = (rng.standard_normal((3, 500)) * 0.1 + 5).astype(np.float32)
    b = (rng.standard_normal((3, 2000)) * 0.1 + 5).astype(np.float32)
    w[0, 0:] += 30.0               # onset at the very first sample
    w[1, -1] += 30.0               # single hot sample at the last slot
    fire, _, onset = detect_hosts(w, b, 3.0, 0.0)
    f0, _, o0 = detect_rows(w, b, 3.0, 0.0)
    np.testing.assert_array_equal(fire, f0)
    np.testing.assert_array_equal(onset, o0)
    assert onset[0] == 0 and bool(fire[0])
    assert onset[1] == 499 and bool(fire[1])


def test_quiet_rows_onset_is_argmax_fallback():
    rng = np.random.default_rng(1)
    w = (rng.standard_normal((5, 500)) * 0.5 + 5).astype(np.float32)
    b = (rng.standard_normal((5, 2000)) * 0.5 + 5).astype(np.float32)
    fire, _, onset = detect_hosts(w, b, 3.0, 0.35)
    _, _, o0 = detect_rows(w, b, 3.0, 0.35)
    np.testing.assert_array_equal(onset, o0)


def test_persistence_count_matches_f64_mean_rule():
    for n in (1, 3, 500, 501, 997):
        for p in (0.0, 0.05, 0.35, 1 / 3, 0.5, 0.9999, 1.0):
            c = persistence_count(n, p)
            for cnt in (max(0, c - 1), c, min(n, c + 1)):
                assert (cnt / n >= p) == (cnt >= c), (n, p, c, cnt)


def _fleet_data(n_hosts, bad_host, cls, seed=0, clip_s=46.0):
    trials = [make_trial(seed + h, cls,
                         intensity=(2.0 if h == bad_host else 0.0),
                         t_on=40.0, confuser_prob=0.0)
              for h in range(n_hosts)]
    t_hi = int(clip_s * trials[0].rate_hz)
    data = np.stack([t.data[:, :t_hi] for t in trials])
    return trials[0].ts[:t_hi], data, trials[0].channels


@pytest.mark.parametrize("cls,layout", [
    *((c, lay) for c in ("nic", "io", "cpu", "gpu")
      for lay in ("slab", "sharded")),
    ("nic", "off_grid")])
def test_fleet_detect_matches_detect_rows(cls, layout):
    """The monitor's flagged hosts, onsets and per-host scores against
    f64 ``detect_rows`` over the same latency tail: on one slab and on a
    2-shard plan (both through the device window), and off the tick grid
    (the direct moment pass, no window)."""
    ts, data, channels = _fleet_data(6, 2, cls, seed=40)
    if layout == "off_grid":
        ts = ts + 0.004
    if layout == "sharded":
        mon = ShardedFleetMonitor(ShardPlan.from_bounds([(0, 3), (3, 6)]),
                                  use_kernels=False)
    else:
        mon = FleetMonitor(use_kernels=False)
    fd = mon.diagnose_fleet(ts, data, channels)
    cfg, T = mon.cfg, ts.size
    wn = min(cfg.window_n, T // 2)
    bn = min(cfg.baseline_n, T - wn)
    li = channels.index(cfg.latency_metric)
    tail = np.asarray(data[:, li, T - wn - bn:], np.float64)
    fire, score, onset = detect_rows(tail[:, bn:], tail[:, :bn],
                                     cfg.threshold, cfg.persistence)
    assert fire.any()
    assert sorted(fd.flagged_hosts) == np.flatnonzero(fire).tolist()
    for h in fd.flagged_hosts:
        assert fd.diagnoses[h].event.t_onset == ts[T - wn + onset[h]]
    np.testing.assert_allclose(fd.per_host_scores, score,
                               rtol=1e-4, atol=1e-4)
    st = mon.incremental_stats()
    assert st["window_full_puts"] == (0 if layout == "off_grid" else
                                      2 if layout == "sharded" else 1)


def test_single_host_fleet():
    ts, data, channels = _fleet_data(1, 0, "io", seed=60)
    fd = FleetMonitor(use_kernels=False).diagnose_fleet(ts, data, channels)
    assert fd.flagged_hosts == [0]
    assert fd.straggler_host == 0
    assert fd.diagnosis is not None


def test_every_host_flagged():
    ts, data, channels = _fleet_data(4, 0, "cpu", seed=80)
    # make every host the injected one
    data = np.stack([data[0]] * 4)
    fd = FleetMonitor(use_kernels=False).diagnose_fleet(ts, data, channels)
    assert sorted(fd.flagged_hosts) == [0, 1, 2, 3]
    assert set(fd.diagnoses) == {0, 1, 2, 3}


def test_stage_seconds_disjoint_and_complete():
    import time
    ts, data, channels = _fleet_data(3, 1, "nic", seed=90)
    mon = FleetMonitor(use_kernels=False)
    mon.diagnose_fleet(ts, data, channels)      # jit warm-up
    mon._strikes = {}
    t0 = time.perf_counter()
    fd = mon.diagnose_fleet(ts, data, channels)
    wall = time.perf_counter() - t0
    assert set(fd.stage_seconds) == {"detect", "gather", "kernel",
                                     "rank", "assemble"}
    total = sum(fd.stage_seconds.values())
    # disjoint stages sum to (slightly under) the observed wall time
    assert total <= wall + 1e-6


def test_strikes_cleared_per_host_on_recovery():
    """A recovered host loses its strikes even while another stays flagged
    — and the strike dict does not accumulate stale hosts on churn."""
    ts, data, channels = _fleet_data(4, 1, "cpu", seed=120)
    ts2, data2, _ = _fleet_data(4, 2, "cpu", seed=120)
    mon = FleetMonitor(use_kernels=False, persistent_threshold=3)
    mon.diagnose_fleet(ts, data, channels)
    assert mon._strikes.get(1) == 1
    # host 1 recovers, host 2 degrades: 1's strike history must vanish
    mon.diagnose_fleet(ts2, data2, channels)
    assert 1 not in mon._strikes
    assert mon._strikes.get(2) == 1
    # churn back and forth: dict never grows beyond the flagged set
    mon.diagnose_fleet(ts, data, channels)
    assert set(mon._strikes) == {1}
