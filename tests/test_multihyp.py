"""Concurrent incident hypotheses: Layer-2 multi-hypothesis detection,
Layer-3 reconciliation, K=1 degeneracy, and fleet multi-cause verdicts.

The refactor's contract, end to end: a second fault arriving during an
active incident opens a second hypothesis instead of dying in the
cooldown; reconciliation attributes each matured hypothesis to a distinct
cause (or suppresses the continuation phantom); with ``max_hypotheses=1``
every stream is byte-identical to the single-pending machine's.
"""
import numpy as np
import pytest

from repro.core.engine import CorrelationEngine, EngineConfig
from repro.core.reconcile import CO_GAP, symptom_table
from repro.core.taxonomy import CauseClass
from repro.monitor.fleet import FleetMonitor
from repro.sim import scoring
from repro.sim.scenario import protocol_seed
from repro.sim.scenarios import SCENARIO_CLASSES, make_scenario

SEED = 41


def _trial(cls, k=0, seed=SEED):
    ci = SCENARIO_CLASSES.index(cls)
    return make_scenario(protocol_seed(seed, ci, k), cls)[0]


# ------------------------------------------------------------------ Layer 2
def test_second_fault_opens_second_hypothesis():
    """overlap_pair: the second fault's step fires INSIDE the first
    incident's cooldown and must still produce its own detection."""
    eng = CorrelationEngine()
    hit = 0
    for k in range(4):
        t = _trial("overlap_pair", k)
        evs = eng.detect_events(np.asarray(t.ts), t.data, t.channels)
        if len(evs) >= 2:
            gaps = [b[0].t_detect - a[0].t_detect
                    for a, b in zip(evs, evs[1:])]
            hit += any(0.0 < g < eng.cfg.cooldown_s for g in gaps)
    assert hit >= 2, "no trial detected a second fault inside the cooldown"


def test_hypothesis_count_bounded():
    eng = CorrelationEngine()
    for cls in ("flap", "cascade", "overlap_full"):
        for k in range(4):
            t = _trial(cls, k)
            evs = eng.detect_events(np.asarray(t.ts), t.data, t.channels)
            # no two emissions may share a detection tick, and the live
            # set can never exceed max_hypotheses concurrent accumulations
            detects = [e.t_detect for e, _ in evs]
            assert len(detects) == len(set(detects))


def test_k1_degeneracy_single_fault_byte_identical():
    """On single-fault timelines a K=3 engine's detection stream equals a
    K=1 engine's byte for byte — the step gate never opens a phantom."""
    eng3 = CorrelationEngine(EngineConfig(max_hypotheses=3))
    eng1 = CorrelationEngine(EngineConfig(max_hypotheses=1))
    sig = lambda evs: [(e.t_onset, e.t_detect, e.score, int(r))
                       for e, r in evs]
    for cls in ("single", "soak"):
        for k in range(4):
            t = _trial(cls, k)
            ts = np.asarray(t.ts)
            assert sig(eng3.detect_events(ts, t.data, t.channels)) == \
                sig(eng1.detect_events(ts, t.data, t.channels))


def test_k1_degeneracy_verdict_stream_identical():
    """process() with K=1 skips reconciliation entirely: verdict streams
    on single-fault trials match the K=3 engine's exactly."""
    eng3 = CorrelationEngine()
    eng1 = CorrelationEngine(EngineConfig(max_hypotheses=1))
    sig = lambda ds: [(d.top_cause, d.event.t_onset, d.event.t_detect,
                       d.t_ready) for d in ds]
    for k in range(4):
        t = _trial("single", k)
        assert sig(eng3.process(t.ts, t.data, t.channels)) == \
            sig(eng1.process(t.ts, t.data, t.channels))


# ------------------------------------------------------- Layer 3 attribution
@pytest.mark.parametrize("cls", ["overlap_pair", "overlap_full"])
def test_overlap_verdicts_cover_both_causes(cls):
    """Every concurrent fault earns a verdict with ITS cause — recall and
    accuracy 1.0 over the suite seed's trials."""
    eng = CorrelationEngine()
    scores = []
    for k in range(4):
        t = _trial(cls, k)
        diags = eng.process(t.ts, t.data, t.channels)
        scores.append(scoring.score_trial(
            t.truth, scoring.verdict_events(diags)))
    s = scoring.summarize(scores)
    assert s["recall"] == 1.0, s
    assert s["accuracy"] == 1.0, s
    assert s["false_verdicts"] == 0, s


def test_verdict_causes_distinct_within_incident():
    """Reconciliation never emits the same cause twice for one incident."""
    eng = CorrelationEngine()
    for cls in ("overlap_pair", "overlap_full", "cascade", "flap"):
        for k in range(4):
            t = _trial(cls, k)
            diags = eng.process(t.ts, t.data, t.channels)
            cool = eng.cfg.cooldown_s
            seen: list = []
            for d in diags:
                # causes repeat only across incidents (a cooldown apart)
                for prev_t, prev_c in seen:
                    if prev_c == d.top_cause:
                        assert d.event.t_detect - prev_t >= cool
                seen.append((d.event.t_detect, d.top_cause))


def test_soak_emits_nothing():
    eng = CorrelationEngine()
    for k in range(4):
        t = _trial("soak", k)
        assert eng.process(t.ts, t.data, t.channels) == []


def test_symptom_table_covers_all_interference_causes():
    tab = symptom_table()
    assert set(tab) == {CauseClass.NIC, CauseClass.CPU, CauseClass.IO,
                        CauseClass.GPU}
    assert set(CO_GAP) == set(tab)
    for chans in tab.values():
        assert all(floor > 0 for _, floor in chans)


# ----------------------------------------------------------------- fleet
def _fleet_slab():
    quiet = _trial("soak", 0, seed=7)
    hot = _trial("overlap_full", 0)
    on = int(hot.truth[0].t_on * 100)
    T = on + 400             # onset inside the trailing detection window
    slab = np.stack([np.asarray(quiet.data)[:, :T],
                     np.asarray(hot.data)[:, :T]]).astype(np.float32)
    return np.asarray(hot.ts)[:T], slab, hot.channels, hot.truth


def test_fleet_multi_cause_verdict_lists():
    """A host under two overlapping faults carries both causes in its
    verdict list, primary first; with K=1 the list is primary-only."""
    ts, slab, channels, truth = _fleet_slab()
    fd = FleetMonitor(EngineConfig()).diagnose_fleet(ts, slab, channels)
    assert fd.flagged_hosts == [1]
    causes = fd.causes[1]
    assert causes[0] == fd.diagnoses[1].top_cause
    assert set(causes) == {e.kind for e in truth}
    assert len(causes) == len(set(causes))

    fd1 = FleetMonitor(EngineConfig(max_hypotheses=1)).diagnose_fleet(
        ts, slab, channels)
    assert fd1.causes[1] == [fd1.diagnoses[1].top_cause]


def test_fleet_causes_f32_gather_matches_f64():
    """The co-cause corroboration runs in f64 on the gathered evidence,
    so the monitor's f32 gather gives the cause lists an f64 copy of the
    same evidence gives."""
    ts, slab, channels, truth = _fleet_slab()
    mon = FleetMonitor(EngineConfig(), use_kernels=False)
    real, seen = mon._rca_from_evidence, []

    def spy(ts_, X, *rest):
        seen.append((ts_, X, rest))
        return real(ts_, X, *rest)
    mon._rca_from_evidence = spy
    fd = mon.diagnose_fleet(ts, slab, channels)
    (ts_, X, rest), = seen
    assert X.dtype == np.float32
    _, causes = real(ts_, np.asarray(X, np.float64), *rest[:-1], {})
    assert fd.causes == causes
    assert set(fd.causes[1]) == {e.kind for e in truth}
