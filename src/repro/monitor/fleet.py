"""Fleet-level RCA: the paper's §5.1 multi-node extension, implemented.

Per-host agents stream (host x metric x time) windows to one correlation
engine.  The batched Layer-2/Layer-3 math (spike scores over every host's
channels, lagged correlation against each host's latency series) runs
through the Pallas kernels — at 1000+ hosts this is the compute hot-spot
the kernels exist for.  Straggler localization = arg-max spike score across
the host axis.

Diagnosis is batched end to end: every host whose latency spike score
clears the threshold is explained in ONE fused-kernel dispatch
(hosts x metrics x lags via kernels.fused) with confidence ranking
vectorized over the host axis — the seed fell back to a per-host scalar
``engine.process`` replay for the single worst straggler, which is exactly
the per-node scaling wall at fleet size.  Verdicts map to mitigation hints
consumed by the training loop (fault tolerance wiring).

The columnar fast path (default, ``fast_detect=True``) keeps the pipeline
f32-contiguous from the telemetry ring to the verdict: Layer 2 is ONE
streaming-detect dispatch (kernels.detect — since PR 5 a single-tick view
of the suite-scale sweep core in kernels.sweep, so the fleet and the eval
share one sweep implementation) and the Layer-3 evidence gather stays f32
into the fused kernel.  ``fast_detect=False`` keeps the seed path — a
spike-kernel dispatch, then an f64 re-slice + scalar-rule ``detect_rows``
replay over the candidates, and an f64 evidence gather — as the parity
oracle: flagged hosts and onsets match the fast path byte-exactly *by
construction* (the sweep core's epsilon guard re-decides any host whose
window holds a z within the guard band of the threshold through the f64
oracle; the persistence gate compares an integer count), asserted by
tests and recorded in BENCH_fleet.json.

``stage_seconds`` reports *disjoint* pipeline stages (detect / gather /
kernel / rank / assemble); they cover most of a round but not all of it
(flag ordering and the strike lifecycle are in none).  Each stage is also
a program span (:mod:`repro.core.spans`), and finer spans split it on the
profiler's clock: ``docs/OPERATIONS.md``, "Tracing a round".
"""
from __future__ import annotations

import dataclasses
import enum
from typing import Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core import confidence as conf_mod
from repro.core import rolling
from repro.core import sanitize as sanitize_mod
from repro.core.engine import (
    MIN_BASELINE_N, EngineConfig, evidence_layout,
    orient_about_baseline, pick_baseline_slice,
)
from repro.core.reconcile import CO_GAP, symptom_table
from repro.core.spans import span
from repro.core.spans import stage as stage_span
from repro.core.spike import detect_rows
from repro.core.taxonomy import CauseClass, Diagnosis, SpikeEvent
from repro.kernels.detect import ops as detect_ops
from repro.kernels.fused import ops as fused_ops
from repro.kernels.spike import ops as spike_ops
from repro.kernels.sweep.ops import Resolved
from repro.kernels.xcorr import ops as xcorr_ops


class Mitigation(str, enum.Enum):
    """Operator action recommended for a verdict class (paper §6)."""
    NONE = "none"
    REBALANCE_INPUT = "rebalance_input_pipeline"   # IO verdict
    REPIN_CPU = "repin_or_isolate_cpu"             # CPU verdict
    HIERARCHICAL_ALLREDUCE = "fallback_hierarchical_allreduce"  # NIC/DCN
    EXCLUDE_AND_RESCALE = "checkpoint_exclude_host_rescale"     # persistent
    THROTTLE_REVIEW = "review_power_thermal_policy"             # GPU verdict
    RESTART_TELEMETRY = "restart_telemetry_agent"  # telemetry-fault verdict


VERDICT_TO_MITIGATION = {
    CauseClass.IO: Mitigation.REBALANCE_INPUT,
    CauseClass.CPU: Mitigation.REPIN_CPU,
    CauseClass.NIC: Mitigation.HIERARCHICAL_ALLREDUCE,
    CauseClass.GPU: Mitigation.THROTTLE_REVIEW,
    CauseClass.TELEMETRY: Mitigation.RESTART_TELEMETRY,
    CauseClass.UNKNOWN: Mitigation.NONE,
}


@dataclasses.dataclass
class FleetDiagnosis:
    """One fleet diagnosis round — the operator-facing verdict record.

    Field-by-field reading guide: ``docs/OPERATIONS.md``.
    """
    straggler_host: int
    straggler_score: float
    diagnosis: Optional[Diagnosis]
    mitigation: Mitigation
    per_host_scores: np.ndarray      # (hosts,) latency spike scores
    #: every host above threshold, worst first (the straggler leads)
    flagged_hosts: List[int] = dataclasses.field(default_factory=list)
    #: host -> diagnosis for ALL flagged hosts (one fused dispatch)
    diagnoses: Dict[int, Diagnosis] = dataclasses.field(default_factory=dict)
    mitigations: Dict[int, Mitigation] = dataclasses.field(default_factory=dict)
    #: host -> ordered verdict causes, primary first.  With
    #: ``cfg.max_hypotheses > 1`` a diagnosed host may carry co-causes:
    #: runner-up ranked causes whose symptom channel is corroborated on the
    #: evidence window and whose confidence sits within the per-cause
    #: ``reconcile.CO_GAP`` of the top cause (concurrent faults on ONE
    #: host).  With a single hypothesis every list is just the primary.
    causes: Dict[int, List[CauseClass]] = dataclasses.field(default_factory=dict)
    #: wall seconds per pipeline stage, disjoint (detect / gather / kernel /
    #: rank / assemble, plus reduce on a sharded round) — they cover most
    #: of the round's wall time, not all: ordering, the strike lifecycle
    #: and the budget update fall in no stage
    stage_seconds: Dict[str, float] = dataclasses.field(default_factory=dict)
    #: hosts whose telemetry is quarantined this round (persistently-bad
    #: validity) — fire suppressed, score zeroed, mitigation
    #: RESTART_TELEMETRY; never reported as stragglers
    quarantined: List[int] = dataclasses.field(default_factory=list)
    #: this round ran in deadline-degraded (detect-only) mode: the latency
    #: budget was blown on consecutive rounds, so Layer-3 RCA was shed for
    #: every flagged host without strike history — a first-class signal,
    #: never a silently-missed 5 s target
    degraded: bool = False
    #: flagged hosts whose RCA was deferred by degraded mode this round
    #: (they still accrue strikes, so they lead the next full round)
    deferred_hosts: List[int] = dataclasses.field(default_factory=list)


class FleetMonitor:
    """Aggregates per-host telemetry windows and runs cluster RCA."""

    def __init__(self, config: Optional[EngineConfig] = None,
                 use_kernels: bool = True,
                 persistent_threshold: int = 3,
                 fast_detect: bool = True,
                 quarantine_enter_frac: float = 0.25,
                 quarantine_exit_frac: float = 0.05,
                 quarantine_enter_rounds: int = 2,
                 quarantine_backoff_init: int = 2,
                 quarantine_backoff_max: int = 16,
                 budget_s: Optional[float] = None,
                 shed_after: int = 2,
                 rearm_after: int = 3,
                 rca_top_k: Optional[int] = None,
                 incremental: bool = True):
        self.cfg = config or EngineConfig()
        self.use_kernels = use_kernels
        self.persistent_threshold = persistent_threshold
        #: cap on Layer-3 RCA candidates per round (None = explain every
        #: flagged host).  Under an incident storm the monitor explains the
        #: ``rca_top_k`` worst flagged hosts (score order, host-id
        #: tie-break) and defers the rest into
        #: ``FleetDiagnosis.deferred_hosts`` — they still accrue strikes,
        #: exactly like deadline-degraded deferral, so persistent
        #: stragglers escalate even while the storm is being triaged.
        #: This is also the fleet-level contract the sharded monitor's
        #: rack->fleet candidate tree bounds its cross-shard traffic with.
        self.rca_top_k = None if rca_top_k is None else int(rca_top_k)
        #: columnar fast path: one streaming-detect dispatch + f32 gather;
        #: False = seed spike-dispatch + f64 detect_rows replay (oracle)
        self.fast_detect = fast_detect
        # incremental O(delta) streaming moments (core/rolling.py): the
        # fast path's baseline moments come from persistent per-(host,
        # block) state instead of an O(rows * bn) direct pass each round.
        # Only engaged on clean on-grid rounds; masked/chaos rounds,
        # reset_host, and checkpoint restore cold-invalidate the affected
        # rows (they rebuild from scratch on the next clean round), and a
        # periodic exact re-anchor bitwise-proves the carried state
        # (``fleet/incremental_parity``).  ``incremental=False`` restores
        # the direct per-round moment pass (the PR 9 behaviour) — the
        # bench's cold baseline.
        self._inc = (rolling.IncrementalMoments(cap_ticks=self.cfg.baseline_n)
                     if (fast_detect and incremental) else None)
        self._strikes: Dict[int, int] = {}
        # telemetry quarantine (hysteresis): a host whose latency-channel
        # invalid fraction exceeds `enter_frac` for `enter_rounds`
        # consecutive rounds is quarantined — its telemetry is the fault,
        # so it must never fire as a straggler.  Re-admission needs
        # `backoff` consecutive clean rounds (invalid fraction at or below
        # `exit_frac`); the backoff doubles on every re-quarantine up to
        # `backoff_max`, so a flapping agent converges to quarantined.
        self.quarantine_enter_frac = float(quarantine_enter_frac)
        self.quarantine_exit_frac = float(quarantine_exit_frac)
        self.quarantine_enter_rounds = int(quarantine_enter_rounds)
        self.quarantine_backoff_init = int(quarantine_backoff_init)
        self.quarantine_backoff_max = int(quarantine_backoff_max)
        self._quarantined: set = set()
        self._bad_streak: Dict[int, int] = {}    # candidate bad rounds
        self._clean_streak: Dict[int, int] = {}  # quarantined clean rounds
        self._quar_backoff: Dict[int, int] = {}  # clean rounds required
        # deadline-aware degraded mode (hysteresis): `shed_after`
        # consecutive rounds over `budget_s` drop the monitor to
        # detect-only — Layer-3 RCA runs only for flagged hosts already
        # carrying strikes, the rest is deferred; `rearm_after`
        # consecutive on-budget rounds re-arm full diagnosis.  budget_s
        # None disables the state machine entirely (every round is full).
        self.budget_s = None if budget_s is None else float(budget_s)
        self.shed_after = int(shed_after)
        self.rearm_after = int(rearm_after)
        self._over_streak = 0
        self._on_streak = 0
        self._degraded = False
        self.shed_rounds = 0       # rounds executed in detect-only mode
        self.deferred_rca = 0      # flagged hosts whose RCA was deferred

    # ------------------------------------------------------------- batched L2
    def host_spike_scores(self, latency_windows: np.ndarray,
                          latency_baselines: np.ndarray) -> np.ndarray:
        """(hosts,) spike scores of each host's latency series.

        latency_windows (hosts, N), baselines (hosts, Nb) — kernel path is
        the batched spike kernel with M=1.
        """
        w = np.asarray(latency_windows, np.float32)[:, None, :]
        b = np.asarray(latency_baselines, np.float32)[:, None, :]
        s = spike_ops.spike_scores(w, b, use_kernel=self.use_kernels)
        return np.asarray(s)[:, 0]

    def batched_correlations(self, latency_windows: np.ndarray,
                             metric_windows: np.ndarray) -> np.ndarray:
        """rho (hosts, metrics, 2K+1) via the Pallas xcorr kernel."""
        return np.asarray(xcorr_ops.lagged_xcorr(
            np.asarray(latency_windows, np.float32),
            np.asarray(metric_windows, np.float32),
            max_lag=self.cfg.max_lag, use_kernel=self.use_kernels))

    # ----------------------------------------------------------- quarantine
    def _update_quarantine(self, bad_frac: np.ndarray,
                           base: int = 0) -> np.ndarray:
        """Advance the per-host quarantine state machine one round.

        ``bad_frac`` (hosts,) is the invalid fraction of each host's
        latency channel over the detection tail.  Returns the (hosts,)
        bool mask of hosts quarantined THIS round.

        ``base`` offsets the state-machine keys: a sharded round advances
        each shard's hosts with ``base=shard_start`` so the per-host
        hysteresis state stays keyed by *absolute* host id.  The machine
        is per-host independent, so advancing shard by shard is the same
        state trajectory as one full-fleet call."""
        H = int(bad_frac.size)
        quar = np.zeros(H, bool)
        for j in range(H):
            h = j + int(base)
            bf = float(bad_frac[j])
            if h in self._quarantined:
                if bf <= self.quarantine_exit_frac:
                    self._clean_streak[h] = self._clean_streak.get(h, 0) + 1
                    need = self._quar_backoff.get(
                        h, self.quarantine_backoff_init)
                    if self._clean_streak[h] >= need:
                        # re-admitted: participates again from this round
                        self._quarantined.discard(h)
                        self._clean_streak.pop(h, None)
                        self._bad_streak.pop(h, None)
                        continue
                else:
                    self._clean_streak[h] = 0
                quar[j] = True
            elif bf > self.quarantine_enter_frac:
                self._bad_streak[h] = self._bad_streak.get(h, 0) + 1
                if self._bad_streak[h] >= self.quarantine_enter_rounds:
                    self._quarantined.add(h)
                    self._clean_streak[h] = 0
                    prev = self._quar_backoff.get(h)
                    self._quar_backoff[h] = (
                        self.quarantine_backoff_init if prev is None
                        else min(prev * 2, self.quarantine_backoff_max))
                    quar[j] = True
            else:
                self._bad_streak.pop(h, None)
        return quar

    # -------------------------------------------------------- survivability
    @property
    def degraded(self) -> bool:
        """True while the deadline hysteresis holds the monitor in
        detect-only mode."""
        return self._degraded

    def reset_host(self, host: int) -> None:
        """Forget one host's strike/quarantine history.

        Called when the host's telemetry agent is replaced or restarted: a
        fresh probe is not a relapsing probe, so its quarantine re-entry
        backoff re-bases to the initial value instead of doubling from the
        old agent's record, and stale strikes cannot escalate the new
        agent's first flag straight to EXCLUDE_AND_RESCALE."""
        h = int(host)
        self._strikes.pop(h, None)
        self._quarantined.discard(h)
        self._bad_streak.pop(h, None)
        self._clean_streak.pop(h, None)
        self._quar_backoff.pop(h, None)
        if self._inc is not None:
            # the replacement agent's ring shares no history with the old
            # one — its cached moment blocks are another process's data
            self._inc.invalidate([h])

    def _update_budget(self, round_cost_s: float) -> None:
        """Advance the deadline hysteresis one round."""
        if self.budget_s is None:
            return
        if round_cost_s > self.budget_s:
            self._over_streak += 1
            self._on_streak = 0
            if not self._degraded and self._over_streak >= self.shed_after:
                self._degraded = True
        else:
            self._on_streak += 1
            self._over_streak = 0
            if self._degraded and self._on_streak >= self.rearm_after:
                self._degraded = False
                self._on_streak = 0

    def state_dict(self) -> Dict[str, object]:
        """All mutable diagnosis state, JSON-serializable (checkpointing).

        Keys of the per-host dicts are stringified so the payload survives
        a JSON round trip; :meth:`load_state_dict` converts them back."""
        return {
            "strikes": {str(k): int(v) for k, v in self._strikes.items()},
            "quarantined": sorted(int(h) for h in self._quarantined),
            "bad_streak": {str(k): int(v)
                           for k, v in self._bad_streak.items()},
            "clean_streak": {str(k): int(v)
                             for k, v in self._clean_streak.items()},
            "quar_backoff": {str(k): int(v)
                             for k, v in self._quar_backoff.items()},
            "over_streak": int(self._over_streak),
            "on_streak": int(self._on_streak),
            "degraded": bool(self._degraded),
            "shed_rounds": int(self.shed_rounds),
            "deferred_rca": int(self.deferred_rca),
        }

    def load_state_dict(self, d: Dict[str, object]) -> None:
        """Restore :meth:`state_dict` output — full replacement, never a
        merge.  Every field is parsed before any is assigned, so a
        malformed payload raises without leaving a half-restored
        monitor."""
        strikes = {int(k): int(v) for k, v in d["strikes"].items()}
        quarantined = {int(h) for h in d["quarantined"]}
        bad = {int(k): int(v) for k, v in d["bad_streak"].items()}
        clean = {int(k): int(v) for k, v in d["clean_streak"].items()}
        backoff = {int(k): int(v) for k, v in d["quar_backoff"].items()}
        over, on = int(d["over_streak"]), int(d["on_streak"])
        degraded = bool(d["degraded"])
        shed, deferred = int(d["shed_rounds"]), int(d["deferred_rca"])
        self._strikes = strikes
        self._quarantined = quarantined
        self._bad_streak = bad
        self._clean_streak = clean
        self._quar_backoff = backoff
        self._over_streak = over
        self._on_streak = on
        self._degraded = degraded
        self.shed_rounds = shed
        self.deferred_rca = deferred
        if self._inc is not None:
            # incremental moments are deliberately NOT serialized
            # (checkpoint bytes stay flat); a restored monitor starts
            # cold and its first clean round re-anchors from scratch
            self._inc.invalidate_all()

    # ------------------------------------------------------------- fleet RCA
    def diagnose_fleet(self, ts: np.ndarray, host_data: np.ndarray,
                       channels: Sequence[str],
                       valid: Optional[np.ndarray] = None,
                       extra_cost_s: float = 0.0) -> FleetDiagnosis:
        """host_data: (hosts, C, T) aligned windows; finds every straggler
        above threshold and explains all of them in one batched dispatch.

        A window too short to leave ``MIN_BASELINE_N`` baseline samples
        after clamping returns a quiet verdict carrying a zero-valued
        ``short_baseline_skip`` entry in ``stage_seconds`` — detection on a
        sigma-floored micro-baseline would flag quiet hosts.

        ``valid`` (hosts, C, T) bool marks per-cell telemetry validity
        (chaos hardening).  Invalid latency cells are excluded from
        detection via the masked oracle (never enter baselines, never
        fire); invalid evidence cells are forward-filled before the RCA
        gather.  Hosts whose latency channel stays persistently invalid
        are *quarantined* by a hysteresis state machine: their telemetry
        is the fault, so they are suppressed from straggler detection and
        reported in ``FleetDiagnosis.quarantined`` with mitigation
        ``RESTART_TELEMETRY`` — a telemetry fault must never surface as a
        GPU/host-interference verdict.  An all-true (or absent) mask
        leaves the clean path byte-identical.

        ``extra_cost_s`` is added to the measured round cost before the
        deadline-budget hysteresis update (a harness models external load
        with it; a deployment passes assembly/IO time).  While degraded,
        the round is detect-only: Layer-3 RCA runs solely for flagged
        hosts already carrying strikes, every other flagged host is
        reported in ``deferred_hosts`` (still accruing a strike, so it
        leads the RCA queue once re-armed or escalates to
        EXCLUDE_AND_RESCALE on persistence).  With ``rca_top_k`` set, at
        most that many hosts get Layer-3 RCA per round (worst first) and
        the overflow is deferred the same way.

        The round is assembled from overridable stages —
        :meth:`_detect_round` (Layer 2 + quarantine over the latency
        tail), an evidence-gather callback, and :meth:`_finish_round`
        (flag ordering, strike/mitigation lifecycle, Layer-3 RCA, budget
        hysteresis) — so the sharded monitor
        (:class:`repro.monitor.shard.ShardedFleetMonitor`) can run
        detection and evidence extraction per shard while reusing the
        exact fleet-level verdict logic, keeping the two byte-identical
        by construction."""
        hosts, C, T = host_data.shape
        with span("monitor.round", hosts=hosts):
            li = list(channels).index(self.cfg.latency_metric)
            vfull = None
            if valid is not None:
                with span("monitor.validity", cells=host_data.size):
                    v = np.asarray(valid, bool)
                    if v.shape != host_data.shape:
                        raise ValueError(
                            f"valid {v.shape} vs data {host_data.shape}")
                    if not v.all():
                        vfull = v
            wn, bn = self.cfg.window_n, self.cfg.baseline_n
            wn = min(wn, T // 2)
            bn = min(bn, T - wn)
            if bn < MIN_BASELINE_N:
                return self._quiet_round(hosts, extra_cost_s)
            tick_end = self._tick_end(ts, T)
            stage: Dict[str, float] = {}
            with stage_span(stage, "detect", "monitor.detect", rows=hosts):
                scores, cand, onset_rel, qhosts = self._detect_round(
                    host_data, vfull, li, T, wn, bn, tick_end=tick_end)

            def evidence_for(geom: "EvidenceGeometry",
                             rca_hosts: np.ndarray) -> np.ndarray:
                return self._gather_evidence(host_data, rca_hosts, geom,
                                             vfull)

            with span("monitor.finish", flagged=cand.size):
                return self._finish_round(ts, channels, li, T, wn, bn,
                                          scores, cand, onset_rel, qhosts,
                                          stage, extra_cost_s, evidence_for)

    def _quiet_round(self, hosts: int, extra_cost_s: float) -> FleetDiagnosis:
        """Short-snapshot quiet verdict (baseline too thin to trust).

        The clamped baseline cannot estimate ambient statistics, and the
        sigma-floored z-score would flag perfectly quiet hosts — so the
        round reports nothing, with an explicit ``short_baseline_skip``
        stage marker instead of spurious stragglers.  A quiet round clears
        strike history exactly like a quiet full window (no host was
        flagged THIS round)."""
        self._strikes.clear()
        self._update_budget(extra_cost_s)
        return FleetDiagnosis(
            straggler_host=0, straggler_score=0.0, diagnosis=None,
            mitigation=Mitigation.NONE,
            per_host_scores=np.zeros(hosts, np.float32),
            stage_seconds={"detect": 0.0, "short_baseline_skip": 0.0},
            degraded=self._degraded)

    def _tick_end(self, ts: np.ndarray, T: int) -> Optional[int]:
        """Exclusive absolute tick index of the round's newest sample.

        The incremental moment cache is keyed to the absolute 100 Hz tick
        grid, so the round's timestamps must sit on it: the newest sample
        must round cleanly to a tick index and the window span must equal
        ``T - 1`` tick periods (no dropped ticks, no clock jumps).  Any
        off-grid round returns None — the detect stage then takes the
        direct moment pass and the cache is left untouched, so irregular
        wall clocks degrade to PR 9 behaviour instead of mis-anchoring.
        """
        if self._inc is None or len(ts) < 2:
            return None
        rate = self.cfg.rate_hz
        e_f = float(ts[-1]) * rate
        e = round(e_f)
        span = (float(ts[-1]) - float(ts[0])) * rate
        if abs(e_f - e) > 0.25 or abs(span - (T - 1)) > 0.25:
            return None
        return int(e) + 1

    def incremental_stats(self) -> Optional[dict]:
        """Counters of the incremental moment state (None when the
        direct moment pass is in use): rounds, re-anchors, the parity
        bit, and cache traffic — surfaced for ops dashboards and the
        ``fleet/incremental_*`` bench rows."""
        return None if self._inc is None else self._inc.stats()

    def _detect_round(self, host_data: np.ndarray,
                      vfull: Optional[np.ndarray], li: int,
                      T: int, wn: int, bn: int,
                      force_oracle: bool = False, device=None,
                      base: int = 0,
                      quar: Optional[np.ndarray] = None,
                      tick_end: Optional[int] = None,
                      launched=None, in_flight: int = 1,
                      ) -> Tuple[np.ndarray, np.ndarray, np.ndarray,
                                 np.ndarray]:
        """Layer-2 detection + telemetry quarantine over the latency tail
        of ``host_data``: :meth:`_detect_launch`, then the half after the
        sweep — collect, zero the quarantined hosts, flag.

        Returns ``(scores, cand, onset_rel, qhosts)``: per-host spike
        scores (quarantined hosts zeroed), the unordered flagged host
        indices, their onsets relative to the detection window, and the
        hosts quarantined this round — all indexed relative to
        ``host_data`` (the sharded caller offsets them by its shard
        base).

        ``launched`` is what :meth:`_detect_launch` returned for this
        slab, when the caller launched it earlier (the sharded monitor
        launches the next shards while this one's sweep runs); only the
        second half then runs, and ``in_flight`` — launched, unfinished
        detects when its pull begins — is recorded on the pull's span."""
        if launched is None:
            launched = self._detect_launch(
                host_data, vfull, li, T, wn, bn, force_oracle=force_oracle,
                device=device, base=base, quar=quar, tick_end=tick_end)
        pending, qhosts = launched
        fire, scores, onset_all = pending.collect(in_flight)
        if qhosts.size:
            fire[qhosts] = False
            scores[qhosts] = 0.0
        cand = np.flatnonzero(fire)
        return scores, cand, onset_all[cand], qhosts

    def _detect_launch(self, host_data: np.ndarray,
                       vfull: Optional[np.ndarray], li: int,
                       T: int, wn: int, bn: int,
                       force_oracle: bool = False, device=None,
                       base: int = 0,
                       quar: Optional[np.ndarray] = None,
                       tick_end: Optional[int] = None):
        """The half of :meth:`_detect_round` before the sweep's results
        come back: the quarantine update, the incremental moments and the
        detect launch.  Returns ``(pending, qhosts)``, the ``launched``
        argument of :meth:`_detect_round`; ``pending.collect()`` gives
        per-host ``(fire, scores, onset)``.

        The shard parameters keep a per-shard invocation byte-identical
        to the corresponding rows of one full-slab call: ``base`` keys
        the quarantine state machine by absolute host id,
        ``force_oracle`` routes a clean shard through the masked f64
        oracle when some OTHER shard saw corruption (a single-slab round
        with any invalid cell takes the oracle for every host),
        ``device`` pins the detect dispatch to the shard's mesh device,
        and ``quar`` substitutes precomputed quarantine decisions so a
        shard re-visited for oracle forcing does not advance the
        hysteresis twice.

        ``tick_end`` (from :meth:`_tick_end`) anchors the incremental
        moment cache to the absolute tick grid.  On a clean round the
        baseline moments come from :class:`~repro.core.rolling.
        IncrementalMoments` at O(delta); a masked/forced-oracle round
        routes through the masked f64 oracle instead *and invalidates*
        the visited rows' incremental state (their slab may carry
        masked/zeroed cells, so carried blocks are no longer trusted) —
        which also means an oracle re-visit of a shard never advances
        the moment state twice."""
        hosts = host_data.shape[0]
        lat = host_data[:, li, :]
        # telemetry quarantine: invalid fraction of the latency channel
        # over the detection tail drives the hysteresis state machine; the
        # update runs every full round (clean rounds advance re-admission)
        lvt = None
        if vfull is not None:
            lvt = np.ascontiguousarray(vfull[:, li, T - wn - bn:T])
            if lvt.all():
                lvt = None
        bad_frac = (np.zeros(hosts) if lvt is None
                    else 1.0 - lvt.mean(axis=1))
        if quar is None:
            with span("detect.quarantine", hosts=hosts):
                quar = self._update_quarantine(bad_frac, base=base)
        qhosts = np.flatnonzero(quar)
        # persistence gate, the scalar spike.detect rule batched over hosts:
        # a host is a straggler only if `persistence` of its window sits
        # above mu + thr*sigma — bare max-z over 500 correlated ambient
        # samples trips routinely.  The gate also yields each survivor's
        # onset estimate for Layer 3.
        if self.fast_detect or lvt is not None or force_oracle:
            # one streaming-detect dispatch over the trailing slab view:
            # score + gate + onset per host, one host->device copy, no
            # candidate re-slice.  A masked round routes through this call
            # on BOTH detect paths — the mask branch IS the f64 oracle, so
            # fast and oracle stay trivially byte-identical under chaos.
            moments = None
            if self._inc is not None:
                if lvt is None and not force_oracle and tick_end is not None:
                    with span("detect.moments", rows=hosts) as sp:
                        moments = self._inc.moments(
                            lat[:, T - wn - bn:T], tick_end, wn, bn,
                            base=base)
                        sp.set_metadata(
                            blocks_computed=self._inc.last_round_computed,
                            rebuilt_rows=self._inc.last_round_rebuilt_rows)
                else:
                    self._inc.invalidate(np.arange(base, base + hosts))
            pending = detect_ops.detect_hosts_slab_launch(
                lat[:, T - wn - bn:T], wn, bn,
                self.cfg.threshold, self.cfg.persistence,
                use_kernel=self.use_kernels, valid=lvt,
                force_oracle=force_oracle, device=device, moments=moments)
            return pending, qhosts
        scores = self.host_spike_scores(lat[:, T - wn:],
                                        lat[:, T - wn - bn:T - wn])
        if qhosts.size:
            scores = np.array(scores)   # kernel output may be readonly
            scores[qhosts] = 0.0
        cand = np.flatnonzero(scores > self.cfg.threshold)
        fire = np.zeros(hosts, bool)
        onset_all = np.full(hosts, -1, np.intp)
        if cand.size:
            latc = np.asarray(lat[cand], dtype=np.float64)
            keep, _, onset = detect_rows(
                latc[:, T - wn:], latc[:, T - wn - bn:T - wn],
                self.cfg.threshold, self.cfg.persistence)
            fire[cand[keep]] = True
            onset_all[cand[keep]] = onset[keep]
        return Resolved((fire, scores, onset_all)), qhosts

    def _rca_selection(self, flagged: np.ndarray, onset_rel: np.ndarray,
                       ) -> Tuple[np.ndarray, np.ndarray, List[int]]:
        """Which flagged hosts get Layer-3 RCA this round, and which defer.

        ``flagged`` must already be in fleet RCA order (score-descending,
        host-id tie-break).  Applies the degraded-mode strike priority
        (detect-only rounds explain only hosts with strike history) and
        the ``rca_top_k`` storm cap; returns ``(rca_hosts, rca_onsets,
        deferred)``.  Pure — no monitor state is touched — so the sharded
        monitor can run the same selection per shard/rack to decide which
        evidence blocks to ship, guaranteeing every host the fleet level
        will RCA has its evidence on hand (the fleet's selection over a
        superset picks a subset of each part's local selection)."""
        rca_hosts, rca_onsets = flagged, onset_rel
        deferred: List[int] = []
        if self._degraded:
            # detect-only round: RCA only for hosts whose flag is
            # *persistent* (strike history) — everything else is
            # deferred, explicitly, instead of silently late
            pri = np.fromiter(
                (self._strikes.get(int(h), 0) > 0 for h in flagged),
                dtype=bool, count=flagged.size)
            rca_hosts, rca_onsets = flagged[pri], onset_rel[pri]
            deferred = [int(h) for h in flagged[~pri]]
        if self.rca_top_k is not None and rca_hosts.size > self.rca_top_k:
            # incident-storm triage: explain the worst ``rca_top_k``
            # hosts this round, defer the rest explicitly (they keep
            # accruing strikes, so persistence still escalates)
            k = self.rca_top_k
            deferred += [int(h) for h in rca_hosts[k:]]
            rca_hosts, rca_onsets = rca_hosts[:k], rca_onsets[:k]
        return rca_hosts, rca_onsets, deferred

    def _finish_round(self, ts: np.ndarray, channels: Sequence[str],
                      li: int, T: int, wn: int, bn: int,
                      scores: np.ndarray, cand: np.ndarray,
                      onset_rel: np.ndarray, qhosts: np.ndarray,
                      stage: Dict[str, float], extra_cost_s: float,
                      evidence_for) -> FleetDiagnosis:
        """Fleet-level verdict assembly shared by every execution layout.

        Orders the flagged hosts (score-descending, host-id tie-break —
        deterministic so sharded and single-slab rounds agree), applies
        the degraded-mode and ``rca_top_k`` RCA deferrals, runs batched
        Layer-3 RCA through ``evidence_for`` (a callback returning the
        gathered evidence slab for exactly the RCA'd hosts, in order —
        the single-slab path slices ``host_data``, the sharded path
        reassembles blocks shipped from shards), advances the
        strike/mitigation lifecycle and the deadline-budget hysteresis,
        and returns the round's :class:`FleetDiagnosis`."""
        # deterministic flag order: score-descending with ascending host id
        # on ties (``cand`` is ascending) — a plain argsort would order
        # tied scores arbitrarily and split the sharded/single-slab paths
        order = np.argsort(-scores[cand], kind="stable")
        flagged, onset_rel = cand[order], onset_rel[order]
        diagnoses: Dict[int, Diagnosis] = {}
        causes: Dict[int, List[CauseClass]] = {}
        mitigations: Dict[int, Mitigation] = {}
        degraded = self._degraded
        deferred: List[int] = []
        if flagged.size:
            rca_hosts, rca_onsets, deferred = self._rca_selection(
                flagged, onset_rel)
            self.deferred_rca += len(deferred)
            if rca_hosts.size:
                geom = self._evidence_geometry(channels, li, T, wn, bn)
                if geom is not None:
                    with stage_span(stage, "gather", "monitor.gather",
                                    hosts=rca_hosts.size) as sp:
                        X = evidence_for(geom, rca_hosts)
                        sp.set_metadata(bytes=X.nbytes)
                    diagnoses, causes = self._rca_from_evidence(
                        ts, X, geom, rca_hosts, (T - wn) + rca_onsets,
                        scores, stage)
        with span("finish.lifecycle", flagged=flagged.size):
            # strike lifecycle: a host that recovered (not flagged THIS
            # round) loses its strike history immediately, even while
            # other hosts stay flagged — otherwise churn leaves stale
            # counts behind forever and the dict grows unbounded with
            # fleet size.  The RCA selection above reads the strikes of
            # flagged hosts only, which this purge never touches.
            flagged_set = {int(h) for h in flagged}
            for h in [h for h in self._strikes if h not in flagged_set]:
                del self._strikes[h]
            deferred_set = set(deferred)
            for h in flagged:
                h = int(h)
                d = diagnoses.get(h)
                if d is None and h not in deferred_set:
                    # no evidence channels: verdict-less host
                    mitigations[h] = Mitigation.NONE
                    continue
                self._strikes[h] = self._strikes.get(h, 0) + 1
                if self._strikes[h] >= self.persistent_threshold:
                    mitigations[h] = Mitigation.EXCLUDE_AND_RESCALE
                elif d is None:    # deferred: verdict comes once re-armed
                    mitigations[h] = Mitigation.NONE
                else:
                    mitigations[h] = VERDICT_TO_MITIGATION[d.top_cause]
            # quarantined hosts carry the telemetry-fault verdict: fire was
            # suppressed and score zeroed above, so they can neither lead
            # the flagged list nor accrue strikes — the only actionable
            # output is "restart that host's telemetry agent"
            for h in qhosts:
                mitigations[int(h)] = Mitigation.RESTART_TELEMETRY
        # the worst *persistent* host; bare arg-max only as the quiet-fleet
        # readout (a transient max-z glitch must not name a straggler)
        straggler = int(flagged[0]) if flagged.size else int(np.argmax(scores))
        if degraded:
            self.shed_rounds += 1
        self._update_budget(sum(stage.values()) + float(extra_cost_s))
        return FleetDiagnosis(
            straggler_host=straggler,
            straggler_score=float(scores[straggler]),
            diagnosis=diagnoses.get(straggler),
            mitigation=mitigations.get(straggler, Mitigation.NONE),
            per_host_scores=scores,
            flagged_hosts=[int(h) for h in flagged],
            diagnoses=diagnoses, mitigations=mitigations, causes=causes,
            stage_seconds=stage,
            quarantined=[int(h) for h in qhosts],
            degraded=degraded,
            deferred_hosts=deferred)

    # ----------------------------------------------------- batched Layer 3+4
    def _evidence_geometry(self, channels: Sequence[str], li: int,
                           T: int, wn: int, bn: int,
                           ) -> "Optional[EvidenceGeometry]":
        """Resolve the shared RCA evidence layout for this round.

        All flagged hosts share the trailing RCA window [T-rn, T): an onset
        is only ever *observed* inside the trailing detection window, so
        reaching ``pre_onset_s`` before it always saturates at the snapshot
        edge — one contiguous slice covers every host, with a common
        baseline window preceding it.  Returns None when the channel set
        carries no evidence channels (verdict-less rounds)."""
        cfg = self.cfg
        rate = cfg.rate_hz
        pre_n = int(cfg.pre_onset_s * rate)
        rca_n = int(cfg.rca_extra_s * rate)
        rn = int(min(T, pre_n + wn + rca_n))
        nb = int(min(bn, T - rn))
        if nb < MIN_BASELINE_N:
            nb = 0
        names, idx, orient = evidence_layout(
            tuple(channels), cfg.latency_metric)
        if not names:
            return None
        return EvidenceGeometry(
            names=tuple(names), orient=orient,
            rows=np.concatenate(([li], idx)),
            cols=np.arange(T - rn - nb, T), rn=rn, nb=nb)

    def _gather_evidence(self, host_data: np.ndarray, flagged: np.ndarray,
                         geom: "EvidenceGeometry",
                         valid: Optional[np.ndarray] = None) -> np.ndarray:
        """Stage the (len(flagged), 1 + M, nb + rn) evidence slab.

        Row 0 is the latency channel, rows 1.. the evidence channels, the
        column span ``geom.cols`` the shared baseline + RCA window.  This
        is the per-host-independent half of Layer 3 — the sharded monitor
        runs it on each shard and ships only these blocks (its top-K
        candidates' evidence) across the shard boundary, never the raw
        (hosts, C, T) telemetry.

        The columnar mode gathers straight to f32 (the fused kernel's
        input dtype) — no f64 round-trip of the evidence slab; the oracle
        path keeps the seed's f64 gather.  Invalid evidence cells
        (crashed collector, frozen channel) must not skew orientation
        means or correlations: they are NaN'd out, then the last valid
        reading is carried forward — degraded evidence, never fabricated
        spikes."""
        gather_dtype = np.float32 if self.fast_detect else np.float64
        sel = np.ix_(flagged, geom.rows, geom.cols)
        X = host_data[sel].astype(gather_dtype)     # (H, 1+M, nb+rn)
        if valid is not None:
            X[~valid[sel]] = np.nan
        return sanitize_mod.forward_fill(X)

    def _rca_from_evidence(self, ts: np.ndarray, X: np.ndarray,
                           geom: "EvidenceGeometry", flagged: np.ndarray,
                           onset_idx: np.ndarray, scores: np.ndarray,
                           stage: Dict[str, float],
                           ) -> "Tuple[Dict[int, Diagnosis], Dict[int, List[CauseClass]]]":
        """Explain every RCA'd host with one fused-kernel dispatch.

        ``X`` is the gathered evidence slab (:meth:`_gather_evidence`, in
        ``flagged`` order), ``onset_idx`` each host's absolute onset
        sample (from the detection gate's stats) — it only timestamps the
        events; for an anomaly older than the window it clamps to the
        window start, the best a streaming trailing-window view can
        report.  Returns ``(diagnoses, causes)``: per host the Diagnosis
        plus its ordered verdict-cause list (primary first; co-causes
        appended only with ``cfg.max_hypotheses > 1`` — see
        :class:`FleetDiagnosis`).

        This half of Layer 3 is deliberately *cross-host coupled* (the
        orientation baseline slice depends on the minimum onset over all
        RCA'd hosts) and therefore always runs at fleet level, on the
        gathered candidates — never per shard."""
        cfg = self.cfg
        rate = cfg.rate_hz
        nb, rn = geom.nb, geom.rn
        names = geom.names
        names_pos = {n: m for m, n in enumerate(names)}
        T = int(geom.cols[-1]) + 1
        with stage_span(stage, "gather", "rca.orient"):
            L_win = X[:, 0, nb:]                            # (H, rn)
            Xm = X[:, 1:, :]                                # (H, M, nb+rn)
            # orientation about the baseline-region mean, batched over
            # hosts — same slice/orientation policy as engine._diagnose
            # (shared helpers)
            head = int(np.min(onset_idx) - (T - rn))
            b_sl = pick_baseline_slice(nb, head, nb + rn)
            XO = orient_about_baseline(Xm, geom.orient, b_sl)
            W = XO[:, :, nb:]                               # (H, M, rn)
            Bm = XO[:, :, b_sl]                             # (H, M, nb')
            # multi-hypothesis co-cause corroboration over the SAME
            # gathered slab: per cause, does some symptom channel show a
            # two-sided raw-z deviation at/above its floor (reconcile's
            # corroboration test, vectorized over hosts)?  Computed in f64
            # on the raw (unoriented, forward-filled) evidence so the fast
            # f32 gather and the f64 oracle agree on every verdict-cause
            # list.
            sym_ok: Dict[CauseClass, np.ndarray] = {}
            if cfg.max_hypotheses > 1:
                for cause, chans in symptom_table().items():
                    ok = np.zeros(flagged.size, bool)
                    for name, floor in chans:
                        m = names_pos.get(name)
                        if m is None:
                            continue
                        seg = np.asarray(Xm[:, m, :], np.float64)
                        B, Wr = seg[:, b_sl], seg[:, nb:]
                        if B.shape[1] == 0 or Wr.shape[1] == 0:
                            continue
                        mb = B.mean(axis=1)
                        sd = np.maximum(B.std(axis=1),
                                        np.maximum(1e-3 * np.abs(mb), 1e-9))
                        ok |= np.abs(Wr.mean(axis=1) - mb) / sd >= floor
                    sym_ok[cause] = ok

        # one fused dispatch: spike scores + max-|rho| + arg-max lag
        with stage_span(stage, "kernel", "rca.kernel", batch=flagged.size):
            s, c, lags = fused_ops.fused_rca_max(
                np.asarray(L_win, np.float32), np.asarray(W, np.float32),
                np.asarray(Bm, np.float32), max_lag=cfg.max_lag,
                use_kernel=self.use_kernels)
            s, c, lags = np.asarray(s), np.asarray(c), np.asarray(lags)

        # "rank" is the confidence fusion only; the Diagnosis-object
        # assembly below is its own disjoint stage
        with stage_span(stage, "rank", "rca.rank"):
            ranked_all = conf_mod.rank_causes_batch(
                names, s, c, lags / rate, cfg.alpha, details=False)
            # operators drill into the worst host (flagged[0]): full
            # per-metric detail for it only, via the same ranker
            ranked_all[0] = conf_mod.rank_causes_batch(
                names, s[:1], c[:1], lags[:1] / rate, cfg.alpha,
                details=True)[0]
        out: Dict[int, Diagnosis] = {}
        causes: Dict[int, List[CauseClass]] = {}
        with stage_span(stage, "assemble", "rca.assemble"):
            now = float(ts[T - 1])
            # Layer-3/4 compute cost, shared by the whole batch (paper's
            # Time-to-RCA includes analysis compute)
            analysis = stage["kernel"] + stage["rank"]
            for j, h in enumerate(flagged):
                h = int(h)
                ranked, per_metric = ranked_all[j]
                ev = SpikeEvent(t_onset=float(ts[int(onset_idx[j])]),
                                t_detect=now, score=float(scores[h]),
                                metric=cfg.latency_metric)
                out[h] = Diagnosis(event=ev, ranked=ranked,
                                   per_metric=per_metric,
                                   t_rca=now + analysis,
                                   analysis_seconds=analysis, t_ready=now)
                cl = [ranked[0].cause] if ranked else []
                if ranked and cfg.max_hypotheses > 1:
                    # co-causes: corroborated runners within their
                    # per-cause confidence gap of the primary, rank order
                    # preserved
                    top = ranked[0].confidence
                    for rc in ranked[1:]:
                        ok = sym_ok.get(rc.cause)
                        if ok is None or not bool(ok[j]):
                            continue
                        if top - rc.confidence > CO_GAP.get(rc.cause, 0.0):
                            continue
                        cl.append(rc.cause)
                causes[h] = cl
        return out, causes


@dataclasses.dataclass(frozen=True)
class EvidenceGeometry:
    """The round-shared RCA evidence layout (:meth:`FleetMonitor.
    _evidence_geometry`): which slab rows and columns every RCA'd host's
    evidence block is cut from.  Shipping this to shards instead of
    recomputing it there keeps the shard-side gather and the single-slab
    gather trivially identical."""

    #: evidence channel names, fused-kernel metric order
    names: Tuple[str, ...]
    #: per-metric orientation signs (``engine.evidence_layout``)
    orient: np.ndarray
    #: slab row indices to gather: ``[latency, *evidence_channels]``
    rows: np.ndarray
    #: slab column indices: the shared baseline + RCA window, contiguous
    cols: np.ndarray
    #: RCA window length in samples
    rn: int
    #: baseline samples preceding the RCA window (0 = too thin, skipped)
    nb: int
