"""Sharded fleet monitoring: the 10k+-host scale-out of ``diagnose_fleet``.

The single-slab :class:`~repro.monitor.fleet.FleetMonitor` stages the
whole fleet as one (hosts, C, T) array — at 64k hosts that slab alone is
tens of gigabytes, and one detect dispatch on one device is the scaling
wall the paper's multi-node extension (§5.1) runs into first.  This
module splits the fleet into contiguous host shards (:class:`ShardPlan`),
runs Layer-2 detection per shard through the one-dispatch sweep core
(whose cost does not scale with the flagged fraction), and merges shard
results through a two-level rack → fleet candidate tree that carries
scalars only:

  shard   detect + quarantine on its own (H_s, C, T) slab, on its own
          mesh device (``parallel.fleet``); ships a
          :class:`ShardCandidates` — flagged host ids, scores, onsets,
          quarantined ids — never the raw slab;
  rack    merges its member shards' candidate lists;
  fleet   concatenates rack candidates and runs the unchanged
          fleet-level verdict logic (:meth:`FleetMonitor._finish_round`)
          over them.  Once that logic has picked the round's RCA set, the
          fleet fetches the evidence blocks of exactly those hosts from
          their owning shards (one provider call per owning shard), so
          a storm's flagged-but-deferred hosts cost no gather.

Byte-exactness is by construction, not by tolerance:

  * detection is per-host independent, and the shard dispatch is the
    same detect launch the single-slab path makes — a shard's rows see
    bit-identical inputs;
  * a corrupt cell ANYWHERE routes every shard through the masked f64
    oracle (``force_oracle``), exactly as one full-slab call with any
    invalid cell takes the oracle for every host — the fast/oracle split
    can never follow shard boundaries;
  * candidate ordering is a total order (score descending, host id
    ascending on ties, ``kind="stable"``), and the fleet-level selection
    runs over every shard's flagged hosts, so it picks exactly the hosts
    one full-slab round would;
  * the evidence gather is per-host independent (an f32 cast and a
    per-row forward fill), so a block fetched from the owning shard's
    slab equals the single-slab gather's row; the cross-host-coupled
    half of Layer 3 (the orientation baseline slice depends on the
    *minimum onset over all RCA'd hosts*) runs once at fleet level on
    the fetched blocks, in RCA order.

``verdict_fingerprint`` canonicalizes the deterministic fields of a
:class:`~repro.monitor.fleet.FleetDiagnosis` (everything except wall-time
measurements) so tests, the bench, and the CI parity gate share one
definition of "byte-exact".
"""
from __future__ import annotations

import collections
import dataclasses
import hashlib
from typing import Callable, Deque, Dict, List, Optional, Sequence, Tuple

import numpy as np

from repro.core.engine import MIN_BASELINE_N
from repro.core.spans import span
from repro.core.spans import stage as stage_span
from repro.kernels import tuning
from repro.monitor.fleet import FleetDiagnosis, FleetMonitor

__all__ = [
    "ShardPlan", "ShardCandidates", "ShardTraffic", "ShardedFleetMonitor",
    "verdict_fingerprint",
]

#: bytes per candidate scalar record crossing the tree: host id (int64),
#: score (f64), onset (int64)
_CAND_RECORD_BYTES = 24


class _ShortBaseline(Exception):
    """Internal: first shard's window is too short for a trusted baseline
    (the round refuses before any shard state advances)."""


@dataclasses.dataclass(frozen=True)
class ShardPlan:
    """How the fleet's host axis is cut into shards and racks.

    ``bounds[s] = (start, end)`` is shard ``s``'s contiguous, half-open
    absolute host range; shards tile ``[0, hosts)`` in order with no gaps
    (ragged sizes allowed — the last shard of a fleet that does not
    divide evenly is simply shorter).  ``racks[r]`` lists the shard
    indices reduced together at the rack level; racks partition the
    shards.  The plan is part of the monitor's checkpointed identity:
    restore validates it, because per-shard execution order is what makes
    the quarantine/strike maps partitionable.
    """

    #: per-shard (start, end) absolute host ranges, contiguous ascending
    bounds: Tuple[Tuple[int, int], ...]
    #: rack -> member shard indices (a partition of ``range(n_shards)``)
    racks: Tuple[Tuple[int, ...], ...]

    def __post_init__(self) -> None:
        if not self.bounds:
            raise ValueError("ShardPlan needs at least one shard")
        pos = 0
        for s, (a, b) in enumerate(self.bounds):
            if a != pos or b <= a:
                raise ValueError(
                    f"shard {s} bounds ({a}, {b}) must tile [0, hosts) "
                    f"contiguously (expected start {pos})")
            pos = b
        seen = [s for rack in self.racks for s in rack]
        if sorted(seen) != list(range(len(self.bounds))):
            raise ValueError(f"racks {self.racks} must partition "
                             f"{len(self.bounds)} shards")

    @property
    def hosts(self) -> int:
        """Total fleet size the plan covers."""
        return self.bounds[-1][1]

    @property
    def n_shards(self) -> int:
        """Number of shard slabs."""
        return len(self.bounds)

    @property
    def n_racks(self) -> int:
        """Number of rack-level reduce groups."""
        return len(self.racks)

    @classmethod
    def for_fleet(cls, hosts: int, shard_hosts: Optional[int] = None,
                  rack_shards: Optional[int] = None) -> "ShardPlan":
        """Even plan: ``shard_hosts`` hosts per shard (last shard ragged),
        ``rack_shards`` shards per rack — both defaulting to the
        ``REPRO_SHARD_HOSTS`` / ``REPRO_RACK_SHARDS`` tuning knobs."""
        hosts = int(hosts)
        if hosts <= 0:
            raise ValueError(f"hosts must be positive, got {hosts}")
        sh = tuning.shard_hosts(shard_hosts)
        bounds = tuple((a, min(a + sh, hosts))
                       for a in range(0, hosts, sh))
        return cls.from_bounds(bounds, rack_shards)

    @classmethod
    def from_bounds(cls, bounds: Sequence[Tuple[int, int]],
                    rack_shards: Optional[int] = None) -> "ShardPlan":
        """Plan from explicit (possibly ragged) shard bounds, racks cut
        every ``rack_shards`` shards."""
        bounds = tuple((int(a), int(b)) for a, b in bounds)
        rk = tuning.rack_shards(rack_shards)
        racks = tuple(tuple(range(i, min(i + rk, len(bounds))))
                      for i in range(0, len(bounds), rk))
        return cls(bounds=bounds, racks=racks)

    def shard_of(self, host: int) -> int:
        """Index of the shard owning an absolute host id."""
        h = int(host)
        for s, (a, b) in enumerate(self.bounds):
            if a <= h < b:
                return s
        raise ValueError(f"host {h} outside plan [0, {self.hosts})")

    def to_dict(self) -> Dict[str, object]:
        """JSON-serializable form (checkpoint payload)."""
        return {"bounds": [[int(a), int(b)] for a, b in self.bounds],
                "racks": [[int(s) for s in rack] for rack in self.racks]}

    @classmethod
    def from_dict(cls, d: Dict[str, object]) -> "ShardPlan":
        """Inverse of :meth:`to_dict`."""
        return cls(bounds=tuple((int(a), int(b)) for a, b in d["bounds"]),
                   racks=tuple(tuple(int(s) for s in rack)
                               for rack in d["racks"]))


@dataclasses.dataclass
class ShardCandidates:
    """What one shard (or one rack) ships up the aggregation tree.

    Scalars for *every* flagged host — ids, scores, onsets are 24 bytes a
    host, cheap enough to never prune.  Evidence blocks do not ride the
    tree: the fleet fetches them for its RCA set once the set is known.
    Raw telemetry never crosses.
    """

    #: absolute flagged host ids, ascending
    idx: np.ndarray
    #: their detection scores (f64)
    score: np.ndarray
    #: their onsets relative to the detection window
    onset: np.ndarray
    #: absolute ids of hosts quarantined this round
    qhosts: np.ndarray

    @property
    def scalar_bytes(self) -> int:
        """Wire size of the always-shipped scalar records."""
        return (self.idx.size * _CAND_RECORD_BYTES
                + self.qhosts.size * 8)


@dataclasses.dataclass
class ShardTraffic:
    """Cross-shard traffic accounting for one sharded round.

    ``raw_bytes`` is the counterfactual — what shipping every shard's
    full (H_s, C, T) slab to the fleet level would have cost — so
    ``total_bytes / raw_bytes`` is the tree's traffic reduction, the
    bench's bounded-cross-shard-traffic claim."""

    #: shard->rack bytes: scalar candidate records
    shard_scalar_bytes: int = 0
    #: shard->rack bytes: the fetched evidence blocks of the RCA set
    shard_evidence_bytes: int = 0
    #: rack->fleet bytes: scalar candidate records (post rack merge)
    rack_scalar_bytes: int = 0
    #: rack->fleet bytes: the same fetched blocks, on their second hop
    rack_evidence_bytes: int = 0
    #: per-host scores shipped for the FleetDiagnosis readout (8 B/host)
    score_bytes: int = 0
    #: counterfactual: total raw slab bytes that did NOT cross
    raw_bytes: int = 0
    #: flagged candidates that crossed shard->rack
    n_candidates: int = 0
    #: evidence blocks fetched: one per RCA'd host
    n_evidence: int = 0

    @property
    def total_bytes(self) -> int:
        """Everything that actually crossed the tree."""
        return (self.shard_scalar_bytes + self.shard_evidence_bytes
                + self.rack_scalar_bytes + self.rack_evidence_bytes
                + self.score_bytes)


def _fhex(x: float) -> str:
    """Byte-exact float canonicalization (hex survives JSON round trips
    losslessly, unlike repr-at-17-digits corner cases)."""
    return float(x).hex()


def verdict_fingerprint(fd: FleetDiagnosis) -> Dict[str, object]:
    """Canonical deterministic content of a :class:`FleetDiagnosis`.

    Includes every field the sharded/single-slab parity contract covers —
    straggler, per-host scores, flagged order, mitigations, multi-cause
    lists, quarantine, degraded/deferred fields, and the deterministic
    parts of each Diagnosis (event timestamps/scores, ranked causes with
    confidences, per-metric evidence) — and excludes only wall-time
    measurements (``stage_seconds``, ``t_rca``, ``analysis_seconds``),
    which no two executions ever share.  Floats are hex-encoded so the
    comparison is bitwise.
    """
    def diag_fp(d) -> Dict[str, object]:
        return {
            "event": {"t_onset": _fhex(d.event.t_onset),
                      "t_detect": _fhex(d.event.t_detect),
                      "score": _fhex(d.event.score),
                      "metric": d.event.metric},
            "ranked": [{"cause": rc.cause.value,
                        "confidence": _fhex(rc.confidence),
                        "top_metric": rc.top_metric,
                        "spike_score": _fhex(rc.spike_score),
                        "correlation": _fhex(rc.correlation),
                        "lag_s": _fhex(rc.lag_s)} for rc in d.ranked],
            "per_metric": {name: {k: _fhex(v) for k, v in sorted(m.items())}
                           for name, m in sorted(d.per_metric.items())},
            "t_ready": None if d.t_ready is None else _fhex(d.t_ready),
        }

    scores = np.ascontiguousarray(
        np.asarray(fd.per_host_scores, np.float64))
    return {
        "straggler_host": int(fd.straggler_host),
        "straggler_score": _fhex(fd.straggler_score),
        "mitigation": fd.mitigation.value,
        "per_host_scores_sha256": hashlib.sha256(
            scores.tobytes()).hexdigest(),
        "flagged_hosts": [int(h) for h in fd.flagged_hosts],
        "mitigations": {str(h): m.value
                        for h, m in sorted(fd.mitigations.items())},
        "causes": {str(h): [c.value for c in cl]
                   for h, cl in sorted(fd.causes.items())},
        "diagnoses": {str(h): diag_fp(d)
                      for h, d in sorted(fd.diagnoses.items())},
        "quarantined": [int(h) for h in fd.quarantined],
        "degraded": bool(fd.degraded),
        "deferred_hosts": [int(h) for h in fd.deferred_hosts],
    }


#: provider contract for :meth:`ShardedFleetMonitor.diagnose_sharded` —
#: ``provider(shard_index) -> (host_data, valid_or_None)`` for that
#: shard's host range
ShardProvider = Callable[
    [int], Tuple[np.ndarray, Optional[np.ndarray]]]


class ShardedFleetMonitor(FleetMonitor):
    """A :class:`FleetMonitor` whose rounds execute shard by shard.

    Drop-in: :meth:`diagnose_fleet` accepts the same in-memory
    (hosts, C, T) slab and returns a verdict-identical
    :class:`FleetDiagnosis` (see :func:`verdict_fingerprint`); the fleet
    is internally processed as ``plan.n_shards`` independent slabs, each
    detect dispatch pinned to its mesh device.  At the scales the plan
    exists for, use :meth:`diagnose_sharded` instead: a *provider*
    callback materializes one shard's slab at a time, so the full fleet
    slab never exists in memory (64k hosts × 10 channels × 3100 ticks is
    ~8 GB as one array; one 1024-host shard is ~127 MB).  Shards ship
    scalar candidates up the rack → fleet tree; the fleet then fetches
    the evidence of its RCA set only, one owning shard's slab at a time.

    All verdict state — strikes, quarantine hysteresis, degraded mode —
    lives in the base class keyed by absolute host id, advanced shard by
    shard; the plan itself is carried in :meth:`state_dict` and validated
    on restore, so a checkpoint cannot silently re-partition the fleet.
    """

    def __init__(self, plan: ShardPlan,
                 devices: Optional[Sequence[object]] = None,
                 **kwargs):
        """Bind the monitor to ``plan``; ``devices`` (default: the JAX
        device pool) are assigned round-robin per shard, and ``kwargs``
        pass through to :class:`FleetMonitor` unchanged."""
        super().__init__(**kwargs)
        #: the shard/rack layout this monitor executes
        self.plan = plan
        from repro.parallel.fleet import shard_devices
        #: per-shard detect-dispatch device (round-robin over the pool)
        self.devices = shard_devices(plan.n_shards, devices)
        #: traffic accounting of the most recent sharded round
        self.last_traffic: Optional[ShardTraffic] = None

    # ------------------------------------------------------------ execution
    def diagnose_fleet(self, ts: np.ndarray, host_data: np.ndarray,
                       channels: Sequence[str],
                       valid: Optional[np.ndarray] = None,
                       extra_cost_s: float = 0.0) -> FleetDiagnosis:
        """Single-slab signature, shard-by-shard execution.

        ``host_data`` must cover exactly ``plan.hosts`` hosts; shards are
        views into it (no copy).  Knowing the whole mask upfront lets the
        round pick the oracle/fast path once instead of re-visiting
        shards (see :meth:`diagnose_sharded`)."""
        with span("monitor.round", hosts=self.plan.hosts):
            host_data = np.asarray(host_data)
            if host_data.shape[0] != self.plan.hosts:
                raise ValueError(
                    f"host_data covers {host_data.shape[0]} hosts,"
                    f" plan covers {self.plan.hosts}")
            vfull = None
            if valid is not None:
                v = np.asarray(valid, bool)
                if v.shape != host_data.shape:
                    raise ValueError(f"valid {v.shape} vs data "
                                     f"{host_data.shape}")
                if not v.all():
                    vfull = v
            li = list(channels).index(self.cfg.latency_metric)
            T = host_data.shape[2]
            wn = min(self.cfg.window_n, T // 2)
            bn = min(self.cfg.baseline_n, T - wn)
            any_invalid = (vfull is not None
                           and not vfull[:, li, T - wn - bn:T].all())

            def provider(s: int) -> Tuple[np.ndarray, Optional[np.ndarray]]:
                a, b = self.plan.bounds[s]
                return (host_data[a:b],
                        None if vfull is None else vfull[a:b])

            return self._diagnose_shards(ts, provider, channels,
                                         extra_cost_s,
                                         any_invalid=any_invalid)

    def diagnose_sharded(self, ts: np.ndarray, provider: ShardProvider,
                         channels: Sequence[str],
                         extra_cost_s: float = 0.0) -> FleetDiagnosis:
        """One fleet round with lazily-materialized shard slabs.

        ``provider(s)`` returns shard ``s``'s ``(host_data, valid)`` —
        ``host_data`` of shape ``(bounds[s][1] - bounds[s][0], C, T)``,
        ``valid`` a same-shape bool mask or None.  The provider must be
        deterministic within the round: when one shard reports telemetry
        corruption, shards that already ran the fast path are re-visited
        through the masked f64 oracle (the single-slab masked round takes
        the oracle for *every* host), which calls the provider a second
        time for those shards.  After the visits, the provider is called
        once more for each shard that owns a host of the round's RCA set,
        in ascending shard order, to fetch those hosts' evidence.  A clean
        round visits each shard exactly once before the fetch."""
        with span("monitor.round", hosts=self.plan.hosts):
            return self._diagnose_shards(ts, provider, channels,
                                         extra_cost_s, any_invalid=None)

    def _diagnose_shards(self, ts: np.ndarray, provider: ShardProvider,
                         channels: Sequence[str], extra_cost_s: float,
                         any_invalid: Optional[bool]) -> FleetDiagnosis:
        """Shared sharded-round core (see class docstring for the tree).

        Shards are launched in order — provider, validity, detect launch
        — and finished oldest first — sweep pull, flagging — with at most
        one launched, unfinished shard per distinct device in
        :attr:`devices`: on four chips four sweeps run side by side while
        the host prepares the next shard, and on one chip each launch is
        finished before the next (the serial order).  Placement and
        overlap never change a verdict.  Once the fleet has picked its
        RCA set, each owning shard is visited once more, in ascending
        order, for its RCA'd hosts' evidence blocks.

        ``any_invalid`` None means "unknown until shards are visited"
        (provider mode, re-visit clean shards if corruption turns up);
        a bool means the caller inspected the full mask upfront."""
        plan = self.plan
        li = list(channels).index(self.cfg.latency_metric)
        per_shard: List[Optional[ShardCandidates]] = [None] * plan.n_shards
        shard_scores: List[Optional[np.ndarray]] = [None] * plan.n_shards
        quar_saved: List[Optional[np.ndarray]] = [None] * plan.n_shards
        ran_oracle = [False] * plan.n_shards
        saw_invalid = [False] * plan.n_shards
        traffic = ShardTraffic()
        stage: Dict[str, float] = {"detect": 0.0}
        dims: Optional[Tuple[int, int, int]] = None  # (T, wn, bn)
        tick_end: Optional[int] = None  # one grid anchor for every shard
        # launched, unfinished shards, oldest first: at most one per chip
        n_chips = len(set(self.devices))
        pending: Deque[Tuple[int, np.ndarray, object]] = collections.deque()

        def load(s: int) -> Tuple[np.ndarray, Optional[np.ndarray]]:
            """``provider(s)`` checked against the plan: the shard's slab
            and its mask, None when every cell is valid."""
            a, b = plan.bounds[s]
            with span("shard.provider", shard=s):
                slab, val = provider(s)
            slab = np.asarray(slab)
            if slab.ndim != 3 or slab.shape[0] != b - a:
                raise ValueError(f"shard {s} slab {slab.shape} vs bounds "
                                 f"({a}, {b})")
            if dims is not None and slab.shape[2] != dims[0]:
                raise ValueError(f"shard {s} T={slab.shape[2]} vs {dims[0]}")
            if val is None:
                return slab, None
            v = np.asarray(val, bool)
            if v.shape != slab.shape:
                raise ValueError(f"shard {s} valid {v.shape} vs slab "
                                 f"{slab.shape}")
            return slab, (None if v.all() else v)

        def visit(s: int, force_oracle: bool) -> None:
            """Launch half: provider, validity, detect launch."""
            nonlocal dims, tick_end
            with span("shard.visit", shard=s, oracle=force_oracle):
                a, b = plan.bounds[s]
                slab, vfull = load(s)
                if dims is None:
                    T = slab.shape[2]
                    wn = min(self.cfg.window_n, T // 2)
                    bn = min(self.cfg.baseline_n, T - wn)
                    if bn < MIN_BASELINE_N:
                        raise _ShortBaseline
                    dims = (T, wn, bn)
                    tick_end = self._tick_end(ts, T)
                T, wn, bn = dims
                saw_invalid[s] = (
                    vfull is not None
                    and not vfull[:, li, T - wn - bn:T].all())
                # base=a keys the incremental moment rows (and quarantine
                # state) by absolute host id; a forced-oracle re-visit
                # invalidates rather than advances them, so a shard visited
                # twice in one round cannot double-advance the moment state
                with stage_span(stage, "detect", "monitor.detect", rows=b - a):
                    quar, launched = self._detect_launch(
                        slab, vfull, li, T, wn, bn,
                        force_oracle=force_oracle, device=self.devices[s],
                        base=a, quar=quar_saved[s], tick_end=tick_end)
                quar_saved[s] = quar
                ran_oracle[s] = force_oracle or saw_invalid[s]
                pending.append((s, quar, launched))

        def finish() -> None:
            """Finish half of the oldest launched shard: collect, flag."""
            n = len(pending)
            s, quar, launched = pending.popleft()
            a, _ = plan.bounds[s]
            with span("shard.finish", shard=s, chip=self.devices[s].id):
                with stage_span(stage, "detect", "monitor.detect"):
                    scores, cand, onset_rel, qloc = self._detect_round(
                        quar, launched, in_flight=n)
                per_shard[s] = ShardCandidates(
                    idx=cand + a, score=scores[cand], onset=onset_rel,
                    qhosts=qloc + a)
                shard_scores[s] = scores

        def launch(s: int, force_oracle: bool) -> None:
            visit(s, force_oracle)
            while len(pending) >= n_chips:
                finish()

        force_all = bool(any_invalid)
        try:
            launch(0, force_oracle=force_all)
        except _ShortBaseline:
            # same short-snapshot refusal as the single-slab path, decided
            # before any shard state advances
            self.last_traffic = ShardTraffic()
            return self._quiet_round(plan.hosts, extra_cost_s)
        for s in range(1, plan.n_shards):
            launch(s, force_oracle=force_all)
        while pending:
            finish()
        if any_invalid is None and any(saw_invalid):
            # corruption surfaced after some shards took the fast path:
            # re-visit exactly those through the oracle so the round
            # matches what one full-slab masked call would have computed
            for s in range(plan.n_shards):
                if not ran_oracle[s]:
                    launch(s, force_oracle=True)
            while pending:
                finish()

        # rack-level reduce: merge member candidate lists (scalars only)
        with stage_span(stage, "reduce", "shard.reduce") as sp:
            rack_cands: List[ShardCandidates] = []
            for rack in plan.racks:
                members = [per_shard[s] for s in rack]
                for m in members:
                    traffic.shard_scalar_bytes += m.scalar_bytes
                    traffic.n_candidates += int(m.idx.size)
                rc = ShardCandidates(
                    idx=np.concatenate([m.idx for m in members]),
                    score=np.concatenate([m.score for m in members]),
                    onset=np.concatenate([m.onset for m in members]),
                    qhosts=np.concatenate([m.qhosts for m in members]))
                traffic.rack_scalar_bytes += rc.scalar_bytes
                rack_cands.append(rc)

            # fleet level: concatenate rack candidates (shard order keeps
            # absolute ids ascending) and hand the merged round to the
            # unchanged fleet verdict logic
            scores = np.concatenate([shard_scores[s]
                                     for s in range(plan.n_shards)])
            cand = np.concatenate([rc.idx for rc in rack_cands])
            onset_rel = np.concatenate([rc.onset for rc in rack_cands])
            qhosts = np.concatenate([rc.qhosts for rc in rack_cands])
            sp.set_metadata(candidates=traffic.n_candidates)
        traffic.score_bytes = int(scores.size) * 8
        # counterfactual: what shipping every raw f32 shard slab would cost
        T, wn, bn = dims
        traffic.raw_bytes = plan.hosts * len(channels) * T * 4
        self.last_traffic = traffic

        def evidence_for(geom, rca_hosts: np.ndarray,
                         ) -> Tuple[np.ndarray, Dict[str, int]]:
            """The fetch: evidence for exactly the fleet's RCA set, one
            provider call per owning shard, in ascending shard order."""
            owner = np.array([plan.shard_of(h) for h in rca_hosts])
            X = np.empty((rca_hosts.size, geom.rows.size, geom.cols.size),
                         np.float32)
            shards = np.unique(owner)
            for s in shards:
                k = np.flatnonzero(owner == s)
                slab, vfull = load(int(s))
                X[k] = self._gather_evidence(
                    slab, rca_hosts[k] - plan.bounds[s][0], geom, vfull)
                del slab, vfull         # one shard slab alive at a time
            # each block crosses shard -> rack -> fleet
            traffic.shard_evidence_bytes = int(X.nbytes)
            traffic.rack_evidence_bytes = int(X.nbytes)
            traffic.n_evidence = int(rca_hosts.size)
            return X, {"shards": int(shards.size)}

        with span("monitor.finish", flagged=cand.size):
            return self._finish_round(ts, channels, li, T, wn, bn, scores,
                                      cand, onset_rel, qhosts, stage,
                                      extra_cost_s, evidence_for)

    # ------------------------------------------------------------ checkpoint
    def state_dict(self) -> Dict[str, object]:
        """Base monitor state plus the shard plan (restore validates it)."""
        d = super().state_dict()
        d["shard_plan"] = self.plan.to_dict()
        return d

    def load_state_dict(self, d: Dict[str, object]) -> None:
        """Restore, refusing a checkpoint partitioned under a different
        plan — the quarantine/strike maps are keyed by absolute host id,
        so they survive *identical* re-partitioning only.  A payload
        without a plan (written by a single-slab monitor) is accepted:
        absolute host ids make single-slab state shard-agnostic."""
        if "shard_plan" in d:
            their = ShardPlan.from_dict(d["shard_plan"])
            if their != self.plan:
                raise ValueError(
                    f"checkpoint shard plan {their.to_dict()} does not "
                    f"match monitor plan {self.plan.to_dict()}; "
                    "cold-start or rebuild the monitor with the "
                    "checkpointed plan")
        super().load_state_dict(d)
