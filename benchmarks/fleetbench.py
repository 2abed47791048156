"""Fleet-RCA throughput + detection-sweep benchmarks (perf trajectory).

Four sections, all emitted into BENCH_fleet.json by run.py:

  sweep/  — full-trial ``CorrelationEngine.process`` wall time, rolling-
            statistics fast path vs the seed scalar per-tick path, at the
            default boundary cadence and at the 10-sample streaming cadence.
  fleet/  — batched ``FleetMonitor.diagnose_fleet`` vs B sequential
            per-host ``engine.process`` replays, at B in {16, 64, 256,
            1024}: hosts/sec, speedup, per-stage wall time, plus the
            streaming-detect stage (one dispatch over the f32 slab) with a
            byte-exact flagged/onset parity check against f64
            ``detect_rows``.
  fleet/live_* — the live path: ``FleetAggregator`` staging (seqlock
            read_window into a preallocated slab) vs per-host
            ``window(copy=True)`` snapshots + ``np.stack``, and the
            torn-read retry rate of the seqlock reader under a
            writer-storm thread.
  eval/   — event-batched Layer 3: ``run_eval`` with all trials' events in
            ONE fused dispatch per diagnoser vs the per-event path, plus
            the columnar TrialStore path (slab-indexed evidence gather,
            ``SLICE_OPS``-counted).

The batched fleet path runs the fused spike+xcorr math through the jit'd
XLA reference (`use_kernels=False`) — on CPU the Pallas kernels execute in
interpret mode, which validates numerics but is not a timing path; kernel
parity is covered by tests/test_fused.py.
"""
from __future__ import annotations

import sys
import threading
import time
from typing import List, Sequence, Tuple

import numpy as np

from repro.core import engine as engine_mod
from repro.core.baselines import make_baseline
from repro.core.engine import CorrelationEngine, EngineConfig
from repro.monitor.aggregator import FleetAggregator
from repro.monitor.fleet import FleetMonitor
from repro.sim.scenario import (
    N_PER_CLASS, PROTOCOL_CLASSES, TrialStore, make_trial,
)
from repro.telemetry.agent import TelemetryAgent
from repro.telemetry.collectors import SimCollector
from repro.telemetry.ringbuffer import MultiChannelRing

_CLIP_S = 46.0     # trailing snapshot: event at t_on=40 s is inside it


def _median_wall(fn, reps: int = 3) -> float:
    walls = []
    for _ in range(reps):
        t0 = time.perf_counter()
        fn()
        walls.append(time.perf_counter() - t0)
    return float(np.median(walls))


def _median_stages(mon: FleetMonitor, ts, data, channels, reps: int,
                   ):
    """(median wall, per-stage median seconds, last FleetDiagnosis) over
    ``reps`` diagnose_fleet calls — stage attribution from one run is a
    single sample and too noisy to report."""
    walls, stages = [], {}
    fd = None
    for _ in range(reps):
        mon._strikes = {}
        t0 = time.perf_counter()
        fd = mon.diagnose_fleet(ts, data, channels)
        walls.append(time.perf_counter() - t0)
        for k, v in fd.stage_seconds.items():
            stages.setdefault(k, []).append(v)
    med = {k: float(np.median(v)) for k, v in stages.items()}
    return float(np.median(walls)), med, fd


# ---------------------------------------------------------------- sweep bench
def sweep_rows(n_trials: int = 8, reps: int = 3,
               ) -> List[Tuple[str, float, str]]:
    """Rolling-stats engine sweep vs seed scalar path, same trials."""
    trials = [make_trial(7000 + i,
                         PROTOCOL_CLASSES[i % len(PROTOCOL_CLASSES)])
              for i in range(n_trials)]
    rows: List[Tuple[str, float, str]] = []
    for tag, cfg in (("boundary", EngineConfig()),
                     ("10ms", EngineConfig(eval_every=10))):
        eng = CorrelationEngine(cfg)

        def run(fast: bool) -> None:
            for t in trials:
                eng.process(t.ts, t.data, t.channels, fast=fast)

        fast_s = _median_wall(lambda: run(True), reps)
        scalar_s = _median_wall(lambda: run(False), reps)
        rows.append((f"sweep/rolling_s/{tag}", fast_s,
                     f"{n_trials} trials, 90s @100Hz"))
        rows.append((f"sweep/scalar_s/{tag}", scalar_s, "seed per-tick path"))
        rows.append((f"sweep/speedup/{tag}", scalar_s / fast_s,
                     "scalar / rolling"))
    return rows


# ----------------------------------------------------------- slab sweep bench
def _event_sig(evs) -> list:
    """Deterministic signature of one row's (event, rca_index) list."""
    return [(ev.t_onset, ev.t_detect, ev.score, int(t)) for ev, t in evs]


def sweep_slab_rows(n_per_class: int = 4, reps: int = 3,
                    fleet_hosts: int = 256,
                    ) -> List[Tuple[str, float, str]]:
    """Suite-scale Layer-2: per-trial ``detect_events`` loop vs the one-
    dispatch slab sweep (``detect_events_store``), on the full multi-fault
    scenario suite (the scorecard's trials), at the boundary cadence and
    the 10-sample streaming cadence.

    ``eval/sweep_parity`` is the byte-exact invariant CI gates on: the
    slab path must reproduce the per-row oracle's event sets —
    ``t_onset`` / ``t_detect`` stamps, scores AND rca indices — across
    every trial of the suite (cooldown, pending flush and multi-event
    trials included), at both cadences.  ``fleet/sweep_single_tick``
    records the fleet-detect reuse of the same sweep core (one tick at
    the slab edge) against the f64 ``detect_rows`` oracle.
    """
    from repro.core.spike import detect_rows
    from repro.kernels.detect import ops as detect_ops
    from repro.sim import scenarios as scen

    rows: List[Tuple[str, float, str]] = []
    trials = scen.build_suite(n_per_class, 41)
    store = TrialStore.from_trials(trials)
    parity = 1.0
    for tag, cfg in (("boundary", EngineConfig()),
                     ("10ms", EngineConfig(eval_every=10))):
        eng = CorrelationEngine(cfg)

        def loop():
            return [eng.detect_events(store.ts, store.slab[i],
                                      store.channels)
                    for i in range(len(store))]

        def slab():
            return eng.detect_events_store(store.ts, store.slab,
                                           store.channels)

        ref, got = loop(), slab()
        parity = min(parity, float(
            [_event_sig(e) for e in ref] == [_event_sig(e) for e in got]))
        loop_s = _median_wall(loop, reps)
        slab_s = _median_wall(slab, reps)
        n_ev = sum(len(e) for e in ref)
        rows.append((f"eval/sweep_loop_s/{tag}", loop_s,
                     f"per-trial detect_events loop, {len(store)} trials, "
                     f"{n_ev} events"))
        rows.append((f"eval/sweep_slab_s/{tag}", slab_s,
                     "one batched sweep dispatch + numpy resolve"))
        rows.append((f"eval/sweep_speedup/{tag}", loop_s / slab_s,
                     "per-trial loop / slab sweep"))
    rows.append(("eval/sweep_parity", parity,
                 "1.0 = slab events byte-exact vs per-row oracle "
                 "(stamps, scores, rca indices; both cadences)"))

    # fleet reuse: the same sweep core at a single tick IS the streaming
    # fleet detect — time it on a fleet slab and re-prove detect_rows parity
    cfg = EngineConfig()
    wn, bn = cfg.window_n, cfg.baseline_n
    H = int(fleet_hosts)
    ts, data, channels = _make_fleet(H, bad_host=H // 2)
    li = list(channels).index(cfg.latency_metric)
    T = data.shape[-1]
    tail = np.ascontiguousarray(data[:, li, T - wn - bn:], np.float32)

    def single_tick():
        # the CPU deployment path: masked-XLA ref (the Pallas kernel runs
        # in interpret mode on CPU — a correctness, not a timing, path)
        return detect_ops.detect_hosts_slab_launch(
            tail, wn, bn, cfg.threshold, cfg.persistence,
            use_kernel=False).collect()

    single_tick()                                          # jit warm-up
    tick_s = _median_wall(single_tick, reps)
    fire, _, onset = single_tick()
    t64 = np.asarray(tail, np.float64)
    f0, _, o0 = detect_rows(t64[:, bn:], t64[:, :bn], cfg.threshold,
                            cfg.persistence)
    rows.append((f"fleet/sweep_single_tick_s/H{H}", tick_s,
                 "fleet detect through the shared sweep core, one tick"))
    rows.append((f"fleet/sweep_single_tick_parity/H{H}",
                 float(np.array_equal(fire, f0)
                       and np.array_equal(onset, o0)),
                 "1.0 = fire/onset byte-exact vs f64 detect_rows"))
    return rows


# ---------------------------------------------------------------- fleet bench
def _make_fleet(n_hosts: int, bad_host: int, seed: int = 0,
                n_unique: int = 16, cls: str = "nic",
                bad_every: int = 0):
    """(ts, (hosts, C, T) data, channels).  Quiet hosts cycle over
    ``n_unique`` distinct ambient trials (fleet-size-independent setup
    cost); one injected straggler.  ``bad_every`` > 0 additionally injects
    every bad_every'th host (the incident-storm profile)."""
    quiet = [make_trial(seed + u, cls, intensity=0.0, t_on=40.0,
                        confuser_prob=0.0)
             for u in range(min(n_unique, n_hosts))]
    n_bad = min(8, n_hosts)
    bad = [make_trial(seed + 777 + u, cls, intensity=2.0, t_on=40.0,
                      confuser_prob=0.0) for u in range(n_bad)]
    t_hi = int(_CLIP_S * quiet[0].rate_hz)

    def pick(h):
        if h == bad_host or (bad_every and h % bad_every == 0):
            return bad[h % n_bad]
        return quiet[h % len(quiet)]

    data = np.stack([pick(h).data[:, :t_hi] for h in range(n_hosts)])
    return quiet[0].ts[:t_hi], data, quiet[0].channels


def _detect_compare_rows(B: int, ts, data32, channels, reps: int,
                         tag: str = "") -> Tuple[list, float, dict, object]:
    """Streaming-detect stage rows for one fleet slab, with the flagged
    hosts and onsets checked against f64 ``detect_rows`` over the same
    latency tail.

    Returns (rows, batched wall, median stages, FleetDiagnosis)."""
    from repro.core.spike import detect_rows
    mon = FleetMonitor(use_kernels=False)
    mon.diagnose_fleet(ts, data32, channels)            # jit warm-up
    batched_s, stages, fd = _median_stages(mon, ts, data32, channels, reps)
    cfg, T = mon.cfg, data32.shape[-1]
    wn = min(cfg.window_n, T // 2)
    bn = min(cfg.baseline_n, T - wn)
    li = list(channels).index(cfg.latency_metric)
    tail = np.asarray(data32[:, li, T - wn - bn:], np.float64)
    fire, _, onset = detect_rows(tail[:, bn:], tail[:, :bn], cfg.threshold,
                                 cfg.persistence)
    parity = float(
        sorted(fd.flagged_hosts) == np.flatnonzero(fire).tolist()
        and all(fd.diagnoses[h].event.t_onset == ts[T - wn + onset[h]]
                for h in fd.flagged_hosts))
    rows = [
        (f"fleet/detect_fast_s{tag}/B{B}", stages["detect"],
         f"one streaming-detect dispatch, f32 slab; "
         f"{len(fd.flagged_hosts)} flagged"),
        (f"fleet/detect_parity{tag}/B{B}", parity,
         "1.0 = flagged hosts + onsets byte-exact vs f64 detect_rows"),
    ]
    return rows, batched_s, stages, fd


def fleet_rows(batch_sizes: Sequence[int] = (16, 64, 256, 1024),
               reps: int = 3, sequential_baseline: bool = True,
               ) -> List[Tuple[str, float, str]]:
    rows: List[Tuple[str, float, str]] = []
    for B in batch_sizes:
        ts, data, channels = _make_fleet(B, bad_host=B // 2)
        # the columnar deployment hands the monitor the ring's f32 slab
        data32 = np.ascontiguousarray(data, np.float32)
        det_rows, batched_s, stages, fd = _detect_compare_rows(
            B, ts, data32, channels, reps)
        rows.append((f"fleet/batched_s/B{B}", batched_s,
                     f"{len(fd.flagged_hosts)} flagged, straggler="
                     f"{fd.straggler_host}"))
        rows.append((f"fleet/hosts_per_s/B{B}", B / batched_s, "batched"))
        rows += det_rows
        if sequential_baseline:
            eng = CorrelationEngine()

            def sequential() -> None:
                for h in range(B):
                    eng.process(ts, data[h], channels)

            seq_s = _median_wall(sequential, max(1, reps - 1))
            rows.append((f"fleet/sequential_s/B{B}", seq_s,
                         "B x engine.process (rolling fast path, f64)"))
            rows.append((f"fleet/speedup/B{B}", seq_s / batched_s,
                         "f64 sequential / f32-columnar batched (PR 2 "
                         "redefined the batched side; detect_* rows are "
                         "the like-for-like comparison)"))
        for stage, wall in stages.items():
            rows.append((f"fleet/stage_s/{stage}/B{B}", wall, ""))
    # incident-storm profile at the largest B: ~1/4 of the fleet degraded;
    # the streaming detect's one-dispatch cost does not grow with the
    # flagged fraction
    B = max(batch_sizes)
    ts, data, channels = _make_fleet(B, bad_host=B // 2, bad_every=4)
    data32 = np.ascontiguousarray(data, np.float32)
    det_rows, _, _, _ = _detect_compare_rows(B, ts, data32, channels, reps,
                                             tag="_storm")
    rows += det_rows
    return rows


# -------------------------------------------------------------- sharded bench
def _shard_pool(seed: int = 9300, cls: str = "nic", t_on: float = 28.0,
                clip_s: float = 34.0):
    """(ts, (n_unique+n_bad, C, T') f32 trial pool, channels, n_quiet).

    The provider-fed storm rows assemble each shard's slab from this
    fixed pool, so fleet size costs shard-slab assembly — the full
    (B, C, T) array never exists (the point of the provider API).
    Trials are clipped tighter than ``_CLIP_S`` (event at ``t_on`` still
    inside the trailing window) to keep the 64k-host row affordable."""
    quiet = [make_trial(seed + u, cls, intensity=0.0, t_on=t_on,
                        confuser_prob=0.0) for u in range(16)]
    bad = [make_trial(seed + 777 + u, cls, intensity=2.0, t_on=t_on,
                      confuser_prob=0.0) for u in range(8)]
    t_hi = int(clip_s * quiet[0].rate_hz)
    pool = np.stack([t.data[:, :t_hi] for t in quiet + bad]
                    ).astype(np.float32)
    return quiet[0].ts[:t_hi], pool, quiet[0].channels, len(quiet)


def shard_rows(parity_hosts: int = 96,
               storm_hosts: Sequence[int] = (16384, 65536),
               shard_hosts: int = None, reps: int = 3,
               ) -> List[Tuple[str, float, str]]:
    """Sharded fleet monitor: byte-exact parity bit + 10k+-host scale-out.

    Two sections:

      fleet/shard_parity      the CI-gated bit (``benchmarks/regress.py``
                              requires exactly 1.0): single-slab vs
                              sharded ``verdict_fingerprint`` equality on
                              a ragged 3-shard plan across a clean round,
                              two corruption rounds (quarantine entry),
                              an incident-storm round with an RCA top-K
                              cap (deferral), and the provider path with
                              late-surfacing corruption (oracle re-visit
                              of fast-path shards);
      fleet/shard_*/B{B}      storm-profile throughput + cross-shard
                              traffic at 16k-64k hosts through
                              ``diagnose_sharded`` — shard slabs are
                              materialized one at a time from a fixed
                              trial pool, and the rows record what
                              actually crossed the rack->fleet tree
                              (candidate scalars + the fetched evidence
                              blocks of the RCA set) against the
                              raw-slab counterfactual.
    """
    from repro.kernels import tuning
    from repro.monitor.shard import (
        ShardPlan, ShardedFleetMonitor, verdict_fingerprint,
    )

    rows: List[Tuple[str, float, str]] = []
    cfg = EngineConfig()

    # ---- parity scenario on a deliberately ragged plan
    H = int(parity_hosts)
    cut1, cut2 = H // 3, 2 * H // 3 + 1
    plan = ShardPlan.from_bounds([(0, cut1), (cut1, cut2), (cut2, H)],
                                 rack_shards=2)
    ts, clean, channels = _make_fleet(H, bad_host=cut1 + 1, seed=9400)
    _, storm, _ = _make_fleet(H, bad_host=cut1 + 1, seed=9400, bad_every=5)
    li = list(channels).index(cfg.latency_metric)
    valid = np.ones(clean.shape, bool)
    valid[H - 2, li, -1200:] = False      # ~half the detect tail invalid

    mono = FleetMonitor(use_kernels=False, rca_top_k=4)
    shard = ShardedFleetMonitor(plan, use_kernels=False, rca_top_k=4)
    parity, n_rounds = 1.0, 0
    fd = None
    for data, v in ((clean, None), (clean, valid), (clean, valid),
                    (storm, None)):
        a = mono.diagnose_fleet(ts, data, channels, valid=v)
        fd = shard.diagnose_fleet(ts, data, channels, valid=v)
        parity = min(parity, float(
            verdict_fingerprint(a) == verdict_fingerprint(fd)))
        n_rounds += 1
    covered = bool(a.quarantined) and bool(a.deferred_hosts)
    # provider path: corruption on the LAST shard only — the fast-path
    # shards must be re-visited through the oracle and still match the
    # single-slab masked round
    pvalid = np.ones(clean.shape, bool)
    pvalid[H - 2, li, -200:] = False      # below the quarantine threshold
    calls: List[int] = []

    def provider(s: int):
        calls.append(s)
        a0, b0 = plan.bounds[s]
        return clean[a0:b0], pvalid[a0:b0]

    shard2 = ShardedFleetMonitor(plan, use_kernels=False)
    fdp = shard2.diagnose_sharded(ts, provider, channels)
    ref = FleetMonitor(use_kernels=False).diagnose_fleet(
        ts, clean, channels, valid=pvalid)
    # visits, the re-visits of fast-path shards 0-1, then one evidence
    # fetch per shard owning an RCA'd host, ascending
    owners = sorted({plan.shard_of(h) for h in fdp.diagnoses})
    revisited = calls == list(range(plan.n_shards)) + [0, 1] + owners
    parity = min(parity, float(
        verdict_fingerprint(fdp) == verdict_fingerprint(ref)
        and revisited and covered))
    rows.append(("fleet/shard_parity", parity,
                 f"1.0 = sharded verdicts byte-exact vs single slab over "
                 f"{n_rounds + 1} rounds (ragged shards, quarantine, "
                 "top-K deferral, oracle re-visit)"))
    tr = shard.last_traffic
    rows.append((f"fleet/shard_xfer_frac/H{H}",
                 tr.total_bytes / tr.raw_bytes,
                 "storm round, rca_top_k=4: bytes crossing the tree / "
                 "raw shard slabs"))

    # ---- storm-profile scale-out through the provider API
    sh = tuning.shard_hosts(shard_hosts)
    topk = tuning.shard_topk()
    pts, pool, pchannels, n_quiet = _shard_pool()
    n_pool = pool.shape[0]

    def make_provider(plan_b, bad_host, bad_every):
        def prov(s: int):
            a0, b0 = plan_b.bounds[s]
            idx = np.array(
                [n_quiet + h % (n_pool - n_quiet)
                 if (h == bad_host or (bad_every and h % bad_every == 0))
                 else h % n_quiet
                 for h in range(a0, b0)])
            return pool[idx], None
        return prov

    # jit warm-up at one shard so the timed rounds hit the compile cache
    warm = ShardedFleetMonitor(
        ShardPlan.from_bounds([(0, min(sh, 64))], rack_shards=1),
        use_kernels=False)
    warm.diagnose_sharded(
        pts, make_provider(warm.plan, bad_host=1, bad_every=16), pchannels)

    for B in storm_hosts:
        B = int(B)
        plan_b = ShardPlan.for_fleet(B, shard_hosts=sh)
        mon = ShardedFleetMonitor(plan_b, use_kernels=False,
                                  rca_top_k=topk)
        prov = make_provider(plan_b, bad_host=1, bad_every=16)
        walls = []
        fd = None
        for _ in range(max(1, reps - 2)):
            mon._strikes = {}
            t0 = time.perf_counter()
            fd = mon.diagnose_sharded(pts, prov, pchannels)
            walls.append(time.perf_counter() - t0)
        round_s = float(np.median(walls))
        tr = mon.last_traffic
        tag = f"B{B}"
        rows.append((f"fleet/shard_round_s/{tag}", round_s,
                     f"{plan_b.n_shards} shards x {sh} hosts, "
                     f"{plan_b.n_racks} racks, storm bad_every=16, "
                     f"rca_top_k={topk}, {len(fd.flagged_hosts)} flagged"))
        rows.append((f"fleet/shard_hosts_per_s/{tag}", B / round_s,
                     "provider-fed sharded round (slab assembly included)"))
        rows.append((f"fleet/shard_stage_detect_s/{tag}",
                     fd.stage_seconds.get("detect", 0.0),
                     "sum of per-shard detect dispatches"))
        rows.append((f"fleet/shard_stage_reduce_s/{tag}",
                     fd.stage_seconds.get("reduce", 0.0),
                     "rack->fleet candidate merge (scalars only)"))
        rows.append((f"fleet/shard_xfer_bytes/{tag}",
                     float(tr.total_bytes),
                     f"{tr.n_candidates} candidate records + "
                     f"{tr.n_evidence} evidence blocks + per-host scores"))
        rows.append((f"fleet/shard_xfer_frac/{tag}",
                     tr.total_bytes / tr.raw_bytes,
                     "vs shipping every raw shard slab to the fleet level"))
    return rows


# ------------------------------------------- incremental streaming moments
def _drive_incremental(mons, ts, data32, channels, round_ticks,
                       chaos_round: int, li: int):
    """Drive monitors in lockstep over the growing-window schedule.

    Each round every monitor sees the identical slab slice back to back
    (interleaving keeps allocator/page-cache warming symmetric between
    the warm and cold variants).  Returns per-monitor
    ``(detect_s, wall_s, fingerprints)`` lists plus the rounds in which
    ``mons[0]``'s incremental state re-anchored.  Round ``chaos_round``
    carries a validity mask with a corrupted latency tail on one host —
    the masked-oracle round that must invalidate the incremental state
    without moving any verdict.
    """
    from repro.monitor.shard import verdict_fingerprint
    inc = getattr(mons[0], "_inc", None)
    det = [[] for _ in mons]
    walls = [[] for _ in mons]
    fps = [[] for _ in mons]
    re_rounds = []
    B = data32.shape[0]
    for i, tk in enumerate(round_ticks):
        vmask = None
        if i == chaos_round:
            vmask = np.ones((B, len(channels), tk), bool)
            vmask[B // 2, li, -200:] = False
        re0 = inc.reanchors if inc is not None else 0
        for j, mon in enumerate(mons):
            t0 = time.perf_counter()
            fd = mon.diagnose_fleet(ts[:tk], data32[:, :, :tk], channels,
                                    valid=vmask)
            walls[j].append(time.perf_counter() - t0)
            det[j].append(fd.stage_seconds["detect"])
            fps[j].append(verdict_fingerprint(fd))
        if inc is not None and inc.reanchors > re0:
            re_rounds.append(i)
    return det, walls, fps, re_rounds


def incremental_rows(batch_sizes: Sequence[int] = (256, 1024),
                     shard_batch: int = 16384,
                     start_s: float = 36.0, step_s: float = 0.5,
                     reanchor_every: int = 6, chaos_round: int = 8,
                     ) -> List[Tuple[str, float, str]]:
    """Incremental O(delta) streaming moments vs the per-round direct pass.

    Emits, per quiet fleet size B (plus a storm profile at the largest B
    and a provider-fed sharded fleet at ``shard_batch``):

      fleet/incremental_speedup/*   warm incremental monitor vs the same
                                    monitor recomputing moments from
                                    scratch every round
                                    (``incremental=False`` — the PR 9
                                    detect stage), median over a
                                    growing-window round schedule.
      fleet/incremental_parity      the CI-gated bit (exactly 1.0):
                                    every re-anchor bitwise-matched the
                                    carried block state, the chaos round
                                    forced invalidation + rebuild, and
                                    the incremental monitor's verdict
                                    fingerprints equal the from-scratch
                                    monitor's on every round (masked
                                    round included) — plain and sharded.
      fleet/incremental_reanchor_s  detect-stage cost of a re-anchor
                                    round (state rebuilt AND compared).
      fleet/incremental_round_cpu_frac/B*  full monitor round as a
                                    fraction of the round period — the
                                    analysis-side cousin of the paper's
                                    1.21 % collection overhead.

    The schedule appends ``step_s`` of fresh ticks per round — the live
    cadence the incremental state is built for — with one masked chaos
    round in the middle and ``reanchor_every`` small enough that several
    re-anchors land inside the window.
    """
    cfg = EngineConfig()
    rate = cfg.rate_hz
    rows: List[Tuple[str, float, str]] = []
    parity_ok = True
    reanchor_costs: List[float] = []

    def schedule(t_end_s: float):
        return list(range(int(start_s * rate), int(t_end_s * rate) + 1,
                          max(1, int(step_s * rate))))

    def keep(idx, n, re_rounds=()):
        # round 0 pays the one-time cold build (and the process-wide XLA
        # compile); the chaos round is the masked oracle on both
        # monitors and the round after it is the forced full rebuild;
        # re-anchor rounds are costed separately by
        # fleet/incremental_reanchor_s (the bench runs them 5x denser
        # than the REPRO_REANCHOR_ROUNDS=32 default to exercise the
        # parity machinery) — none of these is the quiet steady state
        drop = {0, idx, idx + 1} | set(re_rounds)
        return [i for i in range(n) if i not in drop]

    def compare(tag: str, ts, data32, channels, make_warm, make_cold,
                round_ticks):
        nonlocal parity_ok
        li = list(channels).index(cfg.latency_metric)
        mon_w, mon_c = make_warm(), make_cold()
        det, walls, fps, re_rounds = _drive_incremental(
            [mon_w, mon_c], ts, data32, channels, round_ticks,
            chaos_round, li)
        (det_w, det_c), (fp_w, fp_c) = det, fps
        st = mon_w.incremental_stats() or {}
        if fp_w != fp_c or st.get("parity") != 1.0 \
                or not re_rounds or not st.get("forced_invalidations"):
            parity_ok = False
        reanchor_costs.extend(det_w[i] for i in re_rounds if i != 0)
        ok = keep(chaos_round, len(round_ticks), re_rounds)
        sp = (float(np.median([det_c[i] for i in ok]))
              / float(np.median([det_w[i] for i in ok])))
        rows.append((f"fleet/incremental_speedup/{tag}", round(sp, 3),
                     "detect stage, warm block-cached moments vs "
                     "from-scratch per round, median over "
                     f"{len(ok)} appended-delta rounds"))
        return float(np.median([walls[0][i] for i in ok]))

    for B in batch_sizes:
        ts, data, channels = _make_fleet(B, bad_host=min(3, B - 1))
        data32 = np.ascontiguousarray(data, np.float32)

        def warm():
            m = FleetMonitor(use_kernels=False)
            m._inc.reanchor_every = reanchor_every
            return m

        wall = compare(f"B{B}", ts, data32, channels, warm,
                       lambda: FleetMonitor(use_kernels=False,
                                            incremental=False),
                       schedule(_CLIP_S))
        rows.append((f"fleet/incremental_round_cpu_frac/B{B}",
                     round(wall / step_s, 4),
                     "median full monitor round / round period "
                     f"({step_s} s cadence) — analysis-side overhead, "
                     "paper's collection target is 1.21%"))

    if batch_sizes:
        B = max(batch_sizes)
        ts, data, channels = _make_fleet(B, bad_host=3, bad_every=4)
        data32 = np.ascontiguousarray(data, np.float32)

        def warm_storm():
            m = FleetMonitor(use_kernels=False)
            m._inc.reanchor_every = reanchor_every
            return m

        compare(f"B{B}_storm", ts, data32, channels, warm_storm,
                lambda: FleetMonitor(use_kernels=False, incremental=False),
                schedule(_CLIP_S))

    if shard_batch:
        from repro.monitor.shard import (ShardedFleetMonitor, ShardPlan,
                                         verdict_fingerprint)
        ts_p, pool, channels_p, n_quiet = _shard_pool()
        plan = ShardPlan.for_fleet(shard_batch)
        li_p = list(channels_p).index(cfg.latency_metric)
        t_hi = pool.shape[2]
        rt = list(range(int(30.0 * rate), t_hi + 1,
                        max(1, int(step_s * rate))))
        cr = min(chaos_round, len(rt) - 2)

        # provider path: the full (B, C, T) slab never exists — each
        # shard's slab is tiled from the fixed trial pool on demand;
        # both monitors run interleaved on identical provider output
        mon_w = ShardedFleetMonitor(plan, use_kernels=False)
        mon_c = ShardedFleetMonitor(plan, use_kernels=False,
                                    incremental=False)
        # the shared round counter advances once per SHARD call; scale
        # the period so one shard re-anchors roughly every
        # ``reanchor_every`` fleet rounds (rotating re-anchor)
        mon_w._inc.reanchor_every = reanchor_every * plan.n_shards
        det_w, det_c, fp_w, fp_c, re_rounds = [], [], [], [], []
        for i, tk in enumerate(rt):
            def provider(s, tk=tk, chaos=(i == cr)):
                a, b = plan.bounds[s]
                idx = np.arange(a, b) % n_quiet
                if a <= 7 < b:
                    idx[7 - a] = n_quiet          # one bad straggler
                sl = np.ascontiguousarray(pool[idx, :, :tk])
                v = None
                if chaos and s == 0:
                    v = np.ones(sl.shape, bool)
                    v[0, li_p, -200:] = False
                return sl, v
            re0 = mon_w._inc.reanchors
            fd_w = mon_w.diagnose_sharded(ts_p[:tk], provider, channels_p)
            fd_c = mon_c.diagnose_sharded(ts_p[:tk], provider, channels_p)
            det_w.append(fd_w.stage_seconds["detect"])
            det_c.append(fd_c.stage_seconds["detect"])
            fp_w.append(verdict_fingerprint(fd_w))
            fp_c.append(verdict_fingerprint(fd_c))
            if mon_w._inc.reanchors > re0:
                re_rounds.append(i)
        st = mon_w.incremental_stats() or {}
        if fp_w != fp_c or st.get("parity") != 1.0 \
                or not re_rounds or not st.get("forced_invalidations"):
            parity_ok = False
        reanchor_costs.extend(det_w[i] for i in re_rounds if i != 0)
        ok = keep(cr, len(rt), re_rounds)
        sp = (float(np.median([det_c[i] for i in ok]))
              / float(np.median([det_w[i] for i in ok])))
        rows.append((f"fleet/incremental_speedup/B{shard_batch}",
                     round(sp, 3),
                     "sharded provider path (1024-host shards, "
                     "per-shard incremental state keyed by absolute "
                     "host id), detect stage, warm vs from-scratch, "
                     f"median over {len(ok)} appended-delta rounds"))

    rows.append(("fleet/incremental_parity", 1.0 if parity_ok else 0.0,
                 "bitwise re-anchor vs carried state + chaos-round "
                 "invalidation + verdict fingerprints equal to the "
                 "from-scratch monitor on every round (plain + sharded); "
                 "restore-path re-anchor covered by restart/"
                 "fleet_replay_parity and tests/test_rolling.py"))
    if reanchor_costs:
        rows.append(("fleet/incremental_reanchor_s",
                     float(np.median(reanchor_costs)),
                     "detect stage on a re-anchor round: from-scratch "
                     "rebuild + bitwise compare + sweep"))
    return rows


# ------------------------------------------------------------ live fleet bench
def live_rows(n_hosts: int = 8, window_s: float = 20.0, reps: int = 5,
              storm_s: float = 0.4) -> List[Tuple[str, float, str]]:
    """Live fleet path: aggregator staging vs per-host copying snapshots.

    The agents are virtual-clock driven past the ring wrap point so the
    staged window spans the wrap (the expensive case for a naive gather);
    the storm rows push from a real thread while a reader loops
    ``read_window`` and report the seqlock retry rate.
    """
    rows: List[Tuple[str, float, str]] = []
    trials = [make_trial(8200 + h, "nic",
                         intensity=(2.0 if h == n_hosts // 2 else 0.0),
                         t_on=40.0, confuser_prob=0.0)
              for h in range(n_hosts)]
    agents = []
    for t in trials:
        sim = SimCollector(t.channels, t.ts, t.data)
        agents.append(TelemetryAgent([sim], rate_hz=100.0,
                                     history_s=window_s + 10.0))
    agg = FleetAggregator(agents, window_s=window_s)
    agg.run_virtual(0.0, 46.0)          # wraps the (window+10)s rings
    agg.assemble()                       # warm-up

    assemble_s = _median_wall(agg.assemble, reps)

    def copies() -> None:
        # the seed deployment snapshot: one allocating consistent copy per
        # host, then a stacking copy into the (hosts, C, T) slab
        np.stack([a.window(window_s)[1] for a in agents])

    copy_s = _median_wall(copies, reps)
    H = n_hosts
    rows.append((f"fleet/live_assemble_s/H{H}", assemble_s,
                 f"aggregator staging, {window_s:.0f}s window, wrapped"))
    rows.append((f"fleet/live_copy_s/H{H}", copy_s,
                 "per-host window(copy=True) + np.stack"))
    rows.append((f"fleet/live_speedup/H{H}", copy_s / assemble_s,
                 "copying snapshots / aggregator staging"))

    mon = FleetMonitor(use_kernels=False)
    agg.diagnose(mon)                    # jit warm-up
    diag_s = _median_wall(lambda: agg.diagnose(mon), max(1, reps - 2))
    rows.append((f"fleet/live_diagnose_s/H{H}", diag_s,
                 "assemble + diagnose_fleet on the staged slab"))

    # torn-read retry rate under a writer storm (ring-level, wall-clock)
    ring = MultiChannelRing([f"c{i}" for i in range(8)], capacity=2048)
    stop = threading.Event()

    def writer() -> None:
        i = 0
        keys = {f"c{j}": 0.0 for j in range(8)}
        while not stop.is_set():
            ring.push_row(float(i), keys)
            i += 1

    old = sys.getswitchinterval()
    sys.setswitchinterval(1e-5)
    th = threading.Thread(target=writer, daemon=True)
    reads = 0
    try:
        th.start()
        t_end = time.perf_counter() + storm_s
        while time.perf_counter() < t_end:
            ring.read_window(512)
            reads += 1
    finally:
        stop.set()
        th.join(timeout=5.0)
        sys.setswitchinterval(old)
    rows.append(("fleet/live_storm_reads_per_s", reads / storm_s,
                 "read_window loop against a hot writer thread"))
    rows.append(("fleet/live_torn_retry_rate",
                 ring.torn_retries / max(reads, 1),
                 f"{ring.torn_retries} retries / {reads} reads — every "
                 "returned snapshot validated consistent"))
    return rows


# ---------------------------------------------------------------- chaos bench
def chaos_rows(reps: int = 3) -> List[Tuple[str, float, str]]:
    """Chaos-hardening invariants + clean-path sanitization overhead.

    Three rows CI gates on (``benchmarks/regress.py``):

      chaos/soak_false_verdicts   verdict count over one trial of each
                                  pure-corruption chaos class — a poisoned
                                  telemetry stream must yield ZERO
                                  GPU/host-fault verdicts;
      chaos/masked_parity         sweep_rows / sweep_rows_exact with an
                                  all-true validity mask vs no mask —
                                  must be byte-identical (the clean path
                                  pays for chaos hardening with nothing);
      chaos/sanitize_overhead_frac  wall cost of the per-row validity
                                  scan relative to the detection sweep it
                                  guards, on clean suite rows — bounded
                                  so sanitization stays a rounding error.
    """
    from repro.core import sanitize
    from repro.core.spike import MASK_NEG  # noqa: F401  (kernel sentinel)
    from repro.kernels.sweep import ops as sweep_ops
    from repro.sim import scenarios as scen
    from repro.sim.scenario import protocol_seed

    rows: List[Tuple[str, float, str]] = []
    cfg = EngineConfig()
    eng = CorrelationEngine(cfg)

    # 1) pure-corruption trio through the full engine: zero verdicts
    classes = list(scen.SCENARIO_CLASSES)
    n_verd = n_trials = 0
    for name in ("chaos_soak", "frozen_channel", "crash_restart"):
        t = scen.make_scenario(
            protocol_seed(41, classes.index(name), 0), name)[0]
        n_verd += len(eng.process(t.ts, t.data, t.channels))
        n_trials += 1
    rows.append(("chaos/soak_false_verdicts", float(n_verd),
                 f"verdicts over {n_trials} pure-corruption chaos trials "
                 "(must be 0)"))

    # 2) all-true mask vs no mask: byte-identical sweep outputs
    rng = np.random.default_rng(17)
    wn, bn = cfg.window_n, cfg.baseline_n
    T = bn + 3 * wn
    lat = rng.normal(10.0, 1.0, (8, T))
    lat[3, bn + wn:bn + 2 * wn] += 8.0          # one genuine spike
    ticks = np.arange(bn + wn, T + 1, wn, dtype=np.int64)
    ones = np.ones_like(lat, bool)
    parity = 1.0
    for exact in (False, True):
        fn = sweep_ops.sweep_rows_exact if exact else sweep_ops.sweep_rows
        a = fn(lat, wn, bn, ticks, cfg.threshold, cfg.persistence)
        b = fn(lat, wn, bn, ticks, cfg.threshold, cfg.persistence,
               valid=ones)
        parity = min(parity, float(all(
            np.array_equal(x, y) for x, y in zip(a, b))))
    rows.append(("chaos/masked_parity", parity,
                 "1.0 = all-true validity mask byte-identical to no mask "
                 "(sweep_rows + sweep_rows_exact)"))

    # 3) clean-path sanitization overhead vs the sweep it guards
    big = rng.normal(10.0, 1.0, (16, T))

    def scan() -> None:
        for r in range(big.shape[0]):
            sanitize.validity_mask(big[r])
            sanitize.forward_fill(big[r])

    def sweep() -> None:
        sweep_ops.sweep_rows(big, wn, bn, ticks, cfg.threshold,
                             cfg.persistence)

    sweep()                                     # jit warm-up
    scan_s = _median_wall(scan, reps)
    sweep_s = _median_wall(sweep, reps)
    rows.append(("chaos/sanitize_overhead_frac", scan_s / sweep_s,
                 "validity scan + fill wall / detection sweep wall, "
                 "clean rows (CI bound: <= 0.9)"))
    return rows


# ------------------------------------------------------------- restart bench
def restart_rows(reps: int = 1) -> List[Tuple[str, float, str]]:
    """Monitor survivability: fleet-level crash/restore replay parity,
    checkpoint wall costs, and deadline-aware shedding.

    Three invariants CI gates on (``benchmarks/regress.py``):

      restart/fleet_replay_parity  a session crashed mid-incident and
                                   warm-restored from its checkpoint must
                                   deliver the *byte-identical* verdict
                                   stream of an uninterrupted session;
      restart/duplicate_verdicts   the delivered stream (pre-crash verdicts
                                   + post-restore replay) must contain no
                                   repeated verdict signature — the
                                   restored cooldown map IS the dedup;
      restart/shed_rounds, restart/deferred_rca
                                   the degraded-mode path must actually
                                   shed (detect-only rounds) and defer RCA
                                   for unproven hosts under overload.
    """
    import os
    import tempfile

    from repro.monitor.checkpoint import MonitorSession

    rows: List[Tuple[str, float, str]] = []
    H = 8
    ts, data, channels = _make_fleet(H, bad_host=H // 2)
    slab = np.ascontiguousarray(data, np.float32)
    rate = 1.0 / float(ts[1] - ts[0])
    # one diagnosis round per second from first-full-baseline to trial end;
    # the injected fault (t_on = 40 s) enters the trailing window mid-run
    round_ticks = [min(int(r * rate), ts.shape[0])
                   for r in range(36, int(_CLIP_S) + 1)]

    def run_uninterrupted():
        sess = MonitorSession(FleetMonitor(use_kernels=False), channels)
        out = []
        for hi in round_ticks:
            out += sess.tick(ts[:hi], slab[:, :, :hi])[1]
        return out, sess

    base_verdicts, _ = run_uninterrupted()

    with tempfile.TemporaryDirectory() as td:
        path = os.path.join(td, "fleet.ckpt")
        sess = MonitorSession(FleetMonitor(use_kernels=False), channels)
        delivered = []
        save_ms = []
        ckpt_bytes = 0
        crash_at = None
        for k, hi in enumerate(round_ticks):
            delivered += sess.tick(ts[:hi], slab[:, :, :hi])[1]
            t0 = time.perf_counter()
            ckpt_bytes = max(ckpt_bytes, sess.save(path))
            save_ms.append((time.perf_counter() - t0) * 1e3)
            if delivered and crash_at is None:
                crash_at = k      # crash right after the first verdict
                break
        # the process dies here: a FRESH monitor + session warm-restores
        sess2 = MonitorSession(FleetMonitor(use_kernels=False), channels)
        t0 = time.perf_counter()
        restored = sess2.restore(path)
        restore_ms = (time.perf_counter() - t0) * 1e3
        for _ in range(max(0, reps - 1)):       # timing stability only
            t0 = time.perf_counter()
            MonitorSession(FleetMonitor(use_kernels=False),
                           channels).restore(path)
            restore_ms = min(restore_ms, (time.perf_counter() - t0) * 1e3)
        for k, hi in enumerate(round_ticks):
            if crash_at is not None and k <= crash_at:
                continue          # rounds the dead process already served
            delivered += sess2.tick(ts[:hi], slab[:, :, :hi],
                                    replay=(k == (crash_at or -1) + 1))[1]

    sigs = [v.sig() for v in delivered]
    parity = float(restored
                   and sigs == [v.sig() for v in base_verdicts])
    dup = len(sigs) - len(set(sigs))
    rows.append(("restart/fleet_replay_parity", parity,
                 "1.0 = crash/restore verdict stream byte-identical to "
                 "uninterrupted session"))
    rows.append(("restart/duplicate_verdicts", float(dup),
                 "repeated verdict signatures in the delivered stream "
                 "(must be 0)"))
    rows.append(("restart/suppressed_replay",
                 float(sess2.stats.duplicates_suppressed),
                 "re-derivations deduped by the restored cooldown map"))
    rows.append(("restart/replay_ticks", float(sess2.stats.replay_ticks),
                 "samples re-driven through the restored state"))
    rows.append(("restart/checkpoint_bytes", float(ckpt_bytes), ""))
    rows.append(("restart/checkpoint_save_ms", float(np.median(save_ms)),
                 "atomic tmp+fsync+rename write"))
    rows.append(("restart/checkpoint_restore_ms", float(restore_ms),
                 "validate (magic/version/CRC) + full state apply"))

    # degraded mode: overload the loop before the fault arrives, keep it
    # overloaded while the incident enters the window (fresh host -> RCA
    # deferred), then lift the load and let the budget re-arm
    mon = FleetMonitor(use_kernels=False, budget_s=0.05, shed_after=2,
                       rearm_after=3)
    sess3 = MonitorSession(mon, channels)
    for k, hi in enumerate(round_ticks):
        cost = 1.0 if k < 6 else 0.0
        sess3.tick(ts[:hi], slab[:, :, :hi], extra_cost_s=cost)
    rows.append(("restart/shed_rounds", float(mon.shed_rounds),
                 "degraded (detect-only) rounds under synthetic overload"))
    rows.append(("restart/deferred_rca", float(mon.deferred_rca),
                 "flagged-host RCA deferrals while degraded"))
    rows.append(("restart/rearmed", float(not mon.degraded),
                 "1.0 = budget hysteresis re-armed after load lifted"))
    return rows


# ----------------------------------------------------------------- eval bench
def eval_rows(n_per_class: int = 4, reps: int = 3,
              ) -> List[Tuple[str, float, str]]:
    """Event-batched Layer 3 (one fused dispatch per diagnoser) vs the
    per-event sequential diagnosis, same trials, identical predictions.

    Trial *generation* is excluded from the timed region — this isolates
    the diagnosis path ``run_eval`` drives (detection sweep + Layer 3).
    """
    rows: List[Tuple[str, float, str]] = []
    trials = [make_trial(7100 + N_PER_CLASS * ci + k, cls)
              for ci, cls in enumerate(PROTOCOL_CLASSES)
              for k in range(n_per_class)]
    inputs = [(t.ts, t.data, t.channels) for t in trials]
    dg = make_baseline("ours")
    dg.diagnose_trials(inputs)              # ragged-dispatch jit warm-up

    batched_s = _median_wall(lambda: dg.diagnose_trials(inputs), reps)
    seq_s = _median_wall(
        lambda: [dg.diagnose_trial(*t) for t in inputs], reps)
    rb = dg.diagnose_trials(inputs)
    rs = [dg.diagnose_trial(*t) for t in inputs]
    match = float(all(a.pred == b.pred for a, b in zip(rb, rs)))
    rows.append(("eval/batched_s", batched_s,
                 f"{len(trials)} trials, one fused Layer-3 dispatch"))
    rows.append(("eval/sequential_s", seq_s, "one _diagnose per event"))
    rows.append(("eval/speedup", seq_s / batched_s, "sequential / batched"))
    rows.append(("eval/pred_parity", match,
                 "1.0 = per-trial predictions identical"))

    # columnar trial store: the whole eval as one f32 (trials, C, T) slab,
    # evidence gathered by slab indexing instead of per-event reslicing
    store = TrialStore.from_trials(trials)
    dg.diagnose_store(store)            # warm-up
    store_s = _median_wall(lambda: dg.diagnose_store(store), reps)
    c0 = engine_mod.SLICE_OPS
    rstore = dg.diagnose_store(store)
    ops_store = engine_mod.SLICE_OPS - c0
    c0 = engine_mod.SLICE_OPS
    dg.diagnose_trials(inputs)
    ops_event = engine_mod.SLICE_OPS - c0
    match_store = float(all(a.pred == b.pred for a, b in zip(rstore, rs)))
    rows.append(("eval/store_s", store_s,
                 "TrialStore slab path, one fused dispatch"))
    rows.append(("eval/store_speedup", seq_s / store_s,
                 "sequential / store"))
    rows.append(("eval/store_pred_parity", match_store,
                 "1.0 = per-trial predictions identical to per-event"))
    rows.append(("eval/slice_ops_per_event", float(ops_event),
                 "python-level evidence reslices, batched per-event path"))
    rows.append(("eval/slice_ops_store", float(ops_store),
                 "slab fancy-index gathers, store path"))
    return rows
