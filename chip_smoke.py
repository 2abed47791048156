"""Bring-up check: the fleet monitor's round on a TPU, through its entry
points, at fleet sizes its users run.

    python chip_smoke.py              # one chip
    python chip_smoke.py --four-chip  # the sharded round over four chips

One process owns the chip(s) and starts no other.  Every input is made
from seeds by ``repro.sim``.  Any failed check exits non-zero before the
last line; on success the last line of stdout is one JSON object naming
the device as JAX reports it.

One chip:

  * device — the first JAX device must be a TPU;
  * live — ``FleetAggregator`` over 64 simulated agents on the virtual
    clock, one host with a seeded NIC fault, diagnosed by
    ``FleetMonitor()`` (Pallas kernels on): that host must be the
    straggler, with top cause NIC;
  * ARGUS-scale — ``ShardedFleetMonitor`` over 16384 hosts (16 shards of
    1024, 22 channels at 100 Hz, every 16th host faulted), a warm-up and
    3 rounds through ``diagnose_sharded``: flagged hosts and onsets must
    equal f64 ``detect_rows`` on the host, and top causes, mitigations
    and quarantine must equal the oracle monitor's
    (``use_kernels=False``);
  * kernels — the round's sweep and fused dispatches must lower to
    ``tpu_custom_call`` (compiled Mosaic kernels, not the interpreter).

``--four-chip`` runs only the 65536-host sharded round placed round-robin
on four chips, against the same plan pinned to one chip: the verdict
fingerprints must be equal and every chip must have run its shards.
Timings printed on the way are informative, not benchmark numbers.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time

import numpy as np

sys.path.insert(0, os.path.join(os.path.dirname(os.path.abspath(__file__)),
                                "src"))

import jax  # noqa: E402

from repro.core.engine import EngineConfig  # noqa: E402
from repro.core.spike import detect_rows  # noqa: E402
from repro.core.taxonomy import CauseClass  # noqa: E402
from repro.kernels import tuning  # noqa: E402
from repro.kernels.detect import ops as detect_ops  # noqa: E402
from repro.kernels.fused import ops as fused_ops  # noqa: E402
from repro.kernels.sweep import ops as sweep_ops  # noqa: E402
from repro.launch.cache import enable_compile_cache  # noqa: E402
from repro.monitor.aggregator import FleetAggregator  # noqa: E402
from repro.monitor.fleet import FleetMonitor  # noqa: E402
from repro.monitor.shard import (  # noqa: E402
    ShardedFleetMonitor, ShardPlan, verdict_fingerprint,
)
from repro.sim.scenario import make_trial  # noqa: E402
from repro.telemetry.agent import TelemetryAgent  # noqa: E402
from repro.telemetry.collectors import SimCollector  # noqa: E402

#: live phase: seed and faulted host; with these the oracle monitor (and
#: the kernel path, interpreted on the CPU) names that host, cause NIC
LIVE_SEED = 4200
LIVE_BAD_HOST = 37
#: storm profile: every STORM_EVERY-th host carries the NIC fault
STORM_EVERY = 16
#: fleet sizes: one chip, and four chips
ARGUS_HOSTS = 16384
FOUR_CHIP_HOSTS = 65536
#: timed rounds after the warm-up
ROUNDS = 3

_COMPILE = {"backend_s": 0.0, "compiles": 0, "cache_hits": 0}


def _on_duration(event: str, duration: float, **_) -> None:
    if event == "/jax/core/compile/backend_compile_duration":
        _COMPILE["backend_s"] += duration
        _COMPILE["compiles"] += 1


def _on_event(event: str, **_) -> None:
    if event == "/jax/compilation_cache/cache_hits":
        _COMPILE["cache_hits"] += 1


def check(ok: bool, what: str) -> None:
    """Exit non-zero, printing no result, unless ``ok``."""
    if not ok:
        raise SystemExit(f"FAIL: {what}")
    print(f"ok: {what}", flush=True)


def require_tpu(n_chips: int) -> list:
    """The device check: the first device is a TPU and there are enough."""
    devs = jax.devices()
    d0 = devs[0]
    print(f"device: platform={d0.platform} kind={d0.device_kind} "
          f"count={len(devs)}", flush=True)
    check(d0.platform == "tpu", f"JAX runs on a TPU (got {d0.platform})")
    check(len(devs) >= n_chips, f"{n_chips} chip(s) present")
    return devs


# ---------------------------------------------------------------- live path
def live_phase() -> None:
    """Aggregator -> FleetMonitor on a live virtual-clock fleet of 64
    agents.  The staged window covers the 20 s baseline plus the 5 s
    detection window; the fault starts at 40 s."""
    cfg = EngineConfig()
    window_s = cfg.baseline_s + cfg.window_s
    agents = []
    for h in range(64):
        t = make_trial(LIVE_SEED + h, "nic", duration_s=50.0, t_on=40.0,
                       intensity=2.0 if h == LIVE_BAD_HOST else 0.0,
                       confuser_prob=0.0)
        agents.append(TelemetryAgent([SimCollector(t.channels, t.ts, t.data)],
                                     rate_hz=t.rate_hz, history_s=30.0))
    agg = FleetAggregator(agents, window_s=window_s)
    agg.run_virtual(0.0, 46.0)
    fd = agg.diagnose(FleetMonitor())
    check(fd is not None and fd.straggler_host == LIVE_BAD_HOST,
          f"live: straggler is the injected host {LIVE_BAD_HOST}")
    check(fd.diagnosis is not None
          and fd.diagnosis.top_cause == CauseClass.NIC,
          "live: top cause NIC")


# ---------------------------------------------------------- ARGUS-scale round
def storm_pool(seed: int = 9300, t_on: float = 28.0, clip_s: float = 34.0):
    """(ts, (24, C, T) f32 pool, channels, n_quiet): 16 quiet hosts and 8
    NIC-faulted ones, clipped so the fault sits in the trailing window."""
    quiet = [make_trial(seed + u, "nic", intensity=0.0, t_on=t_on,
                        confuser_prob=0.0) for u in range(16)]
    bad = [make_trial(seed + 777 + u, "nic", intensity=2.0, t_on=t_on,
                      confuser_prob=0.0) for u in range(8)]
    t_hi = int(clip_s * quiet[0].rate_hz)
    pool = np.stack([t.data[:, :t_hi] for t in quiet + bad]).astype(np.float32)
    return quiet[0].ts[:t_hi], pool, quiet[0].channels, len(quiet)


def storm_provider(plan: ShardPlan, pool: np.ndarray, n_quiet: int):
    """Shard provider: host h replays pool trial ``h % n_quiet``, or a
    faulted one when ``h % STORM_EVERY == 0``."""
    n_bad = pool.shape[0] - n_quiet

    def provider(s: int):
        a, b = plan.bounds[s]
        h = np.arange(a, b)
        idx = np.where(h % STORM_EVERY == 0,
                       n_quiet + (h // STORM_EVERY) % n_bad, h % n_quiet)
        return pool[idx], None
    return provider


class FirstCall:
    """Wraps ``module.name`` (a jitted function) while active, keeping the
    arguments of its first call so the dispatch can be lowered again."""

    def __init__(self, module, name: str):
        self.module, self.name = module, name
        self.fn = getattr(module, name)
        self.args = None

    def __enter__(self):
        def record(*a, **k):
            if self.args is None:
                self.args = (a, k)
            return self.fn(*a, **k)
        setattr(self.module, self.name, record)
        return self

    def __exit__(self, *exc) -> None:
        setattr(self.module, self.name, self.fn)

    def lowered_text(self) -> str:
        a, k = self.args
        return self.fn.lower(*a, **k).as_text()


def _detect_reference(plan, provider, li, wn, bn, cfg):
    """f64 ``detect_rows`` over every shard's latency tail, on the host:
    (fire, onset) for all hosts."""
    fire, onset = [], []
    for s in range(plan.n_shards):
        slab, _ = provider(s)
        T = slab.shape[2]
        tail = np.asarray(slab[:, li, T - wn - bn:T], np.float64)
        f, _, o = detect_rows(tail[:, bn:], tail[:, :bn], cfg.threshold,
                              cfg.persistence)
        fire.append(f)
        onset.append(o)
    return np.concatenate(fire), np.concatenate(onset)


def argus_phase() -> None:
    """The sharded storm round against the f64 detector and the oracle
    monitor; the round's sweep and fused dispatches must lower to
    compiled Mosaic kernels."""
    hosts = ARGUS_HOSTS
    cfg = EngineConfig()
    ts, pool, channels, n_quiet = storm_pool()
    plan = ShardPlan.for_fleet(hosts)
    provider = storm_provider(plan, pool, n_quiet)
    topk = tuning.shard_topk()
    li = list(channels).index(cfg.latency_metric)
    T = ts.size
    wn, bn = cfg.window_n, cfg.baseline_n
    print(f"argus: {hosts} hosts, {plan.n_shards} shards x "
          f"{plan.bounds[0][1]}, {len(channels)} channels x {T} ticks, "
          f"rca_top_k={topk}", flush=True)
    fire_ref, onset_ref = _detect_reference(plan, provider, li, wn, bn, cfg)
    want_flagged = set(np.flatnonzero(fire_ref).tolist())

    mon = ShardedFleetMonitor(plan, rca_top_k=topk)
    oracle = ShardedFleetMonitor(plan, use_kernels=False, rca_top_k=topk)
    walls = []
    conf_diff = 0.0
    nic_share = []
    for r in range(ROUNDS + 1):
        c0 = dict(_COMPILE)
        t0 = time.perf_counter()
        if r == 0:
            with FirstCall(sweep_ops, "_sweep_jit") as sweep_call, \
                    FirstCall(fused_ops, "_fused_rca_max_jit") as fused_call:
                fd = mon.diagnose_sharded(ts, provider, channels)
        else:
            fd = mon.diagnose_sharded(ts, provider, channels)
        wall = time.perf_counter() - t0
        fo = oracle.diagnose_sharded(ts, provider, channels)
        tag = "warm-up" if r == 0 else f"round {r}"
        print(f"argus {tag}: wall {wall:.3f} s, {hosts / wall:.0f} hosts/s, "
              f"backend compile {_COMPILE['backend_s'] - c0['backend_s']:.3f}"
              f" s ({_COMPILE['compiles'] - c0['compiles']} compiles, "
              f"{_COMPILE['cache_hits'] - c0['cache_hits']} cache hits), "
              f"{len(fd.flagged_hosts)} flagged, {len(fd.diagnoses)} RCA'd",
              flush=True)
        if r:
            walls.append(wall)
        check(set(fd.flagged_hosts) == want_flagged,
              f"{tag}: flagged hosts equal f64 detect_rows "
              f"({len(want_flagged)})")
        check(all(fd.diagnoses[h].event.t_onset
                  == float(ts[T - wn + onset_ref[h]]) for h in fd.diagnoses),
              f"{tag}: RCA'd onsets equal f64 detect_rows")
        check(sorted(fd.diagnoses) == sorted(fo.diagnoses) and all(
            fd.diagnoses[h].top_cause == fo.diagnoses[h].top_cause
            for h in fd.diagnoses), f"{tag}: top causes equal the oracle's")
        check(fd.mitigations == fo.mitigations,
              f"{tag}: mitigations equal the oracle's")
        check(fd.quarantined == fo.quarantined,
              f"{tag}: quarantine equals the oracle's")
        for h, d in fd.diagnoses.items():
            ref = {rc.cause: rc.confidence for rc in fo.diagnoses[h].ranked}
            conf_diff = max([conf_diff] + [
                abs(rc.confidence - ref[rc.cause]) for rc in d.ranked
                if rc.cause in ref])
        nic_share.append(np.mean([d.top_cause == CauseClass.NIC
                                  for d in fd.diagnoses.values()]))
    med = float(np.median(walls))
    print(f"argus: median round {med:.3f} s, {hosts / med:.0f} hosts/s; "
          f"RCA'd hosts with top cause NIC: {min(nic_share):.3f}; "
          f"max |confidence - oracle|: {conf_diff:.3e}", flush=True)

    # every host's decision, through the same shard dispatch on the full
    # baseline + window tail (the kernel's widest single-tick width)
    fire_k, onset_k = [], []
    for s in range(plan.n_shards):
        slab, _ = provider(s)
        f, _, o = detect_ops.detect_hosts_slab_launch(
            slab[:, li, T - wn - bn:T], wn, bn, cfg.threshold,
            cfg.persistence, device=mon.devices[s]).collect()
        fire_k.append(f)
        onset_k.append(o)
    check(np.array_equal(np.concatenate(fire_k), fire_ref)
          and np.array_equal(np.concatenate(onset_k), onset_ref),
          f"full-tail sweep: fire and onset of all {hosts} hosts equal f64 "
          "detect_rows")
    for name, call in (("sweep", sweep_call), ("fused", fused_call)):
        check(call.args is not None
              and "tpu_custom_call" in call.lowered_text(),
              f"the round's {name} dispatch lowers to tpu_custom_call")


# ----------------------------------------------------------------- 4 chips
def four_chip_phase(devices: list) -> None:
    """Shards round-robin on four devices vs the plan pinned to one."""
    hosts = FOUR_CHIP_HOSTS
    ts, pool, channels, n_quiet = storm_pool()
    plan = ShardPlan.for_fleet(hosts)
    provider = storm_provider(plan, pool, n_quiet)
    topk = tuning.shard_topk()
    devs = list(devices[:4])
    spread = ShardedFleetMonitor(plan, devices=devs, rca_top_k=topk)
    check(all(spread.devices[s] == devs[s % 4]
              for s in range(plan.n_shards)),
          f"{plan.n_shards} shards assigned round-robin to 4 devices")
    peak0 = [_peak(d) for d in devs]
    t0 = time.perf_counter()
    fd4 = spread.diagnose_sharded(ts, provider, channels)
    t4 = time.perf_counter() - t0
    peak1 = [_peak(d) for d in devs]
    print(f"four-chip: {hosts} hosts over 4 devices, first round "
          f"{t4:.3f} s; peak bytes before {peak0} after {peak1}", flush=True)
    check(all(b > a >= 0 for a, b in zip(peak0, peak1)),
          "every chip's peak memory rose during the spread round")
    pinned = ShardedFleetMonitor(plan, devices=devs[:1], rca_top_k=topk)
    t0 = time.perf_counter()
    fd1 = pinned.diagnose_sharded(ts, provider, channels)
    t1 = time.perf_counter() - t0
    print(f"four-chip: same plan pinned to one device, first round "
          f"{t1:.3f} s; {len(fd4.flagged_hosts)} flagged", flush=True)
    check(verdict_fingerprint(fd4) == verdict_fingerprint(fd1),
          "verdict fingerprint equal: four devices vs one")


def _peak(device) -> int:
    """The device's peak bytes in use so far; -1 when not reported."""
    return int((device.memory_stats() or {}).get("peak_bytes_in_use", -1))


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--four-chip", action="store_true",
                    help="run only the sharded round over four chips")
    args = ap.parse_args()
    enable_compile_cache()
    jax.monitoring.register_event_duration_secs_listener(_on_duration)
    jax.monitoring.register_event_listener(_on_event)
    devs = require_tpu(4 if args.four_chip else 1)
    if args.four_chip:
        four_chip_phase(devs)
    else:
        live_phase()
        argus_phase()
    print(f"compile: backend {_COMPILE['backend_s']:.3f} s over "
          f"{_COMPILE['compiles']} compiles, {_COMPILE['cache_hits']} "
          "persistent-cache hits", flush=True)
    d0 = devs[0]
    print(json.dumps({"ok": True, "device": {
        "platform": d0.platform, "kind": d0.device_kind,
        "count": len(devs)}}))


if __name__ == "__main__":
    main()
