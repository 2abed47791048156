"""The comparison that decides ``correct``, driven through the harness at a
tiny size on the CPU (Pallas in interpret mode; the harness's look for a
chip is skipped by calling ``run_cell`` directly).

* sound runs of both entries come out correct;
* the control — the reference on bfloat16-rounded telemetry in the
  program's place — comes out not correct;
* with the timed path broken underneath, ``correct`` comes out false, once
  for each fault the cell can have: a round that returns its state
  unchanged, half of the hosts left out of detection, the exchange of one
  rack's candidates left out (sharded entry), and an answer altered where
  the fused kernel produces it.
"""
import json
import time
from pathlib import Path

import numpy as np
import pytest

from bench import check, run

ROOT = Path(__file__).resolve().parents[2]
SEED = 3_000_000_019


def tiny(config: str, traffic: str):
    cfg = json.loads((ROOT / "bench/configs" / f"{config}.json").read_text())
    tr = json.loads((ROOT / "bench/traffic" / f"{traffic}.json").read_text())
    tr.update(quiet_pool=3, fault_pool=2)
    if tr["fault_every"]:
        tr["fault_every"] = 4
    if cfg["entry"] == "sharded":
        cfg.update(hosts=64, shard_hosts=16, rack_shards=2, rca_top_k=4,
                   distinct_timelines=2)
    else:
        cfg.update(hosts=32)
    cell = {"name": f"{config}-{traffic}", "config": config,
            "traffic": traffic, "chips": 1}
    spec = json.loads((ROOT / "BENCHMARK.json").read_text())
    return cell, cfg, tr, spec


def go(config, traffic, seconds=1.5):
    cell, cfg, tr, spec = tiny(config, traffic)
    return run.run_cell(cell, cfg, tr, spec, SEED, seconds, False,
                        time.perf_counter(), log=lambda s: None)


@pytest.mark.parametrize("config,traffic", [
    ("argus16k", "storm"), ("superpod1k", "storm"), ("argus16k", "quiet")])
def test_sound_run_is_correct(config, traffic):
    res = go(config, traffic)
    assert res["correct"], res["checks"]
    assert res["attempted"] > 0 and res["failed"] == 0


@pytest.mark.parametrize("config", ["argus16k", "superpod1k"])
def test_control_is_not_correct(config):
    import importlib
    cell, cfg, tr, spec = tiny(config, "storm")
    entry = importlib.import_module(
        f"bench.entries.{cfg['entry']}").Entry(cfg, tr, SEED)
    entry.setup()
    entry.release()
    g = entry.geometry(entry.channels)
    rd = check.control_readings(entry, g, cfg["rca_top_k"],
                                check.check_sample(SEED, 40, 24))
    assert not check.judge(rd, cfg["limits"]), rd


# ------------------------------------------------------------------ faults
def _stale(monkeypatch, mod):
    orig = mod.Entry.round
    first = {}

    def round_(self, k):
        if "fd" not in first:
            first["fd"] = orig(self, k)
        return first["fd"]
    monkeypatch.setattr(mod.Entry, "round", round_)


def _half(monkeypatch, mod):
    from repro.monitor.fleet import FleetMonitor
    orig = FleetMonitor._detect_round

    def detect(self, host_data, *a, **k):
        scores, cand, onset, q = orig(self, host_data, *a, **k)
        keep = cand < host_data.shape[0] // 2
        scores = np.array(scores)
        scores[host_data.shape[0] // 2:] = 0.0
        return scores, cand[keep], onset[keep], q
    monkeypatch.setattr(FleetMonitor, "_detect_round", detect)


def _exchange(monkeypatch, mod):
    from repro.monitor.shard import ShardedFleetMonitor
    orig = ShardedFleetMonitor._finish_round

    def finish(self, ts, channels, li, T, wn, bn, scores, cand, onset,
               qhosts, *a, **k):
        a0, b0 = self.plan.bounds[self.plan.racks[0][-1]]
        keep = cand < b0
        return orig(self, ts, channels, li, T, wn, bn, scores, cand[keep],
                    onset[keep], qhosts, *a, **k)
    monkeypatch.setattr(ShardedFleetMonitor, "_finish_round", finish)


def _altered(monkeypatch, mod):
    from repro.kernels.fused import ops as fused_ops
    orig = fused_ops.fused_rca_max

    def fused(*a, **k):
        s, c, lag = orig(*a, **k)
        c = np.array(c)
        c[0] = np.minimum(c[0] + 0.05, 1.0)
        return s, c, lag
    monkeypatch.setattr(fused_ops, "fused_rca_max", fused)


FAULTS = {"state_unchanged": _stale, "half_hosts": _half,
          "exchange_left_out": _exchange, "answer_altered": _altered}


@pytest.mark.parametrize("config,fault", [
    ("argus16k", "state_unchanged"), ("argus16k", "half_hosts"),
    ("argus16k", "exchange_left_out"), ("argus16k", "answer_altered"),
    ("superpod1k", "state_unchanged"), ("superpod1k", "half_hosts"),
    ("superpod1k", "answer_altered")])
def test_fault_is_not_correct(monkeypatch, config, fault):
    import importlib
    cell, cfg, tr, spec = tiny(config, "storm")
    mod = importlib.import_module(f"bench.entries.{cfg['entry']}")
    FAULTS[fault](monkeypatch, mod)
    res = run.run_cell(cell, cfg, tr, spec, SEED, 1.5, False,
                       time.perf_counter(), log=lambda s: None)
    assert not res["correct"], res["checks"]
