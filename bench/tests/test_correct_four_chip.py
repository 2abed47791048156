"""``argus64k``'s comparison that decides ``correct``, driven through the
harness at ``test_correct.tiny``'s cut on four CPU devices (a subprocess,
since the device count is fixed when JAX starts): shards run on devices
``s mod 4`` with their sweeps side by side.

* a sound run comes out correct, on four devices;
* the control comes out not correct;
* a round that returns its state unchanged, and one rack's candidates
  left out of the exchange, come out not correct.

    JAX_PLATFORMS=cpu python -m pytest bench/tests/test_correct_four_chip.py -q
"""
import json
import os
import subprocess
import sys
from pathlib import Path

import pytest

HERE = Path(__file__).resolve()
ROOT = HERE.parents[2]


def _cases() -> dict:
    """Run every case in this process (four host devices); returns the
    readings of each."""
    import importlib
    import time

    import jax
    import test_correct as tc
    from bench import check, run

    cell, cfg, tr, spec = tc.tiny("argus64k", "storm")
    cell["chips"] = 4
    mod = importlib.import_module(f"bench.entries.{cfg['entry']}")

    def go():
        res = run.run_cell(cell, cfg, tr, spec, tc.SEED, 1.5, False,
                           time.perf_counter(), log=lambda s: None)
        return {"correct": res["correct"], "checks": res["checks"],
                "attempted": res["attempted"], "failed": res["failed"],
                "devices": res["device"]["count"]}

    out = {"sound": go(), "devices": len(jax.devices())}
    entry = mod.Entry(cfg, tr, tc.SEED)
    entry.setup()
    out["shard_chips"] = [d.id for d in entry.monitor.devices]
    entry.release()
    rd = check.control_readings(entry, entry.geometry(entry.channels),
                                cfg["rca_top_k"],
                                check.check_sample(tc.SEED, 40, 24))
    out["control"] = {"correct": check.judge(rd, cfg["limits"]),
                      "checks": rd}
    for fault in ("state_unchanged", "exchange_left_out"):
        with pytest.MonkeyPatch.context() as mp:
            tc.FAULTS[fault](mp, mod)
            out[fault] = go()
    return out


@pytest.fixture(scope="module")
def four():
    env = dict(os.environ, JAX_PLATFORMS="cpu",
               XLA_FLAGS="--xla_force_host_platform_device_count=4")
    res = subprocess.run([sys.executable, str(HERE)], capture_output=True,
                         text=True, timeout=900, env=env, cwd=str(ROOT))
    assert res.returncode == 0, res.stderr[-3000:]
    line = [ln for ln in res.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


def test_sound_run_on_four_devices_is_correct(four):
    assert four["devices"] == 4 and four["sound"]["devices"] == 4
    assert four["shard_chips"] == [s % 4 for s in range(4)]
    assert four["sound"]["correct"], four["sound"]["checks"]
    assert four["sound"]["attempted"] > 0 and four["sound"]["failed"] == 0


@pytest.mark.parametrize("case", ["control", "state_unchanged",
                                  "exchange_left_out"])
def test_control_and_faults_are_not_correct(four, case):
    assert not four[case]["correct"], four[case]["checks"]


if __name__ == "__main__":
    for p in (str(HERE.parent), str(ROOT), str(ROOT / "src")):
        sys.path.insert(0, p)
    print("RESULT " + json.dumps(_cases(), default=float))
