"""Readings that the limits of ``correct`` are set from, on the chip.

    python3 bench/control.py --workload <cell> --seconds <s> \
        --seeds 11 12 ... --control-seeds 21 22 23 [--rounds N]

In one process: for every ``--seeds`` seed, one run of the cell (set-up,
window, comparison with the reference) and its readings; for every
``--control-seeds`` seed, the control — the plain reference computed on
bfloat16-rounded telemetry, put in the program's place — over ``--rounds``
rounds of that seed's stream (the rounds a run makes), and its readings
against the float64 reference.  The benchmark's own runs never run the
control.  Each reading is printed as one JSON line.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import importlib  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
from pathlib import Path  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check, run  # noqa: E402


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--seeds", type=int, nargs="*", default=[])
    ap.add_argument("--control-seeds", type=int, nargs="*", default=[])
    ap.add_argument("--rounds", type=int, default=0,
                    help="rounds per control stream (default: as many "
                         "as the last program run made)")
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell, cfg, traffic, spec = run.load_cell(args.workload)
    import jax
    if jax.devices()[0].platform != "tpu":
        raise SystemExit("control readings are taken on the chip")
    n_rounds = args.rounds
    for seed in args.seeds:
        t = time.perf_counter()
        res = run.run_cell(cell, cfg, traffic, spec, seed, args.seconds,
                           False, t, log=lambda s: print(s, flush=True))
        n_rounds = n_rounds or (res["attempted"]
                                + int(cfg["warmup_rounds"]))
        print(json.dumps({"side": "program", "seed": seed,
                          "correct": res["correct"],
                          "rounds": res["attempted"],
                          "readings": {k: v["value"] for k, v in
                                       res["checks"].items()}}), flush=True)
    entry_mod = importlib.import_module(f"bench.entries.{cfg['entry']}")
    for seed in args.control_seeds:
        t = time.perf_counter()
        entry = entry_mod.Entry(cfg, traffic, seed)
        entry.setup()
        entry.release()
        g = entry.geometry(entry.channels)
        n = n_rounds or 100
        sample = check.check_sample(seed, n, int(cfg["check_rounds"]))
        rd = check.control_readings(entry, g, cfg["rca_top_k"], sample)
        print(json.dumps({"side": "control", "seed": seed, "rounds": n,
                          "seconds": time.perf_counter() - t,
                          "readings": rd}), flush=True)


if __name__ == "__main__":
    main()
