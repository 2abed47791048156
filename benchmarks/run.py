"""Benchmark harness — one section per paper table/figure.

Prints ``name,value,derived`` CSV rows and writes per-section JSON
artifacts (BENCH_kernels.json, BENCH_fleet.json, EVAL_scorecard.json) so
the perf trajectory is tracked across PRs.  Usage:
    PYTHONPATH=src python -m benchmarks.run            # everything
    PYTHONPATH=src python -m benchmarks.run --only table3,fig2a
    PYTHONPATH=src python -m benchmarks.run --only kernel,fleet --json-dir .
    PYTHONPATH=src python -m benchmarks.run --smoke    # <30 s perf canary

``--smoke`` exercises every benchmark family (kernel, sweep, fleet+eval,
scenario scorecard) at tiny sizes without writing JSON artifacts — the
fail-fast regression canary tier-1 runs via tests/test_bench_smoke.py.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import time


def _emit(rows):
    for name, value, derived in rows:
        print(f"{name},{value:.6g},{derived}")
    return rows


def _write_json(path: str, rows) -> None:
    doc = {name: {"value": value, "derived": derived}
           for name, value, derived in rows}
    os.makedirs(os.path.dirname(path) or ".", exist_ok=True)
    with open(path, "w") as f:
        json.dump(doc, f, indent=2, sort_keys=True)
        f.write("\n")
    print(f"# wrote {path} ({len(doc)} rows)", file=sys.stderr)


def smoke() -> list:
    """All perf-path families at tiny sizes: kernel microbench, engine
    sweep, fleet + event-batched eval, and the scenario scorecard (parity
    bits + headline operational metrics).  Returns the combined rows (also
    printed as CSV)."""
    from benchmarks import fleetbench, kernelbench, scorecard

    rows = _emit(kernelbench.kernel_microbench(B=4, M=8, N=256, K=10,
                                               detect_h=64))
    rows += _emit(kernelbench.tile_sweep_rows())
    rows += _emit(fleetbench.sweep_rows(n_trials=1, reps=1))
    rows += _emit(fleetbench.sweep_slab_rows(n_per_class=1, reps=1,
                                             fleet_hosts=32))
    rows += _emit(fleetbench.fleet_rows(batch_sizes=(16,), reps=1,
                                        sequential_baseline=False))
    rows += _emit(fleetbench.shard_rows(parity_hosts=24, storm_hosts=(64,),
                                        shard_hosts=16, reps=1))
    rows += _emit(fleetbench.incremental_rows(batch_sizes=(8,),
                                              shard_batch=0))
    rows += _emit(fleetbench.live_rows(n_hosts=4, reps=1, storm_s=0.2))
    rows += _emit(fleetbench.eval_rows(n_per_class=1, reps=1))
    rows += _emit(fleetbench.chaos_rows(reps=1))
    rows += _emit(fleetbench.restart_rows(reps=1))
    rows += _emit(scorecard.smoke_rows())
    return rows


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default="",
                    help="comma-separated section prefixes to run")
    ap.add_argument("--json-dir", default=".",
                    help="directory for BENCH_*.json artifacts")
    ap.add_argument("--smoke", action="store_true",
                    help="tiny-size run of all perf families, no JSON")
    args = ap.parse_args()
    want = [s for s in args.only.split(",") if s]
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()

    def on(name: str) -> bool:
        return not want or any(name.startswith(w) for w in want)

    t0 = time.time()
    print("name,value,derived")

    if args.smoke:
        smoke()
        print(f"# smoke total {time.time() - t0:.1f}s", file=sys.stderr)
        return

    from benchmarks import diagnostics, fleetbench, kernelbench, roofline

    if on("table3"):
        _emit(diagnostics.table3_diagnostic())
    if on("table2"):
        _emit(diagnostics.table2_comparison())
    if on("table4"):
        _emit(diagnostics.table4_confusion())
    if on("fig2a"):
        _emit(diagnostics.fig2_overhead())
    if on("ablation"):
        _emit(diagnostics.ablation_probes())
    if on("kernel"):
        rows = _emit(kernelbench.kernel_microbench())
        rows += _emit(kernelbench.tile_sweep_rows())
        _write_json(os.path.join(args.json_dir, "BENCH_kernels.json"), rows)
    if on("fleet"):
        rows = _emit(fleetbench.sweep_rows())
        rows += _emit(fleetbench.sweep_slab_rows())
        rows += _emit(fleetbench.fleet_rows())
        rows += _emit(fleetbench.shard_rows())
        rows += _emit(fleetbench.incremental_rows())
        rows += _emit(fleetbench.live_rows())
        rows += _emit(fleetbench.eval_rows())
        rows += _emit(fleetbench.chaos_rows())
        rows += _emit(fleetbench.restart_rows())
        _write_json(os.path.join(args.json_dir, "BENCH_fleet.json"), rows)
    if on("roofline"):
        _emit(roofline.roofline_rows())
    if on("scorecard"):
        from benchmarks import scorecard
        doc = scorecard.build_scorecard()
        _emit(scorecard.scorecard_rows(doc))
        scorecard.write(doc, os.path.join(args.json_dir,
                                          "EVAL_scorecard.json"))

    print(f"# total {time.time() - t0:.1f}s", file=sys.stderr)


if __name__ == "__main__":
    main()
