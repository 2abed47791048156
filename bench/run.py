"""One run of one benchmark cell, on the chip it is started on.

    python3 bench/run.py --workload <cell> --seed <n> --seconds <s> --trace <0|1>

Reads the cell from ``BENCHMARK.json``, its configuration from
``bench/configs/<name>.json`` and its traffic mix from
``bench/traffic/<name>.json``; the configuration's ``entry`` names the
module in ``bench/entries/`` that drives its entry point.  Set-up makes
the stream from ``--seed``, builds the monitor, warms every shape the
traffic uses, then rounds run back to back for ``--seconds`` (the round
in progress finishes).  Each round diagnoses the trailing window one
cadence later than the last, so the stream advances on the absolute
100 Hz grid.

``--trace 0`` reports the cell's end-to-end metrics, ``--trace 1`` its
per-layer metrics (``bench/metrics/<name>.py``, one reader each) from a
profiler trace of the window.  After the window the program's state is
freed and a sample of the rounds, drawn from the seed, is compared with
the plain reference (``bench/check.py``); the numbers compared and their limits are the last
lines on stderr and the ``checks`` key of the result.  The last line of
stdout is the result, as one JSON object.  Without a TPU, or with fewer
chips than the cell asks for, the run exits non-zero and prints none.
"""
from __future__ import annotations

import time

T_PROCESS = time.perf_counter()

import argparse  # noqa: E402
import contextlib  # noqa: E402
import gc  # noqa: E402
import importlib  # noqa: E402
import importlib.util  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import shutil  # noqa: E402
import sys  # noqa: E402
import types  # noqa: E402
from pathlib import Path  # noqa: E402

import numpy as np  # noqa: E402

ROOT = Path(__file__).resolve().parents[1]
BENCH = ROOT / "bench"
OUT = BENCH / ".out"
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

from bench import check  # noqa: E402

#: harness spans the trace reduction attributes idle device time to
SPANS = ("round", "provider", "generator_push", "assemble",
         "detect_round", "gather_evidence", "rca_from_evidence",
         "finish_round")

_COMPILES = {"compiles": 0, "backend_s": 0.0, "cache_hits": 0}
_LISTENING = []


def _listen() -> None:
    """Count backend compiles and persistent-cache hits (once per
    process)."""
    if _LISTENING:
        return
    import jax

    def on_duration(event: str, duration: float, **_) -> None:
        if event == "/jax/core/compile/backend_compile_duration":
            _COMPILES["compiles"] += 1
            _COMPILES["backend_s"] += duration

    def on_event(event: str, **_) -> None:
        if event == "/jax/compilation_cache/cache_hits":
            _COMPILES["cache_hits"] += 1

    jax.monitoring.register_event_duration_secs_listener(on_duration)
    jax.monitoring.register_event_listener(on_event)
    _LISTENING.append(True)


def load_json(path: Path) -> dict:
    with open(path) as f:
        return json.load(f)


def load_cell(name: str):
    """(cell, config, traffic, benchmark spec) for workload ``name``."""
    spec = load_json(ROOT / "BENCHMARK.json")
    cells = {w["name"]: w for w in spec["workloads"]}
    if name not in cells:
        raise SystemExit(f"unknown workload {name!r}")
    cell = cells[name]
    cfg_entry = {c["name"]: c for c in spec["configs"]}[cell["config"]]
    cfg = load_json(ROOT / cfg_entry["file"])
    traffic = load_json(BENCH / "traffic" / f"{cell['traffic']}.json")
    return cell, cfg, traffic, spec


def applies(metric: dict, cell: dict, spec: dict) -> bool:
    """Whether ``metric`` is reported in ``cell``."""
    if "workloads" in metric:
        return cell["name"] in metric["workloads"]
    if "moves" in metric:
        moved = {m["name"]: m for m in spec["end_to_end"]}[metric["moves"]]
        return applies(moved, cell, spec)
    return True


def load_reader(name: str):
    path = BENCH / "metrics" / f"{name}.py"
    sp = importlib.util.spec_from_file_location(
        "bench_metric_" + name.replace(".", "_").replace("-", "_"), path)
    mod = importlib.util.module_from_spec(sp)
    sp.loader.exec_module(mod)
    return mod.read


def _instrument(monitor, span) -> None:
    """Harness spans around the monitor's stages (traced runs only):
    instance attributes shadow the methods, the program is unchanged."""
    for name in ("detect_round", "gather_evidence", "rca_from_evidence",
                 "finish_round"):
        fn = getattr(monitor, "_" + name)

        def wrapped(*a, _fn=fn, _name=name, **k):
            with span(_name):
                return _fn(*a, **k)
        setattr(monitor, "_" + name, wrapped)


def run_cell(cell: dict, cfg: dict, traffic: dict, spec: dict, seed: int,
             seconds: float, trace: bool, t_start: float,
             log=print) -> dict:
    """Set up, measure, check; returns the result object."""
    import jax
    from repro.launch.cache import enable_compile_cache
    enable_compile_cache()
    _listen()
    if trace:
        span = jax.profiler.TraceAnnotation
    else:
        def span(name):
            return contextlib.nullcontext()
    entry_mod = importlib.import_module(f"bench.entries.{cfg['entry']}")
    entry = entry_mod.Entry(cfg, traffic, seed, span=span)

    # ---------------------------------------------------------- set-up
    t0 = time.perf_counter()
    entry.setup()
    t_data = time.perf_counter() - t0
    sizes = entry.fused_batches()
    t1 = time.perf_counter()
    entry.warm_fused(sizes)
    t_fused = time.perf_counter() - t1
    fds, walls_warm = [], []
    for k in range(int(cfg["warmup_rounds"])):
        entry.prepare(k)
        t2 = time.perf_counter()
        fds.append(entry.round(k))
        walls_warm.append(time.perf_counter() - t2)
    log(f"set-up: stream+monitor {t_data:.3f} s, fused warm-up "
        f"{t_fused:.3f} s for batch sizes {sizes}, warm-up rounds "
        f"{[round(w, 4) for w in walls_warm]} s; compiles so far "
        f"{_COMPILES['compiles']} ({_COMPILES['backend_s']:.3f} s backend),"
        f" persistent-cache hits {_COMPILES['cache_hits']}")
    if trace:
        _instrument(entry.monitor, span)
        if OUT.joinpath("trace").exists():
            shutil.rmtree(OUT / "trace")
        opts = jax.profiler.ProfileOptions()
        opts.python_tracer_level = 0
        opts.host_tracer_level = 1
        jax.profiler.start_trace(str(OUT / "trace"), profiler_options=opts)

    # ---------------------------------------------------------- window
    c0 = dict(_COMPILES)
    before = entry.counters()
    rounds = []
    k = len(fds)
    t_first = time.perf_counter()
    setup_s = t_first - t_start
    t_end = t_first + float(seconds)
    while True:
        g0 = time.perf_counter()
        entry.prepare(k)
        g1 = time.perf_counter()
        with span("round"):
            fd = entry.round(k)
        g2 = time.perf_counter()
        fds.append(fd)
        rec = {"k": k, "wall": g2 - g1, "generator_s": g1 - g0,
               "stages": dict(fd.stage_seconds),
               "flagged": len(fd.flagged_hosts), "rca_n": len(fd.diagnoses)}
        rec.update(entry.round_extra())
        rounds.append(rec)
        k += 1
        if g2 >= t_end:
            break
    t_close = time.perf_counter()
    after = entry.counters()
    in_window = {n: _COMPILES[n] - c0[n] for n in _COMPILES}
    trace_sum = None
    if trace:
        jax.profiler.stop_trace()
    dev = jax.devices()[0]
    peak = int((dev.memory_stats() or {}).get("peak_bytes_in_use", -1))
    walls = np.array([r["wall"] for r in rounds])
    log(f"window: {len(rounds)} rounds in {t_close - t_first:.3f} s; round "
        f"median {np.median(walls):.4f} s, min {walls.min():.4f}, max "
        f"{walls.max():.4f}; generator per round "
        f"{np.mean([r['generator_s'] for r in rounds]) * 1e3:.3f} ms; "
        f"flagged per round {min(r['flagged'] for r in rounds)}-"
        f"{max(r['flagged'] for r in rounds)}, RCA'd "
        f"{sorted(set(r['rca_n'] for r in rounds))}")
    log(f"compiles inside the window: {in_window['compiles']} "
        f"({in_window['backend_s']:.3f} s backend), persistent-cache hits "
        f"{in_window['cache_hits']}")
    if trace:
        from bench import trace_reduce
        trace_sum = trace_reduce.reduce_dir(OUT / "trace", SPANS)

    # ---------------------------------------------------------- metrics
    run = types.SimpleNamespace(
        cell=cell, cfg=cfg, hosts=entry.hosts, rounds=rounds,
        counters_before=before, counters_after=after, trace=trace_sum,
        shard_rows=list(entry.shard_rows), wn=entry.wn, bn=entry.bn,
        geometry=entry.geometry(entry.channels), device_kind=dev.device_kind,
        bench_dir=BENCH)
    metrics = {}
    kind = "per_layer" if trace else "end_to_end"
    for m in spec[kind]:
        if not applies(m, cell, spec):
            continue
        if kind == "end_to_end":
            v = {"setup_s": setup_s,
                 "hosts_per_s": entry.hosts * len(walls) / float(walls.sum()),
                 "round_p95_s": float(np.percentile(walls, 95))}[m["name"]]
        else:
            v = load_reader(m["name"])(run)
        if v is not None:
            metrics[m["name"]] = {"value": float(v), "unit": m["unit"]}

    # ---------------------------------------------------------- check
    t3 = time.perf_counter()
    n_rounds = len(fds)
    entry.last_round = n_rounds - 1
    sample = check.check_sample(seed, n_rounds, int(cfg["check_rounds"]))
    prog = {i: check.normalize(fds[i], entry.tick_end(i), entry.wn,
                               entry.rate) for i in sample}
    staging = entry.staging_diff()
    del fds
    entry.release()
    gc.collect()
    g = entry.geometry(entry.channels)
    refr = check.reference_rounds(
        entry, sample, g, cfg["rca_top_k"], "float64",
        extra_rca={i: list(prog[i]["rca"]) for i in sample})
    per_round = {i: check.compare(prog[i], refr[i], g) for i in sample}
    readings = check.fold(per_round.values())
    if staging is not None:
        readings["staging_diff"] = staging
    limits = cfg["limits"]
    correct = check.judge(readings, limits)
    failed = sum(1 for i, r in per_round.items()
                 if i >= int(cfg["warmup_rounds"])
                 and any(r[n] > limits.get(n, 0) for n in check.EXACT
                         if n in r))
    log(f"check: {len(sample)} of {n_rounds} rounds against the "
        f"reference in {time.perf_counter() - t3:.3f} s")

    result = {
        "correct": bool(correct), "attempted": len(rounds),
        "failed": int(failed), "metrics": metrics,
        "device": {"platform": dev.platform, "kind": dev.device_kind,
                   "count": len(jax.devices()), "memory_peak_bytes": peak},
    }
    if trace_sum is not None:
        result["device"]["busy_s"] = trace_sum.busy_s
        result["device"]["window_s"] = trace_sum.window_s
        result["breakdown"] = trace_sum.breakdown()
    result["checks"] = {n: {"value": readings.get(n), "limit": lim}
                        for n, lim in limits.items()}
    return result


def main() -> None:
    ap = argparse.ArgumentParser(description=__doc__.splitlines()[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args()
    os.environ.setdefault("TPU_LOG_DIR", "disabled")
    cell, cfg, traffic, spec = load_cell(args.workload)
    import jax
    devs = jax.devices()
    if devs[0].platform != "tpu" or len(devs) < int(cell["chips"]):
        print(f"no result: the cell needs {cell['chips']} TPU chip(s), JAX "
              f"found {len(devs)} {devs[0].platform} device(s)",
              file=sys.stderr)
        raise SystemExit(3)
    result = run_cell(cell, cfg, traffic, spec, args.seed, args.seconds,
                      bool(args.trace), T_PROCESS,
                      log=lambda s: print(s, flush=True))
    for n, c in result["checks"].items():
        print(f"check {n}: {c['value']} (limit {c['limit']})",
              file=sys.stderr)
    print(f"correct: {result['correct']}", file=sys.stderr, flush=True)
    print(json.dumps(result), flush=True)


if __name__ == "__main__":
    main()
