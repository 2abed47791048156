"""Device-resident detect windows (``kernels.sweep.ops.DeviceWindows``):
a monitor keeps each slab's (rows, wn) latency window on its device and
puts only the ticks that slid in since the last round.  Every round's
``(fire, score, onset)`` must equal a from-scratch
``detect_hosts_slab_launch`` over the same tail with the same moments, bit
for bit; everything that
drops moment rows drops the window (a full put follows); the periodic
proof finds a carried window that disagrees; and a constant slide
compiles nothing after the first two rounds."""
import json
import os
import subprocess
import sys
import textwrap
import types

import jax
import numpy as np
import pytest

from bench.metrics._spans import window as span_window
from bench import trace_reduce
from benchmarks.fleetbench import _make_fleet
from repro.core.engine import EngineConfig
from repro.kernels.detect import ops as detect_ops
from repro.kernels.sweep import ops as sweep_ops
from repro.monitor import FleetMonitor, ShardedFleetMonitor, ShardPlan

LAT = "coll_allreduce_ms"
#: a short window and baseline so that many rounds fit the 46 s fleet
SMALL = dict(window_s=1.0, baseline_s=4.0)


@pytest.fixture(scope="module")
def fleet():
    ts, data, channels = _make_fleet(24, bad_host=5, seed=3, bad_every=5)
    return ts, data, channels, channels.index(LAT)


@pytest.fixture
def launches(monkeypatch):
    """Every windowed fleet detect launch of the round: its tail, moments
    and, once collected, its ``(fire, score, onset)``."""
    real, calls = detect_ops.detect_window_launch, []

    def spy(window, tail, wn, bn, tick_end, moments, *a):
        rec = dict(tail=np.array(tail), wn=wn, bn=bn, moments=moments)
        calls.append(rec)
        pending = real(window, tail, wn, bn, tick_end, moments, *a)

        def collect(*ca, **ck):
            rec["out"] = pending.collect(*ca, **ck)
            return rec["out"]
        return types.SimpleNamespace(collect=collect)
    monkeypatch.setattr(detect_ops, "detect_window_launch", spy)
    return calls


def _assert_exact(mon, calls):
    """Each windowed launch equals ``detect_hosts_slab_launch`` over the
    same tail with the same moments and no window, bit for bit."""
    for c in calls:
        want = detect_hosts_slab_ref(c, mon)
        for got, ref in zip(c["out"], want):
            assert got.dtype == ref.dtype and np.array_equal(got, ref)
    return len(calls)


def detect_hosts_slab_ref(c, mon):
    return detect_ops.detect_hosts_slab_launch(
        c["tail"], c["wn"], c["bn"], mon.cfg.threshold, mon.cfg.persistence,
        use_kernel=mon.use_kernels, moments=c["moments"]).collect()


def _round(mon, fleet, e, W=2500, valid=None):
    ts, data, channels, _ = fleet
    return mon.diagnose_fleet(ts[e - W:e], data[:, :, e - W:e], channels,
                              valid=valid)


def _puts(mon):
    st = mon.incremental_stats()
    return st["window_delta_puts"], st["window_full_puts"]


@pytest.mark.parametrize("use_kernels", [False, True])
def test_single_slab_rounds_equal_detect_hosts_slab_launch(fleet, launches,
                                                    use_kernels):
    mon = FleetMonitor(use_kernels=use_kernels)
    for k in range(26):
        _round(mon, fleet, 2500 + 50 * k)
    assert _assert_exact(mon, launches) == 26
    st = mon.incremental_stats()
    # the first round fills the window, the 32nd advance would re-prove
    assert _puts(mon) == (25, 1)
    assert st["window_parity_failures"] == 0


_SHARDED = textwrap.dedent("""
    import json, os, sys, types
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=4"
    os.environ["JAX_PLATFORMS"] = "cpu"
    sys.path[:0] = {paths!r}
    import jax
    import numpy as np
    from benchmarks.fleetbench import _make_fleet
    from repro.kernels.detect import ops as detect_ops
    from repro.monitor import (
        FleetMonitor, ShardPlan, ShardedFleetMonitor, verdict_fingerprint)

    HOSTS, SHARD, W, STEP, ROUNDS = 64, 16, 2500, 50, 24
    ts, data, channels = _make_fleet(HOSTS, bad_host=5, seed=11,
                                     bad_every=6)
    real, calls = detect_ops.detect_window_launch, []

    def spy(window, tail, wn, bn, tick_end, moments, *a):
        rec = dict(window=window, tail=np.array(tail), wn=wn, bn=bn,
                   moments=moments)
        calls.append(rec)
        p = real(window, tail, wn, bn, tick_end, moments, *a)

        def collect(*ca, **ck):
            rec["out"] = p.collect(*ca, **ck)
            return rec["out"]
        return types.SimpleNamespace(collect=collect)
    detect_ops.detect_window_launch = spy

    def run(mon):
        del calls[:]
        fps = []
        for r in range(ROUNDS):
            e = W + STEP * r
            t, d = ts[e - W:e], data[:, :, e - W:e]
            if mon.plan is None:
                fd = mon.diagnose_fleet(t, d, channels)
            else:
                fd = mon.diagnose_sharded(
                    t, lambda s: (d[slice(*mon.plan.bounds[s])], None),
                    channels)
            fps.append(json.dumps(verdict_fingerprint(fd), sort_keys=True))
        exact = windowed = 0
        chips = set()
        for c in calls:
            windowed += 1
            chips.add(next(iter(c["window"].x.devices())).id)
            ref = detect_ops.detect_hosts_slab_launch(
                c["tail"], c["wn"], c["bn"], mon.cfg.threshold,
                mon.cfg.persistence, use_kernel=mon.use_kernels,
                moments=c["moments"]).collect()
            exact += all(g.dtype == w.dtype and np.array_equal(g, w)
                         for g, w in zip(c["out"], ref))
        st = mon.incremental_stats()
        return {{"fps": fps, "exact": exact, "windowed": windowed,
                 "chips": sorted(chips),
                 "stats": {{k: v for k, v in st.items()
                            if k.startswith("window_")}}}}

    plan = ShardPlan.for_fleet(HOSTS, shard_hosts=SHARD, rack_shards=2)
    single = FleetMonitor(use_kernels=False)
    single.plan = None
    res = {{"single": run(single)}}
    for n in (1, 4):
        res[str(n)] = run(ShardedFleetMonitor(
            plan, devices=jax.devices()[:n], use_kernels=False))
    print("RESULT " + json.dumps(res))
""")


@pytest.fixture(scope="module")
def sharded_runs(tmp_path_factory):
    """24 sliding rounds of 4 shards x 16 hosts on one device and on
    four forced host devices, and of the single-slab monitor, in a
    subprocess."""
    root = os.path.abspath(os.path.join(os.path.dirname(__file__), ".."))
    script = tmp_path_factory.mktemp("windows") / "probe.py"
    script.write_text(_SHARDED.format(
        paths=[os.path.join(root, "src"), root]))
    out = subprocess.run([sys.executable, script], capture_output=True,
                         text=True, timeout=300)
    assert out.returncode == 0, out.stderr[-3000:]
    line = [ln for ln in out.stdout.splitlines() if ln.startswith("RESULT ")]
    return json.loads(line[-1][len("RESULT "):])


@pytest.mark.parametrize("devices", ["1", "4"])
def test_sharded_rounds_equal_detect_hosts_slab_launch(sharded_runs, devices):
    run = sharded_runs[devices]
    assert run["windowed"] == run["exact"] == 24 * 4
    assert run["chips"] == list(range(int(devices)))
    assert run["fps"] == sharded_runs["single"]["fps"]
    st = run["stats"]
    assert (st["window_full_puts"], st["window_delta_puts"]) == (4, 23 * 4)
    assert st["window_parity_failures"] == 0


def test_single_slab_monitor_matches_in_the_probe(sharded_runs):
    single = sharded_runs["single"]
    assert single["windowed"] == single["exact"] == 24
    assert single["stats"]["window_delta_puts"] == 23


def _warm(mon, fleet, rounds=3, e0=2600):
    for k in range(rounds):
        _round(mon, fleet, e0 + 50 * k)
    return e0 + 50 * rounds


def test_reset_host_drops_the_window(fleet, launches):
    mon = FleetMonitor(use_kernels=False)
    e = _warm(mon, fleet)
    mon.reset_host(7)
    before = _puts(mon)
    _round(mon, fleet, e)
    assert _puts(mon) == (before[0], before[1] + 1)
    assert _assert_exact(mon, launches) == 4


def test_load_state_dict_drops_the_window(fleet, launches):
    mon = FleetMonitor(use_kernels=False)
    e = _warm(mon, fleet)
    mon.load_state_dict(mon.state_dict())
    before = _puts(mon)
    _round(mon, fleet, e)
    assert _puts(mon) == (before[0], before[1] + 1)
    assert _assert_exact(mon, launches) == 4


def test_masked_round_drops_the_window(fleet, launches):
    ts, data, channels, li = fleet
    mon = FleetMonitor(use_kernels=False)
    e = _warm(mon, fleet)
    v = np.ones((24, len(channels), 2500), bool)
    v[3, li, -40:] = False
    before = _puts(mon)
    _round(mon, fleet, e, valid=v)          # the oracle: no window launch
    assert _puts(mon) == before
    _round(mon, fleet, e + 50)
    assert _puts(mon) == (before[0], before[1] + 1)
    assert _assert_exact(mon, launches) == 4


def test_forced_oracle_revisit_drops_every_window(fleet, launches):
    ts, data, channels, li = fleet
    mon = ShardedFleetMonitor(ShardPlan.from_bounds([(0, 8), (8, 16),
                                                     (16, 24)]),
                              use_kernels=False)

    def rnd(e, corrupt=False):
        d = data[:, :, e - 2500:e]

        def provider(s):
            a, b = mon.plan.bounds[s]
            v = None
            if corrupt and s == 2:           # surfaces on the last shard
                v = np.ones(d[a:b].shape, bool)
                v[1, li, -40:] = False
            return d[a:b], v
        return mon.diagnose_sharded(ts[e - 2500:e], provider, channels)

    for k in range(3):
        rnd(2600 + 50 * k)
    assert _puts(mon) == (6, 3)
    # shards 0-1 advance their windows, then are re-visited by the oracle
    rnd(2750, corrupt=True)
    assert _puts(mon) == (8, 3)
    assert not mon._windows._held
    rnd(2800)
    assert _puts(mon) == (8, 6)
    assert _assert_exact(mon, launches) == 3 + 6 + 2 + 3


def test_off_grid_round_drops_the_window(fleet, launches):
    ts, data, channels, _ = fleet
    mon = FleetMonitor(use_kernels=False)
    e = _warm(mon, fleet)
    before = _puts(mon)
    jitter = ts[e - 2500:e] + 0.004          # off the 100 Hz grid
    mon.diagnose_fleet(jitter, data[:, :, e - 2500:e], channels)
    assert _puts(mon) == before              # today's path, no window
    assert not mon._windows._held
    _round(mon, fleet, e + 50)
    assert _puts(mon) == (before[0], before[1] + 1)
    assert _assert_exact(mon, launches) == 4


def test_slide_of_a_whole_window_takes_a_full_put(fleet, launches):
    mon = FleetMonitor(use_kernels=False)
    e = _warm(mon, fleet)
    before = _puts(mon)
    _round(mon, fleet, e - 50 + mon.cfg.window_n)    # d == wn
    assert _puts(mon) == (before[0], before[1] + 1)
    _round(mon, fleet, e - 50 + mon.cfg.window_n - 100)   # d < 0
    assert _puts(mon) == (before[0], before[1] + 2)
    assert _assert_exact(mon, launches) == 5


def test_same_tick_end_takes_a_full_put(fleet, launches):
    """d == 0: the snapshot diagnosed again, here with other values in
    its window; the full put sweeps the new values."""
    ts, data, channels, li = fleet
    mon = FleetMonitor(use_kernels=False)
    e = _warm(mon, fleet)
    other = data.copy()
    other[:, li, e - 250:e - 50] *= 1.5
    before = _puts(mon)
    _round(mon, (ts, other, channels, li), e - 50)   # d == 0
    assert _puts(mon) == (before[0], before[1] + 1)
    _round(mon, (ts, other, channels, li), e)
    assert _puts(mon) == (before[0] + 1, before[1] + 1)
    assert _assert_exact(mon, launches) == 5


def test_no_parity_failure_over_64_rounds(fleet):
    ts, data, channels, _ = fleet
    mon = FleetMonitor(config=EngineConfig(**SMALL), use_kernels=False)
    mon._windows.reanchor_every = 8
    for k in range(64):
        e = 600 + 50 * k
        mon.diagnose_fleet(ts[e - 600:e], data[:, :, e - 600:e], channels)
    st = mon.incremental_stats()
    assert st["window_proofs"] == 63 // 8
    assert st["window_full_puts"] == 1 + 63 // 8
    assert st["window_parity_failures"] == 0


def test_proof_finds_a_carried_window_that_disagrees(fleet, launches):
    """A tick that changes after it was put breaks the append-only
    trust: the next proof counts it, and its round sweeps the full put
    (the new values), so the result is still the slab's own."""
    ts, data, channels, li = fleet
    data = data.copy()
    mon = FleetMonitor(use_kernels=False)
    mon._windows.reanchor_every = 3
    e = _warm(mon, (ts, data, channels, li), rounds=3)   # advances 1, 2
    data[9, li, e - 200] += 5.0              # inside the carried window
    _round(mon, (ts, data, channels, li), e)           # advance 3: proof
    st = mon.incremental_stats()
    assert (st["window_proofs"], st["window_parity_failures"]) == (1, 1)
    assert _assert_exact(mon, launches) == 4


@pytest.mark.parametrize("use_kernels", [False, True])
def test_constant_slide_compiles_nothing_after_two_rounds(fleet,
                                                          use_kernels):
    """Detect only (Layer 3 compiles per flagged batch size): the first
    round compiles the full put, the second the slide; the proofs and a
    same-tick full put that follow compile nothing."""
    ts, data, channels, li = fleet
    mon = FleetMonitor(use_kernels=use_kernels)
    mon._windows.reanchor_every = 4
    wn, bn = mon.cfg.window_n, mon.cfg.baseline_n
    compiles = []

    def detect(e):
        mon._detect_round(*mon._detect_launch(
            data[:, :, e - 2500:e], None, li, 2500, wn, bn,
            tick_end=mon._tick_end(ts[e - 2500:e], 2500)))

    def on_duration(event, duration, **_):
        if event == "/jax/core/compile/backend_compile_duration":
            compiles.append(duration)
    for k in range(2):
        detect(2500 + 50 * k)
    jax.monitoring.register_event_duration_secs_listener(on_duration)
    try:
        for k in range(2, 14):
            detect(2500 + 50 * k)
        detect(2500 + 50 * 13)                         # d == 0
    finally:
        jax.monitoring.unregister_event_duration_listener(on_duration)
    st = mon.incremental_stats()
    assert st["window_proofs"] == 3 and st["window_full_puts"] == 5
    assert compiles == []


def test_windows_drop_by_row_overlap():
    w = sweep_ops.DeviceWindows()
    a, b = w.get(0, 16), w.get(16, 16)
    assert w.get(0, 16) is a
    w.drop(np.array([15]))
    assert set(w._held) == {(16, 16)}
    w.get(8, 16)                        # overlaps rows 16..23
    assert set(w._held) == {(8, 16)}
    assert b.x is None and w.stats()["window_full_puts"] == 0


def test_delta_share_reader_on_a_recorded_trace(tmp_path, fleet):
    """The launch half of ``detect.sweep`` carries ``put`` and the bytes
    actually put; ``window_delta_share.argus16k`` reads the share of
    delta puts."""
    import importlib.util
    ts, data, channels, _ = fleet
    mon = ShardedFleetMonitor(ShardPlan.from_bounds([(0, 12), (12, 24)]),
                              use_kernels=False)
    jax.profiler.start_trace(str(tmp_path))
    try:
        for k in range(3):
            e = 2600 + 50 * k
            with jax.profiler.TraceAnnotation(trace_reduce.WINDOW_SPAN):
                mon.diagnose_sharded(
                    ts[e - 2500:e],
                    lambda s: (data[slice(*mon.plan.bounds[s]), :,
                                    e - 2500:e], None), channels)
    finally:
        jax.profiler.stop_trace()
    from jax.profiler import ProfileData
    w = span_window(ProfileData.from_file(trace_reduce.find_xplane(
        tmp_path)))
    launch = [m for name, _, _, _, m in w.spans
              if name == "detect.sweep" and "put" in m]
    assert [m["put"] for m in launch] == [sweep_ops.PUT_FULL] * 2 + \
        [sweep_ops.PUT_DELTA] * 4
    wn, R = mon.cfg.window_n, 12
    # a full put: the window, mu and sd; a slide: one (R, 2 + 50) array
    assert [m["h2d_bytes"] for m in launch] == \
        [4 * R * wn + 8 * R] * 2 + [4 * R * (2 + 50)] * 4
    stage = [m["bytes"] for name, _, _, _, m in w.spans
             if name == "detect.stage"]
    assert stage == [4 * R * wn] * 2 + [4 * R * (2 + 50)] * 4
    root = os.path.join(os.path.dirname(__file__), "..")
    spec = importlib.util.spec_from_file_location(
        "window_delta_share", os.path.join(
            root, "bench", "metrics", "window_delta_share.argus16k.py"))
    mod = importlib.util.module_from_spec(spec)
    spec.loader.exec_module(mod)
    mod.load = lambda run: w
    assert mod.read(None) == pytest.approx(100.0 * 4 / 6)


@pytest.mark.parametrize("sharded", [False, True])
def test_live_rows_rewritten_in_place_drop_the_window(sharded):
    """A live fleet through :class:`FleetAggregator`, one 50-tick slide a
    round: the straggler's agent dies mid-run (the stager zeroes its row)
    and catches up (a full restage over the zeros), and a late joiner is
    masked by ``diagnose`` until it fills the span.  Each rewrite drops
    the rows' window and moments, so every round's verdict equals a
    monitor without windows or carried moments, bit for bit, whether
    one slab or two shards hold the rows."""
    from repro.monitor.aggregator import FleetAggregator
    from repro.monitor.shard import verdict_fingerprint
    from repro.sim.scenario import make_trial
    from repro.telemetry.agent import TelemetryAgent
    from repro.telemetry.collectors import SimCollector

    bad, late = 2, 5
    trials = [make_trial(900 + h, "nic", intensity=2.0 if h == bad else 0.0,
                         t_on=40.0, confuser_prob=0.0) for h in range(6)]
    agents = [TelemetryAgent([SimCollector(t.channels, t.ts, t.data)],
                             rate_hz=100.0, history_s=40.0) for t in trials]
    a, b = (FleetAggregator(agents, window_s=30.0, dead_after_s=0.2)
            for _ in range(2))
    win = (ShardedFleetMonitor(ShardPlan.from_bounds([(0, 3), (3, 6)]),
                               use_kernels=False)
           if sharded else FleetMonitor(use_kernels=False))
    ref = FleetMonitor(use_kernels=False, incremental=False)
    last = np.full(len(agents), 43.0)
    for h, ag in enumerate(agents):
        if h != late:
            ag.run_virtual(0.0, 43.0)
    flagged = []
    for r in range(14):
        t = (4300 + 50 * (r + 1)) / 100.0
        for h, ag in enumerate(agents):
            if (h == bad and 4 <= r < 9) or (h == late and r < 1):
                continue
            if h == late and r == 1:
                last[h] = t - 28.0               # joins with 28 s of data
            ag.run_virtual(last[h], t)
            last[h] = t
        fa, fb = a.diagnose(win), b.diagnose(ref)
        assert verdict_fingerprint(fa) == verdict_fingerprint(fb), r
        flagged.append(bad in fa.flagged_hosts)
    assert a.stats.dead_hosts > 0 and a.stats.masked_hosts > 0
    # the straggler fired while it lived, and never from a stale window
    assert any(flagged[:4]) and not any(flagged[4:9])
    st = win.incremental_stats()
    assert st["window_delta_puts"] > 0 and st["window_parity_failures"] == 0
