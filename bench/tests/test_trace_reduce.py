"""The trace reduction against a small trace recorded on one TPU v5 lite:
one second of ``superpod1k-storm`` with ``--trace 1``.  Busy time, idle
attribution and kernel time are recomputed here a second way (a 100 ns
occupancy grid, a plain sum of event durations) and must agree."""
from pathlib import Path

import numpy as np
import pytest

from bench import roofline, run, trace_reduce

TRACE = Path(__file__).resolve().parents[1] / "testdata" / \
    "superpod1k-storm-1s.xplane.pb"


@pytest.fixture(scope="module")
def pd():
    from jax.profiler import ProfileData
    return ProfileData.from_file(str(TRACE))


def test_op_name():
    assert trace_reduce.op_name(
        "%_sweep_jit.1 = (s32[1016,1]{1,0}) custom-call(...)") == "_sweep_jit"
    assert trace_reduce.op_name("%copy.15 = f32[8] copy(%x)") == "copy"
    assert trace_reduce.op_name("fusion") == "fusion"


def test_reduction_agrees_with_a_second_count(pd):
    s = trace_reduce.reduce(pd, run.SPANS)
    rounds = [(e.start_ns, e.end_ns) for p in pd.planes
              if p.name.startswith("/host:") for ln in p.lines
              for e in ln.events if e.name == "round"]
    w0, w1 = min(a for a, _ in rounds), max(b for _, b in rounds)
    assert s.window_s == pytest.approx((w1 - w0) * 1e-9)
    step = 100.0
    grid = np.zeros(int((w1 - w0) // step) + 2, bool)
    sweep = fused = 0.0
    n_dev = 0
    for p in pd.planes:
        if not p.name.startswith("/device:TPU:"):
            continue
        n_dev += 1
        for ln in p.lines:
            if ln.name != "XLA Ops":
                continue
            for e in ln.events:
                a, b = max(e.start_ns, w0), min(e.end_ns, w1)
                if b <= a:
                    continue
                grid[int((a - w0) // step):int(np.ceil((b - w0) / step))] = 1
                head = e.name.split(" = ")[0]
                if head.startswith("%_sweep_jit."):
                    sweep += (b - a) * 1e-9
                elif head.startswith("%fused_rca."):
                    fused += (b - a) * 1e-9
    assert n_dev == s.n_devices == 1
    busy_grid = grid.sum() * step * 1e-9
    # the grid rounds each interval out to whole 100 ns cells
    assert s.busy_s <= busy_grid + 1e-9
    assert s.busy_s == pytest.approx(busy_grid, rel=0.05)
    assert sum(s.idle.values()) == pytest.approx(s.window_s - s.busy_s,
                                                 rel=1e-9)
    n_sw, t_sw = s.op_seconds(roofline.is_sweep_op)
    n_fu, t_fu = s.op_seconds(roofline.is_fused_op)
    assert n_sw > 0 and n_fu > 0
    assert t_sw == pytest.approx(sweep, rel=1e-9)
    assert t_fu == pytest.approx(fused, rel=1e-9)
    # the live round's idle time sits under the harness's own spans
    assert {"assemble", "generator_push", "detect_round"} <= set(s.idle)
    bd = s.breakdown()
    assert len(bd["device_ops"]) <= 10 and len(bd["idle_gaps"]) <= 10
    assert bd["device_ops"][0][1] >= bd["device_ops"][-1][1]


def test_no_round_span_is_an_error(pd):
    with pytest.raises(ValueError):
        trace_reduce.reduce(pd, ())
