"""The frozen generator copy is byte-equal to the program's simulator it
was copied from (checked while that original exists)."""
import numpy as np
import pytest

from bench.traffic import generator

scenario = pytest.importorskip("repro.sim.scenario")


@pytest.mark.parametrize("seed,kw", [
    (9300, dict(duration_s=60.0, t_on=20.0, intensity=0.0,
                confuser_prob=0.0)),
    (109301, dict(duration_s=60.0, t_on=20.0, intensity=2.0,
                  confuser_prob=0.0)),
    (7, dict(disturbance="io")),
    (12345, dict(disturbance="gpu", duration_s=40.0)),
])
def test_copy_is_byte_equal(seed, kw):
    kw = dict(kw)
    dist = kw.pop("disturbance", "nic")
    ts, data, channels = generator.make_trial(seed, dist, **kw)
    t = scenario.make_trial(seed, dist, **kw)
    assert channels == t.channels
    assert np.array_equal(ts, t.ts)
    assert data.dtype == t.data.dtype
    assert data.tobytes() == t.data.tobytes()
