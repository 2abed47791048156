"""Compile the Pallas kernels for a described TPU v5e, at the widths the
monitor round passes them.

Nothing runs: the TPU compiler installed beside JAX compiles for a chip
that is described, not attached, and raises what the chip's compiler
would (misaligned blocks, unsupported ops, VMEM overuse).  Interpret-mode
parity tests cannot see those; these catch them with no chip.  The
topology is described inside a fixture, never at import: only one
process at a time may load the TPU library.
"""
import functools
import re

import jax
import jax.numpy as jnp
import pytest
from jax.sharding import SingleDeviceSharding

from bench import roofline, trace_reduce
from repro.core.engine import EngineConfig
from repro.kernels import tuning
from repro.kernels.fused import ops as fused_ops
from repro.kernels.fused.fused import fused_rca_masked_pallas, fused_rca_pallas
from repro.kernels.sweep import ops as sweep_ops
from repro.kernels.sweep.sweep import sweep_rows_pallas
from repro.kernels.xcorr.xcorr import lagged_xcorr_pallas
from repro.monitor.fleet import FleetMonitor
from repro.sim.scenario import make_trial

F32, I32 = jnp.float32, jnp.int32


@pytest.fixture(scope="module")
def one_chip():
    with pytest.MonkeyPatch.context() as mp:
        mp.setenv("TPU_LOG_DIR", "disabled")
        from jax.experimental import topologies
        try:
            topo = topologies.get_topology_desc(platform="tpu",
                                                topology_name="v5e:2x2")
        except Exception as e:   # no TPU compiler in this installation
            pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
        # a described chip's executables cannot be read back from the
        # persistent cache without the chip: keep them out of it
        cache_on = jax.config.jax_enable_compilation_cache
        jax.config.update("jax_enable_compilation_cache", False)
        yield SingleDeviceSharding(topo.devices[0])
        jax.config.update("jax_enable_compilation_cache", cache_on)


@pytest.fixture(scope="module")
def rca_geometry():
    """(rows, metrics, rca window, baseline) of the fleet RCA dispatch at
    the default config: the ARGUS-scale round's 34 s snapshot, one shard's
    ``shard_topk`` candidates."""
    cfg = EngineConfig()
    trial = make_trial(0, "nic", duration_s=34.0, t_on=28.0,
                       confuser_prob=0.0)
    T = trial.data.shape[1]
    li = trial.channels.index(cfg.latency_metric)
    geom = FleetMonitor(cfg)._evidence_geometry(
        trial.channels, li, T, cfg.window_n, cfg.baseline_n)
    return tuning.shard_topk(), len(geom.names), geom.rn, geom.nb


def _shapes(sharding, *specs):
    return [jax.ShapeDtypeStruct(s, dt, sharding=sharding) for s, dt in specs]


def _compile(fn, args):
    compiled = jax.jit(fn).lower(*args).compile()
    assert "tpu_custom_call" in compiled.as_text()
    return compiled


def _pad128(n: int) -> int:
    return -(-n // 128) * 128


@pytest.mark.parametrize("case", ["fleet_window", "fleet_tail", "suite"])
def test_sweep_compiles(one_chip, case):
    """fleet_window: one shard's single tick on the incremental-moments
    path (window columns only); fleet_tail: the same tick over the full
    baseline + window tail; suite: the 48-trial eval slab at the 10-sample
    streaming cadence."""
    cfg = EngineConfig(eval_every=10)
    wn, bn = cfg.window_n, cfg.baseline_n
    if case == "suite":
        R, T = 48, 9000
        nt = len(range(wn + bn, T, cfg.eval_every))
        argmax = False
    else:
        R = tuning.shard_hosts()
        T = wn if case == "fleet_window" else wn + bn
        nt, argmax = 1, True
    fn = functools.partial(
        sweep_rows_pallas, wn=wn, threshold=cfg.threshold,
        min_hot=sweep_ops.persistence_count(wn, cfg.persistence),
        eps=sweep_ops.SWEEP_GUARD_EPS, argmax_fallback=argmax,
        interpret=False)
    _compile(fn, _shapes(one_chip, ((R, T), F32), ((R, nt), F32),
                         ((R, nt), F32), ((nt,), I32), ((R,), I32)))


def test_fused_compiles(one_chip, rca_geometry):
    B, M, rn, nb = rca_geometry
    fn = functools.partial(fused_rca_pallas, max_lag=EngineConfig().max_lag,
                           n_valid=rn, nb_valid=nb, interpret=False)
    _compile(fn, _shapes(one_chip, ((B, _pad128(rn)), F32),
                         ((B, M, _pad128(rn)), F32),
                         ((B, M, _pad128(nb)), F32)))


def test_fused_ragged_compiles(one_chip, rca_geometry):
    _, M, rn, nb = rca_geometry
    B = 64                                    # event-batched eval rows
    fn = functools.partial(fused_rca_masked_pallas,
                           max_lag=EngineConfig().max_lag, interpret=False)
    _compile(fn, _shapes(one_chip, ((B, _pad128(rn)), F32),
                         ((B, M, _pad128(rn)), F32),
                         ((B, M, _pad128(nb)), F32), ((B,), I32),
                         ((B,), I32)))


@pytest.mark.parametrize("kernel", ["xcorr"])
def test_single_purpose_kernels_compile(one_chip, rca_geometry, kernel):
    """The lagged-xcorr kernel behind ``kernels.xcorr.ops``, at the RCA
    window's widths."""
    cfg = EngineConfig()
    B, M, rn, nb = rca_geometry
    fn = functools.partial(lagged_xcorr_pallas, max_lag=cfg.max_lag,
                           n_valid=rn, interpret=False)
    _compile(fn, _shapes(one_chip, ((B, _pad128(rn)), F32),
                         ((B, M, _pad128(rn)), F32)))


_CUSTOM_CALL = re.compile(
    r'^\s*(?:ROOT\s+)?%(\S+) = .*custom-call\(.*'
    r'custom_call_target="tpu_custom_call"', re.M)


def test_kernel_names_match_the_roofline_readers(one_chip, rca_geometry):
    """The device trace names each kernel by the HLO instruction of its
    custom call, which takes the name of the jitted function around the
    ``pallas_call``.  ``bench/roofline.py`` finds the sweep and fused
    kernels by those names: a rename fails here instead of silently
    leaving ``sweep_roofline`` and ``fused_roofline`` without a reading."""
    cfg = EngineConfig()
    wn, R = cfg.window_n, tuning.shard_hosts()
    sweep = sweep_ops._sweep_jit.lower(
        *_shapes(one_chip, ((R, wn), F32), ((R, 1), F32), ((R, 1), F32),
                 ((1,), I32), ((R,), I32)),
        wn=wn, threshold=cfg.threshold,
        min_hot=sweep_ops.persistence_count(wn, cfg.persistence),
        eps=sweep_ops.SWEEP_GUARD_EPS, argmax_fallback=True, use_kernel=True,
        interpret=False, block_t=tuning.sweep_block_t(None)).compile()
    B, M, rn, nb = rca_geometry
    # the fused op asks the dispatch's device whether to interpret
    with jax.default_device(next(iter(one_chip.device_set))):
        fused = fused_ops._fused_rca_max_jit.lower(
            *_shapes(one_chip, ((B, rn), F32), ((B, M, rn), F32),
                     ((B, M, nb), F32)),
            max_lag=cfg.max_lag, use_kernel=True).compile()
    names = [[trace_reduce.op_name(n)
              for n in _CUSTOM_CALL.findall(c.as_text())]
             for c in (sweep, fused)]
    assert names == [[roofline.SWEEP_OP], [roofline.FUSED_OP]]
    assert roofline.is_sweep_op(names[0][0])
    assert roofline.is_fused_op(names[1][0])


@pytest.mark.parametrize("jit", ["advance", "proof"])
def test_window_sweeps_compile_with_the_sweep_kernel(one_chip, jit):
    """The device-window dispatches of one shard at the 0.5 s cadence:
    the slide (one put of mu, sd and 50 new ticks into the donated
    window) and the full put with its coherence proof.  Both keep the kernel's custom call named
    as ``sweep_roofline`` finds it, and the slide writes the window in
    place of the one it was given."""
    cfg = EngineConfig()
    wn, R, d = cfg.window_n, tuning.shard_hosts(), 50
    static = dict(
        wn=wn, threshold=cfg.threshold,
        min_hot=sweep_ops.persistence_count(wn, cfg.persistence),
        eps=sweep_ops.SWEEP_GUARD_EPS, argmax_fallback=True,
        use_kernel=True, interpret=False,
        block_t=tuning.sweep_block_t(None))
    if jit == "advance":
        fn = sweep_ops._advance_sweep_jit
        args = _shapes(one_chip, ((R, wn), F32), ((R, 2 + d), F32))
    else:
        fn = sweep_ops._proof_sweep_jit
        args = _shapes(one_chip, ((R, wn), F32), ((R, wn), F32),
                       ((), I32), ((R, 1), F32), ((R, 1), F32))
    text = fn.lower(*args, **static).compile().as_text()
    assert [trace_reduce.op_name(n) for n in _CUSTOM_CALL.findall(text)] \
        == [roofline.SWEEP_OP]
    assert ("input_output_alias={ {0}: (0" in text) == (jit == "advance")
