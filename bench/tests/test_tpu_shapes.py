"""The cells' kernel shapes compile for a described TPU v5e (no chip
needed): the single-tick sweep over a shard of each configuration, and
the fused RCA dispatch at the batch sizes the storm traffic produces."""
import functools
import os

import pytest

os.environ.setdefault("TPU_LOG_DIR", "disabled")


@pytest.fixture(scope="module")
def one_chip():
    import jax
    from jax.experimental import topologies
    from jax.sharding import SingleDeviceSharding
    jax.config.update("jax_enable_compilation_cache", False)
    try:
        topo = topologies.get_topology_desc(platform="tpu",
                                            topology_name="v5e:2x2")
    except Exception as e:  # noqa: BLE001 - no TPU compiler here
        pytest.skip(f"no v5e:2x2 topology can be described here: {e}")
    return SingleDeviceSharding(topo.devices[0])


@pytest.mark.parametrize("rows", [1024, 1016])
def test_sweep_compiles(one_chip, rows):
    import jax
    import jax.numpy as jnp
    from repro.kernels.sweep.sweep import sweep_rows_pallas
    f = jax.jit(functools.partial(
        sweep_rows_pallas, wn=500, threshold=3.0, min_hot=175, eps=5e-3,
        argmax_fallback=True, interpret=False))
    sd = lambda s, d: jax.ShapeDtypeStruct(s, d, sharding=one_chip)  # noqa
    txt = f.lower(sd((rows, 500), jnp.float32), sd((rows, 1), jnp.float32),
                  sd((rows, 1), jnp.float32), sd((1,), jnp.int32),
                  sd((rows,), jnp.int32)).compile().as_text()
    assert "tpu_custom_call" in txt


@pytest.mark.parametrize("batch", [3, 6, 16])
def test_fused_compiles(one_chip, batch):
    import jax
    import jax.numpy as jnp
    from repro.kernels.fused.fused import fused_rca_pallas
    f = jax.jit(functools.partial(fused_rca_pallas, max_lag=20, n_valid=950,
                                  nb_valid=2000, interpret=False))
    sd = lambda s: jax.ShapeDtypeStruct(s, jnp.float32, sharding=one_chip)  # noqa
    txt = f.lower(sd((batch, 1024)), sd((batch, 18, 1024)),
                  sd((batch, 18, 2048))).compile().as_text()
    assert "tpu_custom_call" in txt
