"""Distribution-layer tests.

Multi-device behaviour runs in a subprocess (device count is locked at
first jax init, so the main test process stays single-device).
"""
import json
import os
import subprocess
import sys
import textwrap

import pytest

from repro.parallel.sharding import LOGICAL_RULES, logical_to_pspec

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def test_logical_to_pspec_dedup():
    from jax.sharding import PartitionSpec as P
    rules = {"embed": "data", "mlp": "model", "heads": "model"}
    # duplicate mesh axis must be dropped from the second occurrence
    spec = logical_to_pspec(("embed", "mlp", "heads"), rules)
    assert spec == P("data", "model", None)


def test_make_rules_head_divisibility():
    import jax
    from repro.configs import get_config
    from repro.parallel.sharding import make_rules
    mesh = jax.make_mesh((1, 1), ("data", "model"))
    # single-device mesh: everything still resolves
    r = make_rules(get_config("starcoder2-7b"), mesh)
    assert isinstance(r, dict)


_SUBPROC = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import sys
    sys.path.insert(0, {src!r})
    import jax, jax.numpy as jnp, numpy as np
    from jax.sharding import AxisType, PartitionSpec as P, NamedSharding
    from repro.configs import get_config
    from repro.launch.inputs import make_real_batch
    from repro.models.registry import build_model
    from repro.parallel.ctx import mesh_context
    from repro.parallel.sharding import make_rules, param_pspecs, logical_to_pspec
    from repro.train.optimizer import OptConfig
    from repro.train.step import build_train_step, init_train_state

    cfg = get_config("yi-9b", smoke=True)
    model = build_model(cfg)
    opt = OptConfig(lr=1e-3, warmup_steps=1)
    batch_np = make_real_batch(cfg, 8, 32, seed=3)

    # single-device reference
    state = init_train_state(model, jax.random.key(0), opt)
    step = jax.jit(build_train_step(model, opt))
    _, m_ref = step(state, {{k: jnp.asarray(v) for k, v in batch_np.items()}})
    loss_ref = float(m_ref["loss"])

    # sharded run on a 4x2 mesh
    mesh = jax.make_mesh((4, 2), ("data", "model"),
                         axis_types=(AxisType.Auto,) * 2)
    rules = make_rules(cfg, mesh)
    with mesh_context(mesh, rules):
        pspecs = param_pspecs(model.param_logical, rules)
        state2 = init_train_state(model, jax.random.key(0), opt)
        tok_sh = NamedSharding(mesh, logical_to_pspec(("act_batch", "act_seq"), rules))
        batch = {{k: jax.device_put(jnp.asarray(v), tok_sh)
                 for k, v in batch_np.items()}}
        step2 = jax.jit(build_train_step(model, opt))
        _, m_sh = step2(state2, batch)
        loss_sh = float(m_sh["loss"])
    print("RESULT", loss_ref, loss_sh)
    assert abs(loss_ref - loss_sh) < 0.05 * abs(loss_ref) + 0.05, (loss_ref, loss_sh)
""")


@pytest.mark.slow
def test_sharded_step_matches_single_device(tmp_path):
    script = tmp_path / "probe.py"
    script.write_text(_SUBPROC.format(src=os.path.abspath(SRC)))
    out = subprocess.run([sys.executable, str(script)], capture_output=True,
                         text=True, timeout=600)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "RESULT" in out.stdout


@pytest.mark.slow
def test_dryrun_smoke_small_devices(tmp_path):
    """dryrun machinery end-to-end with 8 placeholder devices (the full
    512-device sweep runs via the launcher; this guards the plumbing)."""
    env = dict(os.environ, REPRO_DRYRUN_DEVICES="8",
               PYTHONPATH=os.path.abspath(SRC))
    script = textwrap.dedent("""
        import repro.launch.dryrun as dr
        import jax
        from jax.sharding import AxisType
        # shrink the production mesh to the debug size for this probe
        import repro.launch.mesh as mesh_mod
        mesh_mod.make_production_mesh = \
            lambda multi_pod=False: jax.make_mesh(
                (2, 2, 2) if multi_pod else (4, 2),
                ("pod", "data", "model") if multi_pod else ("data", "model"),
                axis_types=(AxisType.Auto,) * (3 if multi_pod else 2))
        rec = dr.run_cell("whisper-base", "train_4k", False, save=False)
        assert rec["status"] == "ok", rec
        rec2 = dr.run_cell("whisper-base", "train_4k", True, save=False)
        assert rec2["status"] == "ok", rec2
        print("DRYRUN-SMOKE-OK")
    """)
    p = tmp_path / "dr.py"
    p.write_text(script)
    out = subprocess.run([sys.executable, str(p)], capture_output=True,
                         text=True, timeout=600, env=env)
    assert out.returncode == 0, out.stderr[-3000:]
    assert "DRYRUN-SMOKE-OK" in out.stdout
