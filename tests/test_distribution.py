"""Distribution correctness: sharded pjit == single-device, on 8 host devices.

Runs in a SUBPROCESS with XLA_FLAGS=--xla_force_host_platform_device_count=8
so the main pytest process keeps its single default device (per the
dry-run's isolation rule).
"""

import os
import subprocess
import sys
import textwrap

import pytest

_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config, reduced
    from repro.configs.base import TrainConfig
    from repro.launch.mesh import _make_mesh
    from repro.launch.steps import make_train_step
    from repro.models import init_params
    from repro.optim.optimizers import adamw_init
    from repro.runtime import sharding as shard_lib

    assert jax.device_count() == 8, jax.devices()

    cfg = reduced(get_config("llama3.2-1b")).with_(n_layers=2, remat=False)
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=2, total_steps=4)

    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                          cfg.vocab_size)}

    # single-device reference
    step_ref = jax.jit(make_train_step(cfg, tcfg))
    p1, o1, m1 = step_ref(params, opt, batch)

    # 2 x 4 (data x model) mesh, full sharding rules
    mesh = _make_mesh((2, 4), ("data", "model"))
    p_specs = shard_lib.param_specs(jax.eval_shape(lambda: params), mesh, cfg,
                                    fsdp=True)
    o_specs = shard_lib.opt_state_specs(jax.eval_shape(lambda: opt), p_specs,
                                        mesh, zero1=True)
    b_specs = shard_lib.batch_specs_tree(jax.eval_shape(lambda: batch), mesh)
    with mesh:
        step_sh = jax.jit(
            make_train_step(cfg, tcfg, grad_specs=p_specs),
            in_shardings=(shard_lib.named(p_specs, mesh),
                          shard_lib.named(o_specs, mesh),
                          shard_lib.named(b_specs, mesh)),
        )
        p2, o2, m2 = step_sh(params, opt, batch)

    np.testing.assert_allclose(float(m1["loss"]), float(m2["loss"]),
                               rtol=2e-4, atol=2e-4)
    for a, b in zip(jax.tree_util.tree_leaves(p1), jax.tree_util.tree_leaves(p2)):
        np.testing.assert_allclose(np.asarray(a, np.float32),
                                   np.asarray(b, np.float32),
                                   rtol=3e-2, atol=3e-3)
    print("DISTRIBUTION_OK")
""")


@pytest.mark.slow
def test_sharded_train_step_matches_single_device():
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _SCRIPT], env=env,
                       capture_output=True, text=True, timeout=600,
                       cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "DISTRIBUTION_OK" in r.stdout


_ELASTIC_SCRIPT = textwrap.dedent("""
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax
    import jax.numpy as jnp
    import numpy as np

    from repro.checkpoint.checkpoint import restore_checkpoint, save_checkpoint
    from repro.configs import get_config, reduced
    from repro.configs.base import TrainConfig
    from repro.launch.mesh import _make_mesh
    from repro.launch.steps import make_train_step
    from repro.models import init_params
    from repro.optim.optimizers import adamw_init
    from repro.runtime import sharding as shard_lib

    cfg = reduced(get_config("llama3.2-1b")).with_(n_layers=2, remat=False)
    tcfg = TrainConfig(learning_rate=1e-3, warmup_steps=2, total_steps=4)
    params = init_params(cfg, jax.random.PRNGKey(0))
    opt = adamw_init(params)
    batch = {"tokens": jax.random.randint(jax.random.PRNGKey(1), (8, 32), 0,
                                          cfg.vocab_size)}

    def step_on_mesh(mesh_shape, p_in, o_in):
        mesh = _make_mesh(mesh_shape, ("data", "model"))
        p_specs = shard_lib.param_specs(jax.eval_shape(lambda: params), mesh,
                                        cfg, fsdp=True)
        with mesh:
            p_in = jax.device_put(p_in, shard_lib.named(p_specs, mesh))
            fn = jax.jit(make_train_step(cfg, tcfg, grad_specs=p_specs),
                         in_shardings=(shard_lib.named(p_specs, mesh),
                                       None, None))
            return fn(p_in, o_in, batch)

    # step once on a 2 x 4 mesh, checkpoint
    p1, o1, m1 = step_on_mesh((2, 4), params, opt)
    import tempfile
    d = tempfile.mkdtemp()
    save_checkpoint(d, 1, (p1, o1))

    # "node failure": come back on 4 x 2 AND on 8 x 1, restore + step
    ref = None
    for shape in ((4, 2), (8, 1)):
        step, (pr, orr), _ = restore_checkpoint(d, 1, (p1, o1))
        p2, o2, m2 = step_on_mesh(shape, pr, orr)
        loss = float(m2["loss"])
        if ref is None:
            ref = loss
        else:
            # bf16 reduction order differs across mesh shapes
            assert abs(loss - ref) < 1e-2 * max(abs(ref), 1.0), (loss, ref)
    print("ELASTIC_OK", ref)
""")


@pytest.mark.slow
def test_elastic_restore_across_mesh_shapes():
    """Checkpoint on a 2x4 mesh; restore + continue on 4x2 and 8x1 — the
    elastic-restart path. Loss after the resumed step must agree."""
    env = dict(os.environ)
    env["PYTHONPATH"] = "src"
    env.pop("XLA_FLAGS", None)
    r = subprocess.run([sys.executable, "-c", _ELASTIC_SCRIPT], env=env,
                       capture_output=True, text=True, timeout=900,
                       cwd=os.path.dirname(os.path.dirname(os.path.abspath(__file__))))
    assert r.returncode == 0, f"stdout:\n{r.stdout}\nstderr:\n{r.stderr}"
    assert "ELASTIC_OK" in r.stdout
