"""Distribution: sharding specs, ImaGen-planned PP, multi-device smoke.

Multi-device cases run in a subprocess (jax pins the device count at
first init, and the main test process must stay single-device for the
other suites). Meshes come from ``launch.mesh.make_mesh`` and are
activated with ``jax.set_mesh``.
"""
import json
import os
import subprocess
import sys
import textwrap

import jax
import numpy as np
import pytest

from repro.distributed.pipeline import plan_1f1b

SRC = os.path.join(os.path.dirname(__file__), "..", "src")


def run_sub(code: str, devices: int = 8) -> str:
    env = dict(os.environ)
    env["XLA_FLAGS"] = f"--xla_force_host_platform_device_count={devices}"
    env["PYTHONPATH"] = SRC
    r = subprocess.run([sys.executable, "-c", textwrap.dedent(code)],
                       capture_output=True, text=True, env=env, timeout=600)
    assert r.returncode == 0, r.stderr[-3000:]
    return r.stdout


def test_plan_1f1b_matches_known_bound():
    for n in (2, 4, 8, 16):
        starts, stash = plan_1f1b(n)
        assert stash == {i: 2 * (n - i) - 1 for i in range(n)}
        # forward stages start one microbatch apart
        for i in range(1, n):
            assert starts[f"f{i}"] == starts[f"f{i-1}"] + 1


def test_param_specs_basic():
    from jax.sharding import PartitionSpec as P

    code = """
    import jax, json
    from repro.models import build_model, get_config
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_host_mesh
    import dataclasses
    mesh = make_host_mesh(2, 4)
    cfg = dataclasses.replace(get_config("qwen2.5-3b"), n_layers=2,
        d_model=64, n_heads=4, n_kv_heads=2, d_ff=64, vocab=64)
    m = build_model(cfg)
    shapes = jax.eval_shape(lambda k: m.init(k), jax.random.PRNGKey(0))
    specs = shd.param_specs(m, shapes, mesh)
    flat = jax.tree_util.tree_flatten_with_path(specs)[0]
    out = {"/".join(str(k) for k, in zip(p)) if False else str(p): str(s)
           for p, s in flat}
    # embed table: vocab on model, d on data
    emb = [s for p, s in flat if "table" in str(p)][0]
    assert "model" in str(emb) and "data" in str(emb), emb
    # attention wq: heads on model (4 % 4 == 0)
    wq = [s for p, s in flat if "'wq'" in str(p)][0]
    assert "model" in str(wq), wq
    print("OK")
    """
    assert "OK" in run_sub(code)


@pytest.mark.slow  # ~19s: compiles + runs a sharded train step twice
def test_pjit_train_step_runs_on_host_mesh():
    code = """
    import jax, dataclasses
    import jax.numpy as jnp
    import numpy as np
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.models import build_model, get_config
    from repro.distributed import sharding as shd
    from repro.launch.mesh import make_host_mesh
    from repro.train import OptConfig, make_train_state, make_train_step

    mesh = make_host_mesh(2, 4)
    cfg = dataclasses.replace(get_config("qwen2.5-3b"), n_layers=2,
        d_model=64, n_heads=4, n_kv_heads=4, d_ff=128, vocab=128,
        dtype="float32", remat=False)
    m = build_model(cfg)
    opt = OptConfig(lr=1e-3)
    state = make_train_state(m, jax.random.PRNGKey(0), opt)
    sspec = shd.state_specs(m, state, mesh)
    batch = {"tokens": jnp.ones((8, 32), jnp.int32),
             "labels": jnp.ones((8, 32), jnp.int32)}
    bspec = shd.batch_specs(batch, mesh)
    named = lambda t: jax.tree.map(
        lambda s: NamedSharding(mesh, s), t,
        is_leaf=lambda x: isinstance(x, P))
    step = jax.jit(make_train_step(m, opt),
                   in_shardings=(named(sspec), named(bspec)),
                   out_shardings=(named(sspec), None))
    with jax.set_mesh(mesh):
        state2, metrics = step(state, batch)
        state3, metrics2 = step(state2, batch)
    assert np.isfinite(float(metrics2["loss"]))
    assert float(metrics2["loss"]) < float(metrics["loss"]) + 1.0
    print("OK loss", float(metrics["loss"]), float(metrics2["loss"]))
    """
    assert "OK" in run_sub(code)


def test_pipeline_forward_multidevice():
    code = """
    import jax, jax.numpy as jnp, numpy as np
    from repro.distributed.pipeline import pipeline_forward
    from repro.launch.mesh import make_mesh
    mesh = make_mesh((4,), ("stage",))
    n_stages, n_micro, mb, d = 4, 6, 2, 8
    ks = jax.random.split(jax.random.PRNGKey(0), n_stages)
    w = jnp.stack([jax.random.normal(k, (d, d)) * 0.3 for k in ks])
    x = jax.random.normal(jax.random.PRNGKey(1), (n_micro, mb, d))
    apply_fn = lambda wi, h: jnp.tanh(h @ wi)
    out = pipeline_forward(w, x, apply_fn, mesh)
    # reference: sequential through all stages
    ref = x
    for i in range(n_stages):
        ref = jnp.tanh(ref @ w[i])
    err = float(jnp.abs(out - ref).max())
    assert err < 1e-5, err
    print("OK", err)
    """
    assert "OK" in run_sub(code, devices=4)


@pytest.mark.slow  # ~24s: full lower+compile of a 6-layer cell
def test_dryrun_single_cell_small():
    """Tiny end-to-end dry-run in a subprocess (8 virtual devices)."""
    code = """
    import os
    os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
    import jax, dataclasses
    import jax.numpy as jnp
    from jax.sharding import NamedSharding, PartitionSpec as P
    from repro.launch.mesh import make_mesh
    from repro.models import build_model, get_config
    from repro.distributed import sharding as shd
    from repro.train import OptConfig, make_train_step
    from repro.train.optimizer import init_opt_state
    mesh = make_mesh((2, 4), ("data", "model"))
    cfg = dataclasses.replace(get_config("gemma3-1b"), n_layers=6,
        d_model=64, n_heads=4, n_kv_heads=1, head_dim=16, d_ff=128,
        vocab=256, window=8)
    m = build_model(cfg)
    opt = OptConfig()
    def mk(key):
        p = m.init(key)
        return {"params": p, "opt": init_opt_state(p)}
    state_shape = jax.eval_shape(mk, jax.random.PRNGKey(0))
    sspec = shd.state_specs(m, state_shape, mesh)
    batch = {"tokens": jax.ShapeDtypeStruct((8, 64), jnp.int32),
             "labels": jax.ShapeDtypeStruct((8, 64), jnp.int32)}
    bspec = shd.batch_specs(batch, mesh)
    named = lambda t: jax.tree.map(lambda s: NamedSharding(mesh, s), t,
                                   is_leaf=lambda x: isinstance(x, P))
    step = make_train_step(m, opt)
    with jax.set_mesh(mesh):
        jf = jax.jit(step, in_shardings=(named(sspec), named(bspec)),
                     out_shardings=(named(sspec), None))
        compiled = jf.lower(state_shape, batch).compile()
    ca = compiled.cost_analysis()
    assert ca.get("flops", 0) > 0
    print("OK flops", ca["flops"])
    """
    assert "OK" in run_sub(code)
