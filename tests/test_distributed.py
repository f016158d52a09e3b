"""Multi-device tests run in subprocesses (8 fake host devices) so the main
pytest process keeps its single real CPU device."""
import json
import os
import subprocess
import sys
import textwrap

import pytest

REPO = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))


def run_py(code: str, timeout=480) -> str:
    env = dict(os.environ)
    env["PYTHONPATH"] = os.path.join(REPO, "src")
    env.pop("XLA_FLAGS", None)
    out = subprocess.run(
        [sys.executable, "-c", textwrap.dedent(code)],
        capture_output=True,
        text=True,
        timeout=timeout,
        env=env,
        cwd=REPO,
    )
    assert out.returncode == 0, f"stderr:\n{out.stderr[-3000:]}"
    return out.stdout


def test_sharded_train_step_runs():
    """Real sharded execution on 8 devices: loss decreases over steps."""
    out = run_py("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.configs.base import ShapeSpec
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_train_state, make_train_step
        from repro.sharding.specs import param_shardings, opt_state_shardings
        from repro.sharding.act import use_activation_mesh
        from repro.data.pipeline import pipeline_for

        cfg = get_config("qwen3-1.7b").reduced()
        mesh = make_mesh((2, 4), ("data", "model"))
        shape = ShapeSpec("t", 64, 4, "train")
        state = make_train_state(cfg, jax.random.PRNGKey(0))
        pspecs = param_shardings(cfg, state["params"], mesh)
        ospecs = opt_state_shardings(cfg, state["opt"], pspecs, mesh)
        sspecs = {"params": pspecs, "opt": ospecs, "step": NamedSharding(mesh, P())}
        state = jax.device_put(state, sspecs)
        pipe = pipeline_for(cfg, shape)
        with use_activation_mesh(mesh):
            step = jax.jit(make_train_step(cfg), donate_argnums=(0,))
            losses = []
            for i in range(8):
                batch = {k: jnp.asarray(v) for k, v in pipe.batch(i).items()}
                state, m = step(state, batch)
                losses.append(float(m["loss"]))
        print("LOSSES", losses[0], losses[-1])
        assert losses[-1] < losses[0], losses
    """)
    assert "LOSSES" in out


def test_elastic_remesh_resumes():
    """Checkpoint on a (2,4) mesh, restore + continue on (1,2) with fewer
    devices — the elastic scaling path."""
    out = run_py("""
        import os, tempfile
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.configs import get_config
        from repro.configs.base import ShapeSpec
        from repro.launch.mesh import make_mesh
        from repro.launch.steps import make_train_state, make_train_step
        from repro.sharding.specs import param_shardings, opt_state_shardings
        from repro.sharding.act import use_activation_mesh
        from repro.data.pipeline import pipeline_for
        from repro.checkpointing.checkpoint import save, restore

        cfg = get_config("llama3.2-3b").reduced()
        shape = ShapeSpec("t", 64, 4, "train")
        pipe = pipeline_for(cfg, shape)
        ckpt = tempfile.mkdtemp()

        def shardings(mesh, state_shape):
            pspecs = param_shardings(cfg, state_shape["params"], mesh)
            ospecs = opt_state_shardings(cfg, state_shape["opt"], pspecs, mesh)
            return {"params": pspecs, "opt": ospecs, "step": NamedSharding(mesh, P())}

        # phase 1: 8 devices
        mesh1 = make_mesh((2, 4), ("data", "model"))
        state = make_train_state(cfg, jax.random.PRNGKey(0))
        state = jax.device_put(state, shardings(mesh1, state))
        with use_activation_mesh(mesh1):
            step = jax.jit(make_train_step(cfg), donate_argnums=(0,))
            for i in range(3):
                state, m = step(state, {k: jnp.asarray(v) for k, v in pipe.batch(i).items()})
        save(ckpt, 3, state)
        l3 = float(m["loss"])

        # phase 2: "node loss" -> re-mesh to 2 devices, restore, continue
        mesh2 = make_mesh((1, 2), ("data", "model"))
        abs_state = jax.eval_shape(lambda: make_train_state(cfg, jax.random.PRNGKey(0)))
        sspecs2 = shardings(mesh2, abs_state)
        target = jax.tree.map(
            lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s), abs_state, sspecs2)
        state2 = restore(ckpt, 3, target)
        with use_activation_mesh(mesh2):
            step2 = jax.jit(make_train_step(cfg), donate_argnums=(0,))
            state2, m2 = step2(state2, {k: jnp.asarray(v) for k, v in pipe.batch(3).items()})
        print("RESUMED", l3, float(m2["loss"]))
        assert np.isfinite(float(m2["loss"]))
        assert int(jax.device_get(state2["step"])) == 4
    """)
    assert "RESUMED" in out


def test_multipod_gradient_reduction_over_pod_axis():
    """Multi-pod mesh: gradients must reduce over the pod axis (DCN)."""
    out = run_py("""
        import os
        os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=8"
        import jax, jax.numpy as jnp, numpy as np
        from jax.sharding import NamedSharding, PartitionSpec as P
        from repro.launch.mesh import make_mesh, dp_axes
        mesh = make_mesh((2, 2, 2), ("pod", "data", "model"))
        assert dp_axes(mesh) == ("pod", "data")
        w = jax.ShapeDtypeStruct((64, 64), jnp.float32, sharding=NamedSharding(mesh, P(None, "model")))
        x = jax.ShapeDtypeStruct((8, 64), jnp.float32, sharding=NamedSharding(mesh, P(("pod", "data"), None)))
        def loss(w, x):
            return ((x @ w) ** 2).mean()
        c = jax.jit(jax.grad(loss)).lower(w, x).compile()
        txt = c.as_text()
        assert "all-reduce" in txt
        print("PODOK")
    """)
    assert "PODOK" in out
