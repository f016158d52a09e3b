"""Training launcher.

    PYTHONPATH=src python -m repro.launch.train --arch qwen3-1.7b --reduced \
        --steps 50 --ckpt-dir /tmp/ckpt [--devices 8 --mesh 2x4]

With ``--devices N`` the launcher forks a host-device mesh (CPU testing);
on a real fleet, jax.distributed handles process groups and the same code
runs per host. Checkpoints are mesh-agnostic (elastic re-mesh: restart with
a different --mesh and training continues from the latest step).
"""
import argparse
import os
import sys


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--arch", default="qwen3-1.7b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--steps", type=int, default=100)
    ap.add_argument("--seq-len", type=int, default=256)
    ap.add_argument("--global-batch", type=int, default=8)
    ap.add_argument("--ckpt-dir", default="/tmp/repro_ckpt")
    ap.add_argument("--ckpt-every", type=int, default=25)
    ap.add_argument("--devices", type=int, default=0, help="fake host devices")
    ap.add_argument("--mesh", default=None, help="e.g. 2x4 => (data=2, model=4)")
    args = ap.parse_args()

    if args.devices and "XLA_FLAGS" not in os.environ:
        os.environ["XLA_FLAGS"] = (
            f"--xla_force_host_platform_device_count={args.devices}"
        )

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    import jax
    from jax.sharding import NamedSharding, PartitionSpec as P

    from repro.configs import get_config
    from repro.configs.base import ShapeSpec
    from repro.launch.mesh import make_mesh
    from repro.launch.steps import make_train_state
    from repro.runtime.train_loop import TrainSupervisor
    from repro.sharding.act import use_activation_mesh
    from repro.sharding.specs import opt_state_shardings, param_shardings

    cfg = get_config(args.arch)
    if args.reduced:
        cfg = cfg.reduced()
    shape = ShapeSpec("train", args.seq_len, args.global_batch, "train")

    mesh = shardings = None
    ctx = None
    if args.mesh:
        dims = tuple(int(x) for x in args.mesh.split("x"))
        axes = ("data", "model") if len(dims) == 2 else ("pod", "data", "model")
        mesh = make_mesh(dims, axes)
        state_shape = jax.eval_shape(
            lambda: make_train_state(cfg, jax.random.PRNGKey(0))
        )
        pspecs = param_shardings(cfg, state_shape["params"], mesh)
        ospecs = opt_state_shardings(cfg, state_shape["opt"], pspecs, mesh)
        shardings = {
            "params": pspecs,
            "opt": ospecs,
            "step": NamedSharding(mesh, P()),
        }
        ctx = use_activation_mesh(mesh)

    sup = TrainSupervisor(
        cfg, shape, args.ckpt_dir, mesh=mesh, shardings=shardings,
        ckpt_every=args.ckpt_every,
    )
    if ctx is not None:
        with ctx:
            report = sup.run(args.steps)
    else:
        report = sup.run(args.steps)
    print(
        f"final_step={report.final_step} loss {report.losses[0]:.3f} -> "
        f"{report.losses[-1]:.3f} checkpoints={report.checkpoints}"
    )


if __name__ == "__main__":
    main()
