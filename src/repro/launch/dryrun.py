import os
os.environ["XLA_FLAGS"] = "--xla_force_host_platform_device_count=512"

"""Multi-pod dry-run: lower + compile every (arch x shape) cell on the
production mesh, record memory/cost/collective artifacts for §Roofline.

MUST be run as a module: ``PYTHONPATH=src python -m repro.launch.dryrun
--arch llama3.2-3b [--multi-pod] [--shapes train_4k,...] --out dryrun.jsonl``

The XLA_FLAGS line above executes before any other import (jax locks the
device count at first init). Smoke tests and benches never import this
module, so they see the real single CPU device.
"""
import argparse  # noqa: E402
import json  # noqa: E402
import time  # noqa: E402
import traceback  # noqa: E402

import jax  # noqa: E402
from jax.sharding import NamedSharding, PartitionSpec as P  # noqa: E402

from repro.configs import ARCHS, ASSIGNED, get_config  # noqa: E402
from repro.configs.base import SHAPES, SHAPE_ORDER, cell_applicable  # noqa: E402
from repro.launch import steps as steps_lib  # noqa: E402
from repro.launch.mesh import make_production_mesh  # noqa: E402
from repro.launch.specs import batch_spec  # noqa: E402
from repro.roofline.hlo_costs import analyze_hlo  # noqa: E402
from repro.sharding.act import use_activation_mesh  # noqa: E402
from repro.sharding import specs as sh  # noqa: E402


def _abstractify(shape_tree, shardings):
    return jax.tree.map(
        lambda l, s: jax.ShapeDtypeStruct(l.shape, l.dtype, sharding=s),
        shape_tree,
        shardings,
    )


def build_cell(arch: str, shape_name: str, mesh):
    """Returns (fn, args_abstract, donate) for the cell."""
    cfg = get_config(arch)
    shape = SHAPES[shape_name]
    bspec = batch_spec(cfg, shape)
    bshard = sh.batch_shardings(cfg, bspec, mesh)
    batch_abs = {
        k: jax.ShapeDtypeStruct(v.shape, v.dtype, sharding=bshard[k])
        for k, v in bspec.items()
    }

    pshape = jax.eval_shape(
        lambda: steps_lib.make_train_state(cfg, jax.random.PRNGKey(0))
    )
    pspecs = sh.param_shardings(cfg, pshape["params"], mesh)

    if shape.kind == "train":
        ospecs = sh.opt_state_shardings(cfg, pshape["opt"], pspecs, mesh)
        sspecs = {
            "params": pspecs,
            "opt": ospecs,
            "step": NamedSharding(mesh, P()),
        }
        state_abs = _abstractify(pshape, sspecs)
        fn = steps_lib.make_train_step(cfg)
        return fn, (state_abs, batch_abs), (0,)

    params_abs = _abstractify(pshape["params"], pspecs)
    if shape.kind == "prefill":
        fn = steps_lib.make_prefill_step(cfg)
        return fn, (params_abs, batch_abs), ()

    # decode: serve_step over a seq_len-sized cache
    cache_shape = steps_lib.abstract_cache(cfg, shape.global_batch, shape.seq_len)
    cspecs = sh.cache_shardings(cfg, cache_shape, mesh, shape.global_batch)
    cache_abs = _abstractify(cache_shape, cspecs)
    fn = steps_lib.make_serve_step(cfg)
    return fn, (params_abs, cache_abs, batch_abs), (1,)


def run_cell(arch: str, shape_name: str, multi_pod: bool) -> dict:
    rec = {
        "arch": arch,
        "shape": shape_name,
        "mesh": "2x16x16" if multi_pod else "16x16",
        "multi_pod": multi_pod,
    }
    cfg = get_config(arch)
    ok, reason = cell_applicable(cfg, SHAPES[shape_name])
    if not ok:
        rec["status"] = "skipped"
        rec["skip_reason"] = reason
        return rec
    try:
        mesh = make_production_mesh(multi_pod=multi_pod)
        t0 = time.time()
        with use_activation_mesh(mesh):
            fn, args, donate = build_cell(arch, shape_name, mesh)
            lowered = jax.jit(fn, donate_argnums=donate).lower(*args)
            t_lower = time.time() - t0
            t0 = time.time()
            compiled = lowered.compile()
            t_compile = time.time() - t0
        ma = compiled.memory_analysis()
        ca = compiled.cost_analysis()
        hlo = compiled.as_text()
        hc = analyze_hlo(hlo)
        rec.update(
            status="ok",
            lower_s=round(t_lower, 2),
            compile_s=round(t_compile, 2),
            n_devices=mesh.size,
            mem={
                "argument_bytes": ma.argument_size_in_bytes,
                "output_bytes": ma.output_size_in_bytes,
                "temp_bytes": ma.temp_size_in_bytes,
                "alias_bytes": ma.alias_size_in_bytes,
            },
            cost_analysis={
                "flops_raw": ca.get("flops", 0.0),
                "bytes_raw": ca.get("bytes accessed", 0.0),
            },
            hlo_costs={
                "dot_flops": hc.dot_flops,
                "collective_bytes": hc.collective_bytes,
                "collective_counts": hc.collective_counts,
                "while_loops": hc.while_loops,
            },
            model={
                "param_count": cfg.param_count(),
                "active_param_count": cfg.active_param_count(),
            },
        )
    except Exception as e:  # noqa: BLE001 — a failing cell is a bug report
        rec["status"] = "error"
        rec["error"] = f"{type(e).__name__}: {e}"
        rec["traceback"] = traceback.format_exc()[-4000:]
    return rec


def main():
    ap = argparse.ArgumentParser(description=__doc__)
    ap.add_argument("--arch", default=None, help="arch id (default: all assigned)")
    ap.add_argument("--shapes", default=None, help="comma-separated shape names")
    ap.add_argument("--multi-pod", action="store_true")
    ap.add_argument("--both-meshes", action="store_true")
    ap.add_argument("--out", default=None, help="append JSONL here")
    ap.add_argument("--list", action="store_true")
    args = ap.parse_args()

    if args.list:
        for a in ASSIGNED:
            print(a)
        return

    archs = [args.arch] if args.arch else list(ASSIGNED)
    shapes = args.shapes.split(",") if args.shapes else list(SHAPE_ORDER)
    meshes = [False, True] if args.both_meshes else [args.multi_pod]

    for arch in archs:
        for mp in meshes:
            for shape_name in shapes:
                rec = run_cell(arch, shape_name, mp)
                line = json.dumps(rec)
                if args.out:
                    with open(args.out, "a") as f:
                        f.write(line + "\n")
                brief = {
                    k: rec.get(k)
                    for k in ("arch", "shape", "mesh", "status", "compile_s")
                }
                if rec.get("status") == "ok":
                    brief["temp_GiB"] = round(rec["mem"]["temp_bytes"] / 2**30, 2)
                    brief["arg_GiB"] = round(rec["mem"]["argument_bytes"] / 2**30, 2)
                if rec.get("status") == "error":
                    brief["error"] = rec["error"][:200]
                print(json.dumps(brief), flush=True)


if __name__ == "__main__":
    main()
