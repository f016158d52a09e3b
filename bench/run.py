"""Run one cell of the benchmark on the chip this process finds.

    python bench/run.py --workload trio-1.5x.zipf-poisson --seed 7 --seconds 51 --trace 0

``BENCHMARK.json`` at the repository's root names the cells. With
``--trace 0`` the result carries the cell's end-to-end metrics, with
``--trace 1`` its per-layer metrics from a traced run. The last line of
standard output is one JSON object: ``correct``, ``attempted``, ``failed``,
``metrics``, ``device`` (``busy_s`` and ``window_s`` when traced),
``breakdown`` when traced, and last ``checks``, the numbers the correctness
check compared, each with its limit; they are also the last lines of
standard error. The run exits 1 and prints no result when JAX finds no TPU
or fewer chips than the cell asks for.

JAX's persistent compilation cache is kept in ``<checkout>/.jax_cache``, so
only a checkout's first run of a cell compiles.
"""
from __future__ import annotations

import time

T_START = time.perf_counter()

import argparse  # noqa: E402
import json  # noqa: E402
import os  # noqa: E402
import sys  # noqa: E402
import tempfile  # noqa: E402

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seed", type=int, required=True)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--trace", type=int, choices=(0, 1), default=0)
    args = ap.parse_args(argv)
    if args.seed < 0:
        ap.error("--seed must be a whole number >= 0")

    # the cache's path is part of its key: a fixed directory in the checkout
    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    tpu_logs = os.path.join(tempfile.gettempdir(), "tpu_logs")
    os.makedirs(tpu_logs, exist_ok=True)
    os.environ.setdefault("TPU_LOG_DIR", tpu_logs)
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    # cache every program, the small ones too, so that a second run compiles nothing
    jax.config.update("jax_persistent_cache_min_compile_time_secs", 0.0)

    from bench import harness

    devices = jax.devices()
    if devices[0].platform != "tpu":
        print(f"no TPU: JAX's first device is {devices[0].platform}", file=sys.stderr)
        return 1
    cell = harness.load_cell(ROOT, args.workload)["cell"]
    if len(devices) < cell["chips"]:
        print(f"{args.workload} needs {cell['chips']} chips, JAX finds {len(devices)}", file=sys.stderr)
        return 1
    result = harness.run_cell(args.workload, args.seed, args.seconds, bool(args.trace), T_START)
    for name, check in result["checks"].items():
        print(f"check {name}: {check['value']} (limit {check['limit']})", file=sys.stderr)
    print(json.dumps(result))
    return 0


if __name__ == "__main__":
    sys.exit(main())
