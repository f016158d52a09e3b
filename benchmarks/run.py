"""Benchmark harness: one module per paper table/figure.

Prints ``name,us_per_call,derived`` CSV. ``python -m benchmarks.run`` runs
everything; ``--only fig07`` filters by prefix. ``--profile`` wraps each
module's run() in cProfile and prints its top-20 cumulative-time entries to
stderr — the supported way to find the simulator's current hot path (see
EXPERIMENTS.md, "Profiling the simulator"). ``--telemetry DIR`` passes a
``DIR/<module>.trace`` Chrome-trace path to every module whose ``run()``
accepts ``telemetry_path`` (the serving/cluster/fault benchmarks).
"""
import argparse
import cProfile
import inspect
import io
import pstats
import sys
import traceback
from pathlib import Path

MODULES = [
    "fig01_llm_multitask",
    "fig02_access_pattern",
    "table1_prediction_accuracy",
    "table2_template_mix",
    "fig06_microbench",
    "fig07_end_to_end",
    "fig08_prediction_ablation",
    "fig09_pipeline",
    "fig10_hardware",
    "fig11_overhead",
    "fig12_suv",
    "fig13_rt_be",
    "sim_throughput",
    "serve_oversub",
    "cluster_oversub",
    "p2p_prefetch",
    "fault_recovery",
]

PROFILE_TOP_N = 20


def main() -> None:
    ap = argparse.ArgumentParser()
    ap.add_argument("--only", default=None, help="module name prefix filter")
    ap.add_argument(
        "--profile", action="store_true",
        help=f"cProfile each module; print top-{PROFILE_TOP_N} by cumulative "
        "time to stderr",
    )
    ap.add_argument(
        "--telemetry", type=Path, default=None, metavar="DIR",
        help="write a DIR/<module>.trace Chrome trace for each module whose "
        "run() accepts telemetry_path",
    )
    args = ap.parse_args()
    if args.telemetry is not None:
        args.telemetry.mkdir(parents=True, exist_ok=True)

    print("name,us_per_call,derived")
    failures = 0
    for mod_name in MODULES:
        if args.only and not mod_name.startswith(args.only):
            continue
        try:
            mod = __import__(f"benchmarks.{mod_name}", fromlist=["run"])
            kwargs = {}
            if (
                args.telemetry is not None
                and "telemetry_path"
                in inspect.signature(mod.run).parameters
            ):
                kwargs["telemetry_path"] = (
                    args.telemetry / f"{mod_name}.trace"
                )
            if args.profile:
                prof = cProfile.Profile()
                rows = prof.runcall(mod.run, **kwargs)
                buf = io.StringIO()
                stats = pstats.Stats(prof, stream=buf)
                stats.sort_stats("cumulative").print_stats(PROFILE_TOP_N)
                print(f"==== profile: {mod_name} ====", file=sys.stderr)
                print(buf.getvalue(), file=sys.stderr, flush=True)
            else:
                rows = mod.run(**kwargs)
            for name, us, derived in rows:
                print(f"{name},{us:.1f},{derived}", flush=True)
        except Exception:  # noqa: BLE001
            failures += 1
            print(f"{mod_name},0,ERROR", flush=True)
            traceback.print_exc(file=sys.stderr)
    if failures:
        sys.exit(1)


if __name__ == "__main__":
    main()
