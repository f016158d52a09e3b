"""Multi-model serving launcher: MSched-scheduled colocation.

    PYTHONPATH=src python -m repro.launch.serve \
        --archs qwen3-1.7b,mamba2-1.3b,minicpm-2b --oversub 1.5 --requests 24

Hosts several models at their published widths (``--reduced`` for the CPU
cut) under one device-memory budget; the MSched coordinator proactively
migrates each model's working set on its slice.
"""
import argparse
import time


def main():
    ap = argparse.ArgumentParser()
    ap.add_argument("--archs", default="qwen3-1.7b,mamba2-1.3b,minicpm-2b")
    ap.add_argument("--reduced", action="store_true")
    ap.add_argument("--oversub", type=float, default=1.5)
    ap.add_argument("--requests", type=int, default=24)
    ap.add_argument("--wall-budget-s", type=float, default=600.0)
    args = ap.parse_args()

    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()

    from repro.core.simulator import percentile
    from repro.runtime.serve_loop import MultiModelServer, Request

    archs = args.archs.split(",")
    server = MultiModelServer(archs, oversub=args.oversub, reduced=args.reduced)
    print(
        f"{len(archs)} models, aggregate {server.footprint_bytes/2**20:.1f} MiB, "
        f"budget {server.budget_bytes/2**20:.1f} MiB "
        f"({100*args.oversub:.0f}% oversubscription)"
    )
    t0 = time.perf_counter()
    reqs = [Request(model=i % len(archs), arrival_s=time.perf_counter()) for i in range(args.requests)]
    for req in reqs:
        server.submit(req)
    stats = server.serve(wall_budget_s=args.wall_budget_s)
    for m in range(len(archs)):
        lat = sorted(r.answered_s - r.submitted_s for r in reqs if r.model == m and r.answered_s is not None)
        p99 = f"{1e3 * percentile(lat, 99):.0f}ms" if lat else "-"
        print(f"model {m} ({archs[m]}): served={stats.served[m]} p99={p99} (host clock)")
    print(
        f"migrated_in={stats.migrated_in_bytes/2**20:.1f}MiB "
        f"migrated_out={stats.migrated_out_bytes/2**20:.1f}MiB "
        f"faults={stats.demand_faults} wall={time.perf_counter()-t0:.1f}s"
    )
    unanswered = args.requests - sum(stats.served.values())
    if unanswered:
        raise SystemExit(f"{unanswered} requests unanswered in the wall budget")


if __name__ == "__main__":
    main()
