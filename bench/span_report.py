"""Where a configuration's time goes, by the live path's own spans.

    python bench/span_report.py --config trio-1.5x --mixes zipf-poisson,zipf-closed128 --seed 5 --seconds 12

One process builds the configuration's server once (with the program's own
weights: the timing does not depend on their values) and, for each traffic
mix in turn, drives the same seeded load twice through ``bench.harness``:
once untimed, as ``--trace 0`` runs it, and once timed and traced, as
``--trace 1`` runs it. The trace covers the window's first
``harness.TRACE_WINDOW_S`` seconds. For each mix it prints one JSON line:

- ``traced``: the harness's ``idle_gaps`` beside ``bench.spans``'s
  ``idle_by_span``, self time per span, ``evict_share``, ``h2d_gbps``,
  ``plan_ms``, ``step_idle_share``, the device ops that ran during
  ``msched.fetch`` spans on each line of the TPU plane (do the copies show
  on the device?), and the slices per second;
- ``untraced``: the slices per second over the same seconds, so that
  traced over untraced is what tracing costs when it is on;
- ``queue_wait_ms``: the 95th percentile of each run's queue wait;
- ``span_cost_us``: the host time of one span with two stats and no
  profiler running, times ``spans_per_slice`` what the spans cost when
  tracing is off.

``--out`` names a file to append the lines to as well.
"""
from __future__ import annotations

import argparse
import bisect
import glob
import json
import os
import shutil
import sys
import tempfile
import time
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def span_cost_us(n: int = 200_000) -> float:
    """Host microseconds of one span with two stats, no profiler running."""
    from jax.profiler import TraceAnnotation

    t = time.perf_counter()
    for i in range(n):
        with TraceAnnotation("msched.cost", task=1, step=i):
            pass
    return 1e6 * (time.perf_counter() - t) / n


def ops_during(trace, spans, top: int = 5) -> dict:
    """TPU line -> its ops (first 60 characters) with the most ns starting
    inside the given spans, which do not overlap."""
    from bench import trace_reduce

    out = {}
    iv = sorted((s.start, s.end) for s in spans)
    starts = [a for a, _ in iv]
    for plane in trace_reduce.device_planes(trace):
        for line, events in plane["lines"].items():
            acc = defaultdict(float)
            for name, s, d in events:
                i = bisect.bisect_right(starts, s) - 1
                if i >= 0 and s < iv[i][1]:
                    acc[name[:60]] += d
            if acc:
                out[f"{plane['name']} {line}"] = sorted(acc.items(), key=lambda x: -x[1])[:top]
    return out


def one_run(server, models, mix, seconds, seed, trace_dir):
    """Drive the mix once; returns the session and its slices per second
    over the window's first ``TRACE_WINDOW_S`` seconds: the slices that
    ended in them over the time from the window's start to the last of them
    (a swapping cell ends only a few slices in that time)."""
    import importlib

    import numpy as np

    from bench import harness

    sess = harness.Session(server, [m["vocab_size"] for m in models], timing=trace_dir is not None,
                           trace_dir=trace_dir)
    sess.warm_up()
    load = importlib.import_module(f"bench.load.{mix['kind']}")
    load.drive(sess, mix, seconds, np.random.default_rng([seed, 0]))
    span = min(seconds, harness.TRACE_WINDOW_S)
    ends = [s.t for s in sess.slices if sess.t0 <= s.t <= sess.t0 + span]
    for q in server.queues.values():
        q.clear()
    # the timed session wrapped each task's step; later runs start unwrapped
    for task in server.runtime.tasks.values():
        vars(task).pop("run_step", None)
    return sess, (len(ends) / (ends[-1] - sess.t0) if ends else 0.0)


def report(server, cfg: dict, cell: str, mix: dict, seconds: float, seed: int, cost_us: float) -> dict:
    """The JSON line of one mix: an untimed run, then a traced one."""
    from bench import harness, spans, trace_reduce

    queue_wait = harness.load_reader(harness.ROOT, "queue_wait_ms").read
    plain, plain_rate = one_run(server, cfg["models"], mix, seconds, seed, None)
    trace_dir = tempfile.mkdtemp(prefix="span-report-")
    try:
        traced, traced_rate = one_run(server, cfg["models"], mix, seconds, seed, trace_dir)
        (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
        tr = trace_reduce.load_xplane(path)
        program = spans.load(path)
    finally:
        shutil.rmtree(trace_dir, ignore_errors=True)
    w0, w1 = trace_reduce.window(tr)
    summary = spans.summarize(tr, program, w0, w1)
    gaps = trace_reduce.idle_gaps(tr, w0, w1)
    slices = traced_rate * (w1 - w0) / 1e9
    fetches = [s for s in spans.in_window(program, w0, w1) if s.name == spans.FETCH]
    return {
        "cell": cell,
        "seed": seed,
        "traced": dict(
            summary,
            window_s=(w1 - w0) / 1e9,
            busy_s=(trace_reduce.busy_ns(tr, w0, w1) or 0.0) / 1e9,
            idle_gaps=[[f"{k} ({n} gaps)", ns / 1e9] for k, (n, ns) in sorted(gaps.items(), key=lambda x: -x[1][1])],
            slices_per_s=traced_rate,
            ops_during_fetch=ops_during(tr, fetches),
        ),
        "untraced": {"slices_per_s": plain_rate},
        "queue_wait_ms": {"untraced": queue_wait(plain), "traced": queue_wait(traced)},
        "span_cost_us": cost_us,
        "spans_per_slice": summary["spans"] / slices if slices else None,
    }


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mixes", required=True, help="comma-separated traffic mixes")
    ap.add_argument("--seed", type=int, default=0)
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--out", help="also append each line to this file")
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax

    from bench import harness

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    (conf,) = [c for c in bench["configs"] if c["name"] == args.config]
    cfg = json.loads(open(os.path.join(ROOT, conf["file"])).read())
    server = harness.build_server(cfg, reduced=False)
    cost = span_cost_us()
    for name in args.mixes.split(","):
        mix = json.loads(open(os.path.join(ROOT, "bench", "traffic", f"{name}.json")).read())
        line = report(server, cfg, f"{args.config}.{name}", mix, args.seconds, args.seed, cost)
        print(json.dumps(line), flush=True)
        if args.out:
            with open(args.out, "a") as f:
                f.write(json.dumps(line) + "\n")
    return 0


if __name__ == "__main__":
    sys.exit(main())
