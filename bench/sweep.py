"""Find the highest open-loop rate a configuration sustains (its knee).

    python bench/sweep.py --config trio-1.5x --rates 2,3,4,5 --seconds 51 --seed 3

One process builds the configuration's server once, warms it up, and then
offers each rate in turn for ``--seconds``, with ``bench/load/open_loop.py``
and the popularity of ``--mix``. For each rate it prints one JSON line: the
requests offered and answered in the window, the latency median and 95th
percentile, how many of the window's requests were still unanswered at its
close, and the mean latency of the window's first and last thirds. A rate
the server sustains ends its window with a short backlog and a last third no
slower than the first. The benchmark's cells offer a fixed rate recorded in
their traffic file; this script is how that rate was found.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--config", required=True)
    ap.add_argument("--mix", default="zipf-poisson")
    ap.add_argument("--rates", required=True, help="comma-separated req/s")
    ap.add_argument("--seconds", type=float, required=True)
    ap.add_argument("--drain", type=float, default=30.0)
    ap.add_argument("--seed", type=int, default=0)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import jax
    import numpy as np

    from bench import harness
    from bench.load import open_loop
    from bench.record import percentile

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    bench = json.loads(open(os.path.join(ROOT, "BENCHMARK.json")).read())
    (conf,) = [c for c in bench["configs"] if c["name"] == args.config]
    cfg = json.loads(open(os.path.join(ROOT, conf["file"])).read())
    mix = json.loads(open(os.path.join(ROOT, "bench", "traffic", f"{args.mix}.json")).read())
    models = cfg["models"]
    seeds = [int(s) for s in np.random.default_rng([args.seed, 1]).integers(0, 2**31, len(models))]
    server = harness.build_server(cfg, reduced=False)
    harness.install_weights(server, models, seeds)
    sess = harness.Session(server, [m["vocab_size"] for m in models], timing=False, trace_dir=None)
    sess.warm_up()
    rng = np.random.default_rng([args.seed, 0])
    for rate in [float(r) for r in args.rates.split(",")]:
        t = time.perf_counter()
        out = open_loop.drive(sess, dict(mix, rate_rps=rate, drain_s=args.drain), args.seconds, rng)
        lat = sorted(out.latencies_s)
        window = [x for x in sess.requests if x.in_window]
        third = max(1, len(window) // 3)
        mean = lambda xs: sum(((x.done or sess.t_end + args.drain) - x.due) for x in xs) / len(xs)  # noqa: E731
        line = {
            "rate_rps": rate,
            "offered": out.attempted,
            "answered_in_window": len(sess.answered_in_window()),
            "failed": out.failed,
            "unanswered_at_close": sum(1 for x in window if x.done is None or x.done > sess.t_end),
            "latency_p50_ms": 1000 * percentile(lat, 50),
            "latency_p95_ms": 1000 * percentile(lat, 95),
            "first_third_mean_ms": 1000 * mean(window[:third]),
            "last_third_mean_ms": 1000 * mean(window[-third:]),
            "slices": len(sess.slices),
            "h2d_bytes": sess.slices[-1].counters.in_bytes - sess.base.in_bytes if sess.slices else 0,
            "wall_s": time.perf_counter() - t,
        }
        print(json.dumps(line), flush=True)
        # let the queues drain before the next rate
        sess.serve(sess.clock() + 120.0, lambda now, answered: None)
    return 0


if __name__ == "__main__":
    sys.exit(main())
