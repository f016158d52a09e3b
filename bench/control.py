"""Readings that the correctness limit of a configuration is set from.

    python bench/control.py --workload trio-1.5x.zipf-closed128 --seeds 1,2,3 --seconds 8

In one process, for each seed: the benchmark's weights for that seed go into
the cell's server, the cell's traffic runs for ``--seconds`` through the
timed path, and once its device state is freed the served answers are
compared with the float32 reference by ``harness.compare``, as a run of
``bench/run.py`` compares them. That gives the program's reading, the
largest logit error of a served answer. The control is the same reference
computed with float8 weights (``bench.reference``, ``precision="fp8"``),
put in the program's place as the answers to the same models, seeds and
tokens, and judged by the same ``harness.compare`` and limit. One JSON line
per seed, then a summary: the program's largest reading (the limit's lower
end), the control's smallest (its upper end), and whether every program run
came out correct and every control run not.
"""
from __future__ import annotations

import argparse
import json
import os
import sys
import tempfile
import time
import types
from collections import defaultdict

ROOT = os.path.dirname(os.path.dirname(os.path.abspath(__file__)))
for p in (os.path.join(ROOT, "src"), ROOT):
    if p not in sys.path:
        sys.path.insert(0, p)


def control_answers(models, seeds, tokens_by_model):
    """The float8 reference's logits as the answers of a session, in the
    form ``harness.compare`` reads: one answer to each given token of each
    model."""
    from bench import reference, weights

    answers = {}
    for i, m in enumerate(models):
        toks = sorted(tokens_by_model.get(i, ()))
        if not toks:
            continue
        params = weights.generate(m, seeds[i])
        low = reference.logits(m, params, toks, precision="fp8")
        del params
        for tok, row in zip(toks, low):
            answers[(i, tok)] = {b"control": row}
    return types.SimpleNamespace(answers=answers, n_answers=len(answers), malformed=0, unchecked=0)


def main(argv=None) -> int:
    ap = argparse.ArgumentParser(description=__doc__.split("\n\n")[0])
    ap.add_argument("--workload", required=True)
    ap.add_argument("--seeds", required=True, help="comma-separated")
    ap.add_argument("--seconds", type=float, required=True)
    args = ap.parse_args(argv)

    os.environ["JAX_COMPILATION_CACHE_DIR"] = os.path.join(ROOT, ".jax_cache")
    os.environ.setdefault("TPU_LOG_DIR", os.path.join(tempfile.gettempdir(), "tpu_logs"))
    from repro.launch.compile_cache import enable_compile_cache

    enable_compile_cache()
    import importlib

    import jax
    import numpy as np

    from bench import harness, integrity

    if jax.devices()[0].platform != "tpu":
        print("no TPU", file=sys.stderr)
        return 1
    spec = harness.load_cell(ROOT, args.workload)
    cfg, mix = spec["config"], spec["traffic"]
    models = cfg["models"]
    load = importlib.import_module(f"bench.load.{mix['kind']}")
    server = harness.build_server(cfg, reduced=False)
    limit = cfg["correct"]["logit_error"]
    program, control = [], []
    for seed in [int(s) for s in args.seeds.split(",")]:
        t = time.perf_counter()
        seeds = [int(s) for s in np.random.default_rng([seed, 1]).integers(0, 2**31, len(models))]
        harness.release(server)
        for q in server.queues.values():
            q.clear()
        expected = harness.install_weights(server, models, seeds)
        sess = harness.Session(server, [m["vocab_size"] for m in models], timing=False, trace_dir=None)
        sess.warm_up()
        load.drive(sess, mix, args.seconds, np.random.default_rng([seed, 0]))
        bad = integrity.mismatched(server, expected)
        harness.release(server)
        got = harness.compare(models, seeds, sess, limit, len(bad))
        tokens = defaultdict(set)
        for m, tok in sess.answers:
            tokens[m].add(tok)
        low = harness.compare(models, seeds, control_answers(models, seeds, tokens), limit)
        program.append((got["correct"], got["checks"]["logit_error"]["value"]))
        control.append((low["correct"], low["checks"]["logit_error"]["value"]))
        line = {
            "seed": seed,
            "program_correct": got["correct"],
            "program_error": program[-1][1],
            "control_correct": low["correct"],
            "control_error": control[-1][1],
            "limit": limit,
            "answers": got["checks"]["answers_compared"]["value"],
            "malformed": got["checks"]["answers_malformed"]["value"],
            "unchecked": got["checks"]["answers_unchecked"]["value"],
            "weights_mismatched": len(bad),
            "distinct": sum(len(v) for v in sess.answers.values()),
            "tokens": {i: len(v) for i, v in tokens.items()},
            "wall_s": time.perf_counter() - t,
        }
        print(json.dumps(line), flush=True)
    summary = {
        "program_error_max": max(e for _, e in program),
        "control_error_min": min(e for _, e in control),
        "limit": limit,
        "program_all_correct": all(c for c, _ in program),
        "control_none_correct": not any(c for c, _ in control),
    }
    print(json.dumps(summary))
    return 0


if __name__ == "__main__":
    sys.exit(main())
