"""A configuration, a traffic mix, a metric and a model family that a later
change adds are found by name, with no edit to a file that is there."""
import hashlib
import importlib
import json
import shutil
import statistics
import sys
import time

import numpy as np
import pytest

from bench import BenchError, costs, families, harness, reference, weights
from bench.tests.support import CPU_PEAKS, ROOT, make_reduced_root, reduce_model


def _digest(root):
    return {p.relative_to(root): hashlib.sha256(p.read_bytes()).hexdigest()
            for p in sorted((root / "bench").rglob("*")) if p.is_file() and "__pycache__" not in p.parts}


def test_new_files_found_by_name(tmp_path):
    root = make_reduced_root(tmp_path / "checkout")
    before = _digest(root)
    bench = json.loads((root / "BENCHMARK.json").read_text())
    # a new configuration: the fit pool with two of the models
    cfg = json.loads((root / "bench/configs/trio-fit.json").read_text())
    cfg["name"] = "duo-fit"
    cfg["models"] = cfg["models"][:2]
    (root / "bench/configs/duo-fit.json").write_text(json.dumps(cfg))
    # a new mix of an existing kind, and a new metric's reader
    (root / "bench/traffic/closed8.json").write_text(json.dumps(
        {"kind": "closed_loop", "why": "8 outstanding", "concurrency": 8, "zipf_s": 0.5, "block": 4}))
    (root / "bench/metrics/answers_per_slice.py").write_text(
        "def read(rec):\n    s = rec.window_slices()\n    return sum(x.answered for x in s) / len(s) if s else None\n")
    bench["configs"].append(dict(bench["configs"][1], name="duo-fit", file="bench/configs/duo-fit.json"))
    bench["workloads"].append({"name": "duo-fit.closed8", "config": "duo-fit", "traffic": "closed8",
                               "chips": 1, "why": "test"})
    bench["per_layer"].append({"name": "answers_per_slice", "unit": "req", "better": "higher",
                               "source": "program_counter", "layer": "serving loop",
                               "moves": "throughput_rps", "workloads": ["duo-fit.closed8"]})
    (root / "BENCHMARK.json").write_text(json.dumps(bench))

    r = harness.run_cell("duo-fit.closed8", 9, 1.0, True, time.perf_counter(),
                         root=root, reduced=True, peaks=CPU_PEAKS)
    assert r["correct"] is True
    assert 0 < r["metrics"]["answers_per_slice"]["value"] <= 8
    assert r["attempted"] > 0
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert set(after) - set(before) == {p for p in after if p.name in
                                        ("duo-fit.json", "closed8.json", "answers_per_slice.py")}
    shutil.rmtree(root)


# A new family, as a later change would add it: Qwen3 with a leaf kind of its
# own, whose step reads a share of its MLPs that its input sets.
PROBE = '''"""Qwen3, whose step reads, of its MLPs, the share token / 13."""
import jax.numpy as jnp

from bench import costs
from bench.families import qwen3
from bench.families.qwen3 import logits, reduce  # noqa: F401
from bench.weights import Leaf

draws = {"zero": lambda key, shape: jnp.zeros(shape, jnp.float32)}


def layout(m):
    tree = qwen3.layout(m)
    tree["head"]["final_norm"] = Leaf(tree["head"]["final_norm"].shape, "float32", "zero")
    return tree


def step_cost(m, params, token):
    flops, nbytes = costs.read_once(layout(m))
    mlp = sum(int(w.size) for w in params["layers"]["mlp"].values())
    skipped = mlp * (1 - token / 13)
    return flops - 2 * skipped, nbytes - 2 * skipped
'''


def test_new_family_is_one_file(tmp_path, monkeypatch):
    root = make_reduced_root(tmp_path / "checkout")
    before = _digest(root)
    (root / "bench/families").mkdir()
    (root / "bench/families/probe.py").write_text(PROBE)
    # the checkout's bench/families/ is where the package finds its families
    monkeypatch.setattr(families, "__path__", [*families.__path__, str(root / "bench/families")])
    importlib.invalidate_caches()
    try:
        # qwen3-1.7b as the program serves it, under the new model_type
        full = json.loads((ROOT / "bench/configs/trio-fit.json").read_text())["models"][0]
        model = reduce_model(dict(full, model_type="probe"))
        cfg = json.loads((root / "bench/configs/trio-fit.json").read_text())
        cfg.update(name="probe-fit", models=[model])
        (root / "bench/configs/probe-fit.json").write_text(json.dumps(cfg))
        (root / "bench/traffic/closed8.json").write_text(json.dumps(
            {"kind": "closed_loop", "why": "8 outstanding", "concurrency": 8, "zipf_s": 0.5, "block": 4}))
        (root / "bench/metrics/step_flops.py").write_text("def read(rec):\n    return rec.costs[0][0]\n")
        (root / "bench/metrics/answered.py").write_text("def read(rec):\n    return len(rec.answered_in_window())\n")
        bench = json.loads((root / "BENCHMARK.json").read_text())
        bench["configs"].append(dict(bench["configs"][1], name="probe-fit", file="bench/configs/probe-fit.json"))
        bench["workloads"].append({"name": "probe-fit.closed8", "config": "probe-fit", "traffic": "closed8",
                                   "chips": 1, "why": "test"})
        for name in ("step_flops", "answered", "mfu.probe", "step_roofline.probe"):
            bench["per_layer"].append({"name": name, "unit": "%", "better": "higher", "source": "host_clock",
                                       "layer": "model step", "moves": "throughput_rps",
                                       "workloads": ["probe-fit.closed8"]})
        (root / "BENCHMARK.json").write_text(json.dumps(bench))

        # a CPU trace has no TPU plane: ten step programs in a millisecond
        summarize = harness._summarize_trace

        def with_steps(trace_dir):
            s = summarize(trace_dir)
            s.step_runs = {0: (10, 1e-3)}
            return s

        monkeypatch.setattr(harness, "_summarize_trace", with_steps)
        r = harness.run_cell("probe-fit.closed8", 2**31 + 5, 1.0, True, time.perf_counter(),
                             root=root, reduced=True, peaks=CPU_PEAKS)
        assert r["correct"] is True, r["checks"]

        params = weights.generate(model, 3)
        assert not np.any(np.asarray(params["head"]["final_norm"]))  # the family's own draw
        probe = sys.modules["bench.families.probe"]
        each = [probe.step_cost(model, params, tok) for tok in harness.INPUTS]
        assert len(set(each)) == 13  # a cost that differs by input
        flops, nbytes = (statistics.fmean(x) for x in zip(*each))
        with pytest.raises(BenchError, match="depends on its input"):
            costs.step_cost(model)
        m = {k: v["value"] for k, v in r["metrics"].items()}
        assert m["step_flops"] == pytest.approx(flops)
        assert m["mfu.probe"] == pytest.approx(100 * m["answered"] * flops / 1.0 / CPU_PEAKS["bf16_flops_per_s"])
        least = max(flops / CPU_PEAKS["bf16_flops_per_s"], nbytes / CPU_PEAKS["hbm_bytes_per_s"])
        assert m["step_roofline.probe"] == pytest.approx(100 * 10 * least / 1e-3)
    finally:
        sys.modules.pop("bench.families.probe", None)
    after = _digest(root)
    assert {k: v for k, v in after.items() if k in before} == before
    assert {p.name for p in set(after) - set(before)} == {
        "probe.py", "probe-fit.json", "closed8.json", "step_flops.py", "answered.py"}
    shutil.rmtree(root)


@pytest.mark.parametrize("call", [
    weights.layout,
    lambda m: weights.make_params(m, None),
    lambda m: reference.logits(m, {}, [1]),
    costs.step_cost,
    reduce_model,
], ids=["layout", "make_params", "reference", "step_cost", "reduce_model"])
def test_unknown_model_type_names_the_file_to_add(call):
    with pytest.raises(BenchError, match=r"add bench/families/nonesuch\.py"):
        call({"model_type": "nonesuch", "arch": "nonesuch-1b"})
