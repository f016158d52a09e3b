"""A configuration, a traffic mix and a metric that a later change adds are
found by name, with no edit to a file that is there."""
import hashlib
import json
import shutil
import time

from bench import harness
from bench.tests.support import CPU_PEAKS, make_reduced_root


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
