"""Helpers for the tests: a checkout-like root at the reduced CPU cut.

``make_reduced_root`` copies ``BENCHMARK.json`` and the benchmark's data
files into a directory and rewrites each configuration's models at the
program's ``.reduced()`` sizes, with 1 MiB pages and the correctness limit
of that cut."""
import json
import os
import shutil
import sys
from pathlib import Path

ROOT = Path(__file__).resolve().parents[2]
for p in (str(ROOT / "src"), str(ROOT)):
    if p not in sys.path:
        sys.path.insert(0, p)

# At the reduced cut the served answers' logit error is 0.037-0.050 and the
# float8 control's 0.47-0.76 (seeds 0-3 on the CPU); a wrong answer's is
# several (test_checks.py).
REDUCED_LIMIT = 0.15


def reduce_model(m: dict) -> dict:
    """Model entry ``m`` at the program's ``.reduced()`` cut, by its family."""
    from bench import families

    return families.load(m["model_type"]).reduce(m)


PAGE = 1 << 20
KV_BYTES = 1 << 20  # each task's KV placeholder


def _pool_oversub(models, share: float) -> float:
    """The program sizes the pool as footprint / oversub. The reduced leaves
    are far below a page, so page rounding dominates; this returns the
    oversub that makes the pool ``share`` of the page-rounded total, as 1.5x
    makes it two thirds at full width."""
    from bench import weights

    leaves = [leaf.nbytes for m in models for _, leaf in weights.leaves_with_paths(weights.layout(m))]
    footprint = sum(leaves) + KV_BYTES * len(models)
    pages = sum(-(-n // PAGE) for n in leaves) + len(models)
    return footprint / (share * pages * PAGE)


def make_reduced_root(dest: Path) -> Path:
    """Pages of 1 MiB keep the full-width geometry's proportions at the
    reduced cut: each model's KV placeholder is one page, as it is in 4 MiB
    pages, and the program's predicted KV extent outgrows it slowly (one
    page per 256 steps)."""
    bench = json.loads((ROOT / "BENCHMARK.json").read_text())
    (dest / "bench" / "configs").mkdir(parents=True)
    for sub in ("traffic", "metrics"):
        shutil.copytree(ROOT / "bench" / sub, dest / "bench" / sub)
    for conf in bench["configs"]:
        cfg = json.loads((ROOT / conf["file"]).read_text())
        cfg["models"] = [reduce_model(m) for m in cfg["models"]]
        cfg["page_bytes"] = PAGE
        if "oversub" in cfg["pool"]:
            cfg["pool"] = {"oversub": _pool_oversub(cfg["models"], 1 / cfg["pool"]["oversub"])}
        cfg["correct"] = {"logit_error": REDUCED_LIMIT}
        (dest / conf["file"]).write_text(json.dumps(cfg))
    (dest / "BENCHMARK.json").write_text(json.dumps(bench))
    return dest


CPU_PEAKS = {"bf16_flops_per_s": 1e12, "hbm_bytes_per_s": 1e11, "hbm_bytes": 1e10}
