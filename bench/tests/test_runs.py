"""One run of each cell end to end at the reduced CPU cut, through the
harness's body (``bench/run.py``'s ``main`` refuses a device that is not a
TPU), each printing a well-formed result line."""
import json
import os
import subprocess
import sys
import time

import pytest

from bench import harness
from bench.tests.support import CPU_PEAKS, ROOT

BENCH = json.loads((ROOT / "BENCHMARK.json").read_text())
CELLS = [c["name"] for c in BENCH["workloads"]]


def _expected(cell, trace):
    spec = harness.load_cell(ROOT, cell)
    return {m["name"]: m["unit"] for m in spec["per_layer" if trace else "end_to_end"]}


# metrics that only the chip's trace gives: a CPU run has no TPU plane
DEVICE_ONLY = {"step_roofline", "device_idle_share", "device_idle_share.open",
               "step_idle_share.open", "step_idle_share.closed"}


@pytest.mark.parametrize("cell,trace", [(c, False) for c in CELLS] + [(c, True) for c in CELLS])
def test_run_cell_result_line(reduced_root, cell, trace):
    r = harness.run_cell(cell, 2**33 + 17, 1.0, trace, time.perf_counter(),
                         root=reduced_root, reduced=True, peaks=CPU_PEAKS)
    line = json.loads(json.dumps(r))
    assert list(line)[:5] == ["correct", "attempted", "failed", "metrics", "device"]
    assert list(line)[-1] == "checks"
    assert line["correct"] is True, line["checks"]
    assert line["attempted"] >= 1 and line["failed"] == 0
    want = _expected(cell, trace)
    got = {k: v["unit"] for k, v in line["metrics"].items()}
    assert got == {k: u for k, u in want.items() if not (trace and k in DEVICE_ONLY)}
    assert all(v["value"] >= 0 for v in line["metrics"].values())
    dev = line["device"]
    assert (dev["platform"], dev["count"]) == ("cpu", 1) and "memory_peak_bytes" in dev
    if trace:
        assert {"busy_s", "window_s"} <= set(dev) and "breakdown" in line
        assert dev["window_s"] >= 1.0
    else:
        assert "setup_s" in line["metrics"] and len(line["metrics"]) >= 2
    assert line["checks"]["logit_error"]["value"] <= line["checks"]["logit_error"]["limit"]


def test_main_refuses_a_cpu():
    env = dict(os.environ, JAX_PLATFORMS="cpu")
    p = subprocess.run(
        [sys.executable, str(ROOT / "bench" / "run.py"), "--workload", CELLS[0],
         "--seed", "1", "--seconds", "1", "--trace", "0"],
        capture_output=True, text=True, env=env, timeout=300,
    )
    assert p.returncode != 0
    assert p.stdout == ""
    assert "no TPU" in p.stderr
