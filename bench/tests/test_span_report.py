"""The span report's line at the reduced CPU cut: both runs of a swapping
mix, the program's numbers read from the traced one."""
import json

from bench import harness, span_report
from bench.tests.support import ROOT


def test_report_of_a_swapping_mix(reduced_root):
    cfg = json.loads((reduced_root / "bench/configs/trio-1.5x.json").read_text())
    mix = json.loads((ROOT / "bench/traffic/zipf-closed128.json").read_text())
    server = harness.build_server(cfg, reduced=True)
    line = span_report.report(server, cfg, "trio-1.5x.zipf-closed128", mix, 1.0, 2**33 + 5, 0.5)
    json.dumps(line)
    traced = line["traced"]
    assert traced["h2d_gbps"] > 0 and traced["plan_ms"] > 0
    assert 0 <= traced["evict_share"] <= 100
    # a CPU run has no TPU plane: no idle time to put anywhere
    assert traced["idle_by_span"] == [] and traced["step_idle_share"] is None
    assert traced["self_s"]["msched.step.logits"] > 0
    assert traced["slices_per_s"] > 0 and line["untraced"]["slices_per_s"] > 0
    assert line["queue_wait_ms"]["untraced"] >= 0 and line["queue_wait_ms"]["traced"] >= 0
    assert line["spans_per_slice"] > 5
    assert not any(server.queues.values())
    assert all("run_step" not in vars(t) for t in server.runtime.tasks.values())
