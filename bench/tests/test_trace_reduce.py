"""The reduction from a trace to busy time, step device time and idle gaps,
on a hand-made trace and on a small trace recorded on the chip."""
import json
from pathlib import Path

import pytest

from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def _ev(name, start, dur):
    return [name, start, dur]


def _hand_trace():
    host = {"name": "/host:CPU", "lines": {"python": [
        _ev("bench.window", 0, 1000),
        _ev("bench.serve", 0, 700),
        _ev("bench.step:0", 100, 200),
        _ev("bench.step:2", 400, 200),
        _ev("bench.wait", 700, 300),
    ]}}
    dev = {"name": "/device:TPU:0", "lines": {
        "XLA Modules": [_ev("jit(<lambda>)", 120, 150), _ev("jit(<lambda>)", 420, 100), _ev("other", 650, 10)],
        "XLA Ops": [_ev("fusion.1", 120, 100), _ev("fusion.2", 200, 70),  # overlapping: union 120-270
                    _ev("fusion.1", 420, 100), _ev("copy", 650, 10),
                    _ev("fusion.3", 990, 50)],  # runs past the window's end
    }}
    return [host, dev, {"name": "/host:metadata", "lines": {}}]


def test_hand_trace():
    t = _hand_trace()
    w0, w1 = tr.window(t)
    assert (w0, w1) == (0, 1000)
    assert tr.busy_ns(t, w0, w1) == 150 + 100 + 10 + 10
    assert tr.step_runs(t, w0, w1) == {0: (1, 150), 2: (1, 100)}
    assert tr.top_ops(t, w0, w1)[0] == ("fusion.1", 200)
    gaps = tr.idle_gaps(t, w0, w1)
    # holes: 0-120 and 270-420 (serve), 520-650 (in step:2 until 600, mid 585),
    # 660-990 (wait, mid 825)
    assert gaps["in serve outside steps"] == (2, 120 + 150)
    assert gaps["in step"] == (1, 130)
    assert gaps["waiting for arrivals"] == (1, 330)


def test_union_merges_touching_and_nested():
    assert tr.union([(5, 6), (0, 2), (1, 3), (3, 4), (10, 12), (10, 11)]) == [(0, 4), (5, 6), (10, 12)]


def test_window_must_be_unique():
    t = _hand_trace()
    t[0]["lines"]["python"].append(_ev("bench.window", 5, 5))
    with pytest.raises(ValueError):
        tr.window(t)


def test_recorded_chip_trace():
    """22 ms of a traced trio-fit window, recorded on a TPU v5e chip: steps
    of mamba2-1.3b with the harness's host spans around them; op names cut
    to their first 40 characters. The expected numbers in the file were
    computed apart, by marking each busy nanosecond on a grid."""
    rec = json.loads((DATA / "trace_slice.json").read_text())
    t, (w0, w1) = rec["planes"], rec["window"]
    want = rec["expected"]
    assert len(tr.device_planes(t)) == 1
    assert want["step_runs"]
    busy = tr.busy_ns(t, w0, w1)
    # the expected value was rasterized at 1 ns, so it may differ by 1 ns per edge
    assert busy == pytest.approx(want["busy_ns"], rel=1e-3)
    runs = tr.step_runs(t, w0, w1)
    assert {int(k): tuple(v) for k, v in want["step_runs"].items()} == {
        m: (n, pytest.approx(ns, rel=1e-9)) for m, (n, ns) in runs.items()
    }
    assert 0 < busy < w1 - w0
