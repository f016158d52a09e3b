"""The reduction of the program's own spans: self time, idle time by span,
and the live path's numbers, on a hand-made trace, on a trace recorded on
the CPU, and on traces with no program span."""
import glob
import json
from pathlib import Path

import pytest

from bench import spans as sp
from bench import trace_reduce as tr

DATA = Path(__file__).resolve().parent / "data"


def _span(name, start, end, line="python", **stats):
    return sp.Span(name, start, end, stats, line)


# one slice: a switch (plan, evict, fetch), a demand fault, two steps
SPANS = [
    _span("msched.slice", 0, 900, task=0, slice=3),
    _span("msched.switch", 10, 410, task=0, in_bytes=300, out_bytes=100),
    _span("msched.plan", 10, 60, pages_in=3, pages_out=1),
    _span("msched.evict", 60, 160, nbytes=100, segments=1),
    _span("msched.fetch", 160, 400, nbytes=300, segments=2),
    _span("msched.fault_service", 420, 480, task=0, nbytes=60),
    _span("msched.evict", 425, 430, nbytes=10, segments=1),
    _span("msched.fetch", 430, 470, nbytes=60, segments=1),
    _span("msched.step", 500, 700, task=0, step=7),
    _span("msched.step.dispatch", 500, 540),
    _span("msched.step.logits", 540, 690),
    _span("msched.step", 700, 880, task=0, step=8),
    _span("msched.step.dispatch", 700, 720),
    _span("msched.step.logits", 720, 880),
]


def _trace():
    host = {"name": "/host:CPU", "lines": {"python": [
        ["bench.window", 0, 1000],
        ["bench.serve", 0, 1000],
    ]}}
    ops = [[f"fusion.{i}", s, e - s] for i, (s, e) in enumerate(
        [(50, 55), (150, 155), (395, 430), (560, 680), (730, 870), (950, 980)])]
    dev = {"name": "/device:TPU:0", "lines": {"XLA Ops": ops}}
    return [host, dev]


def test_self_time_subtracts_children():
    assert sp.self_time(SPANS) == {
        "msched.slice": 900 - 400 - 60 - 200 - 180,
        "msched.switch": 400 - 50 - 100 - 240,
        "msched.plan": 50,
        "msched.evict": 105,
        "msched.fetch": 280,
        "msched.fault_service": 60 - 5 - 40,
        "msched.step": (200 - 40 - 150) + (180 - 20 - 160),
        "msched.step.dispatch": 60,
        "msched.step.logits": 310,
    }


def test_self_time_keeps_threads_apart():
    spans = [_span("msched.step", 0, 100), _span("msched.plan", 10, 20, line="other")]
    assert sp.self_time(spans) == {"msched.step": 100, "msched.plan": 10}


def test_idle_by_innermost_span():
    t = _trace()
    w0, w1 = tr.window(t)
    got = sp.idle_by_span(t, SPANS, w0, w1)
    assert got == {
        "msched.plan": (1, 50),
        "msched.evict": (1, 95),
        "msched.fetch": (1, 240),
        "msched.slice": (1, 130),  # between the fault and the first step
        "msched.step.dispatch": (1, 50),
        "in serve outside steps": (2, 80 + 20),  # after the slice's span
    }
    # the same holes as the harness's labelling, only labelled otherwise
    gaps = tr.idle_gaps(t, w0, w1)
    assert sum(n for n, _ in got.values()) == sum(n for n, _ in gaps.values())
    assert sum(ns for _, ns in got.values()) == sum(ns for _, ns in gaps.values())


def test_numbers_of_the_live_path():
    t = _trace()
    # evictions of the switch and of the fault over the switch's and the fault's time
    assert sp.evict_share(SPANS) == pytest.approx(100.0 * (100 + 5) / (400 + 60))
    assert sp.h2d_gbps(SPANS) == pytest.approx((300 + 60) / (240 + 40))
    assert sp.plan_ms(SPANS) == pytest.approx(50 / 1e6)
    # steps 500-880 (380 ns); busy inside them 560-680 and 730-870
    assert sp.step_idle_share(t, SPANS, 0, 1000) == pytest.approx(100.0 * (380 - 120 - 140) / 380)
    # clipped to the window
    assert sp.step_idle_share(t, SPANS, 0, 600) == pytest.approx(100.0 * (100 - 40) / 100)


def test_copies_by_what_they_served():
    got = sp.copies(SPANS)
    assert got == {
        "msched.switch": {"msched.evict": [pytest.approx(100e-9), 100], "msched.fetch": [pytest.approx(240e-9), 300]},
        "msched.fault_service": {"msched.evict": [pytest.approx(5e-9), 10], "msched.fetch": [pytest.approx(40e-9), 60]},
    }
    assert sp.copies([]) == {} and sp.evict_share([]) is None


def test_summary_shapes_idle_by_span_as_idle_gaps():
    t = _trace()
    s = sp.summarize(t, SPANS, 0, 1000, top=3)
    assert s["idle_by_span"] == [["msched.fetch (1 gaps)", 240e-9], ["msched.slice (1 gaps)", 130e-9],
                                 ["in serve outside steps (2 gaps)", 100e-9]]
    assert s["spans"] == len(SPANS)
    assert s["self_s"]["msched.switch"] == pytest.approx(10e-9)
    # spans that start after the window are left out of the sums
    assert sp.summarize(t, SPANS, 0, 425)["h2d_gbps"] == pytest.approx(300 / 240)
    # a span that starts before the window still labels the gap it holds
    late = sp.summarize(t, SPANS, 20, 1000)
    assert ["msched.plan (1 gaps)", pytest.approx(30e-9)] in late["idle_by_span"]
    assert late["plan_ms"] is None


def test_no_program_span_falls_back_to_the_harness_labels():
    """An older program writes no span: the labels are ``idle_gaps``'s and
    every number is None."""
    from bench.tests.test_trace_reduce import _hand_trace

    t = _hand_trace()
    w0, w1 = tr.window(t)
    assert sp.idle_by_span(t, [], w0, w1) == tr.idle_gaps(t, w0, w1)
    s = sp.summarize(t, [], w0, w1)
    assert [s[k] for k in ("evict_share", "h2d_gbps", "plan_ms", "step_idle_share")] == [None] * 4
    rec = json.loads((DATA / "trace_slice.json").read_text())
    planes, (w0, w1) = rec["planes"], rec["window"]
    assert sp.idle_by_span(planes, [], w0, w1) == tr.idle_gaps(planes, w0, w1)


def test_no_device_plane_gives_no_idle():
    t = [p for p in _trace() if not p["name"].startswith("/device")]
    assert sp.idle_by_span(t, SPANS, 0, 1000) == {}
    assert sp.step_idle_share(t, SPANS, 0, 1000) is None


def test_load_reads_stats_from_a_recorded_trace(tmp_path):
    import jax
    from jax.profiler import TraceAnnotation

    with jax.profiler.trace(str(tmp_path)):
        with TraceAnnotation("bench.window"):
            with TraceAnnotation("msched.switch", task=2) as sw:
                with TraceAnnotation("msched.fetch", nbytes=12345, segments=2):
                    jax.numpy.ones(4).block_until_ready()
                sw.set_metadata(in_bytes=12345, out_bytes=0)
            with TraceAnnotation("bench.other"):
                pass
    (path,) = glob.glob(f"{tmp_path}/**/*.xplane.pb", recursive=True)
    spans = sp.load(path)
    assert [s.name for s in spans] == ["msched.switch", "msched.fetch"]
    assert spans[0].stats == {"task": 2, "in_bytes": 12345, "out_bytes": 0}
    assert spans[1].stats == {"nbytes": 12345, "segments": 2}
    assert spans[0].start <= spans[1].start and spans[1].end <= spans[0].end
    assert spans[0].line == spans[1].line
    # the three-field events of the harness's loader are unchanged
    planes = tr.load_xplane(path)
    w0, w1 = tr.window(planes)
    assert w0 <= spans[0].start and spans[0].end <= w1
    assert all(len(e) == 3 for p in planes for evs in p["lines"].values() for e in evs)


def _record(trace):
    from bench.record import RunRecord

    return RunRecord(models=[], seconds=1.0, setup_s=0.0, t0=0.0, t_end=1.0, requests=[], outcome=None,
                     base=None, slices=[], step_s=[], trace=trace, costs={}, peaks={})


def _summary(spans):
    from bench.record import TraceSummary

    return TraceSummary(window_s=1e-6, busy_s=0.0, step_runs={}, device_ops=[], idle_gaps=[],
                        spans=sp.summarize(_trace(), spans, 0, 1000))


@pytest.mark.parametrize("name", ["evict_share", "h2d_gbps", "plan_ms", "step_idle_share"])
def test_span_metric_readers(name):
    """Each reader gives the window's number from the run record's spans,
    under either variant, and nothing untraced or where no span was written."""
    from bench import harness

    summary = _summary(SPANS)
    assert summary.spans[name] is not None
    for variant in ("open", "closed"):
        reader = harness.load_reader(harness.ROOT, f"{name}.{variant}")
        assert reader.read(_record(summary)) == summary.spans[name]
        assert reader.read(_record(None)) is None
        assert reader.read(_record(_summary([]))) is None
