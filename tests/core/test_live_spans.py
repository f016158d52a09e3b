"""The live path's profiler spans: their nesting, their byte counts against
``LiveStats``, and the serial number that ties a request to its slice."""
import glob

import jax
import pytest
from jax.profiler import ProfileData

from repro.runtime.serve_loop import MultiModelServer, Request

ARCHS = ["qwen3-1.7b", "mamba2-1.3b"]
STEPS_PER_SLICE = 2


def _spans(trace_dir):
    """(name, start ns, end ns, stats) of every ``msched.*`` host event."""
    (path,) = glob.glob(f"{trace_dir}/**/*.xplane.pb", recursive=True)
    out = []
    for plane in ProfileData.from_file(path).planes:
        for line in plane.lines:
            for e in line.events:
                if e.name.startswith("msched."):
                    out.append((e.name, e.start_ns, e.start_ns + e.duration_ns, dict(e.stats)))
    return sorted(out, key=lambda s: (s[1], -s[2]))


def _inside(inner, outer):
    return outer[1] <= inner[1] and inner[2] <= outer[2]


@pytest.fixture(scope="module")
def traced(tmp_path_factory):
    # a pool of about 0.6 x the two models' weights: every switch evicts
    server = MultiModelServer(ARCHS, oversub=3.5, steps_per_slice=STEPS_PER_SLICE)
    stats = server.runtime.stats
    reqs = [Request(model=i % 2, arrival_s=float(i)) for i in range(10)]
    before = (stats.migrated_in_bytes, stats.migrated_out_bytes, len(stats.switch_wall_s))
    trace_dir = str(tmp_path_factory.mktemp("trace"))
    with jax.profiler.trace(trace_dir):
        for r in reqs:
            server.submit(r)
        server.serve(wall_budget_s=120.0)
    assert not any(server.queues.values())
    return server, reqs, before, _spans(trace_dir)


def _named(spans, name):
    return [s for s in spans if s[0] == name]


def test_switch_holds_plan_eviction_and_fetch(traced):
    _, _, _, spans = traced
    switches = _named(spans, "msched.switch")
    assert switches
    for sw in switches:
        plans = [s for s in _named(spans, "msched.plan") if _inside(s, sw)]
        assert len(plans) == 1
        assert {"pages_in", "pages_out"} <= set(plans[0][3])
        moved = {n: sum(s[3]["nbytes"] for s in _named(spans, n) if _inside(s, sw))
                 for n in ("msched.fetch", "msched.evict")}
        assert (moved["msched.fetch"], moved["msched.evict"]) == (sw[3]["in_bytes"], sw[3]["out_bytes"])
    # an oversubscribed pool evicts on some switch and fetches on some switch
    assert any(s[3]["out_bytes"] for s in switches) and any(s[3]["in_bytes"] for s in switches)
    # every copy belongs to a switch or to a demand fault
    owners = switches + _named(spans, "msched.fault_service")
    for copy in _named(spans, "msched.fetch") + _named(spans, "msched.evict"):
        assert copy[3]["nbytes"] > 0 and copy[3]["segments"] >= 1
        assert any(_inside(copy, o) for o in owners)


def test_slice_holds_steps_which_hold_dispatch_and_logits(traced):
    server, _, _, spans = traced
    slices = _named(spans, "msched.slice")
    assert [s[3]["slice"] for s in slices] == list(range(slices[0][3]["slice"], server.runtime.last_slice + 1))
    for sl in slices:
        steps = [s for s in _named(spans, "msched.step") if _inside(s, sl)]
        assert len(steps) == STEPS_PER_SLICE
        assert {s[3]["task"] for s in steps} == {sl[3]["task"]}
        assert len([s for s in _named(spans, "msched.switch") if _inside(s, sl)]) == 1
    steps = _named(spans, "msched.step")
    assert all(any(_inside(s, sl) for sl in slices) for s in steps)
    for st in steps:
        for child in ("msched.step.dispatch", "msched.step.logits"):
            assert len([s for s in _named(spans, child) if _inside(s, st)]) == 1
    assert len(_named(spans, "msched.step.dispatch")) == len(steps)


def test_span_bytes_equal_the_counters(traced):
    server, _, (in0, out0, _), spans = traced
    stats = server.runtime.stats
    assert sum(s[3]["nbytes"] for s in _named(spans, "msched.fetch")) == stats.migrated_in_bytes - in0
    assert sum(s[3]["nbytes"] for s in _named(spans, "msched.evict")) == stats.migrated_out_bytes - out0
    assert stats.migrated_out_bytes > out0


def test_switch_timer_covers_the_fetch(traced):
    server, _, (_, _, n0), spans = traced
    walls = server.runtime.stats.switch_wall_s[n0:]
    switches = _named(spans, "msched.switch")
    assert len(walls) == len(switches)
    for wall, sw in zip(walls, switches):
        fetch = [s for s in _named(spans, "msched.fetch") if _inside(s, sw)]
        assert wall * 1e9 >= sum(s[2] - s[1] for s in fetch)


def test_requests_carry_their_slice(traced):
    _, reqs, _, spans = traced
    task_of = {s[3]["slice"]: s[3]["task"] for s in _named(spans, "msched.slice")}
    for r in reqs:
        assert task_of[r.slice] == r.model
        assert r.submitted_s <= r.started_s <= r.answered_s
    # two steps per slice answer two requests of one model
    assert sorted(sum(1 for r in reqs if r.slice == s) for s in {r.slice for r in reqs}) == [1, 1, 2, 2, 2, 2]
