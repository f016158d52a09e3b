"""The queue-wait reader: the program's own request stamps, nearest rank,
over the window's answered requests; nothing from a program without stamps."""
import types

import pytest

from bench.harness import load_reader
from bench.record import Outcome, RunRecord, Tracked
from bench.tests.support import ROOT


def _record(reqs):
    tracked = [Tracked(0, due=0.0, submitted=0.0, in_window=True, req=r, done=d) for r, d in reqs]
    return RunRecord(models=[], seconds=10.0, setup_s=0.0, t0=0.0, t_end=10.0, requests=tracked,
                     outcome=Outcome(len(tracked), 0), base=None, slices=[], step_s=[], trace=None,
                     costs={}, peaks={})


def _req(submitted, started):
    return types.SimpleNamespace(submitted_s=submitted, started_s=started)


def test_p95_of_the_stamped_waits():
    read = load_reader(ROOT, "queue_wait_ms.open").read
    # 20 answered in the window, waiting 0.1 .. 2.0 s; one answered after it
    reqs = [(_req(1.0, 1.0 + 0.1 * (i + 1)), 5.0) for i in range(20)] + [(_req(0.0, 9.0), 11.0)]
    assert read(_record(reqs)) == pytest.approx(2000.0)  # index floor(0.95 x 20) = 19


def test_unstamped_requests_give_nothing():
    read = load_reader(ROOT, "queue_wait_ms.open").read
    assert read(_record([(types.SimpleNamespace(), 5.0)])) is None
    assert read(_record([])) is None
