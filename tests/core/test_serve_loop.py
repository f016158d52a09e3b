"""MultiModelServer: the live (real-JAX) serving loop hosting several models
on one device budget with MSched-style proactive migration."""
import pytest

from repro.runtime.serve_loop import MultiModelServer, Request

ARCHS = ["qwen3-1.7b", "mamba2-1.3b"]


@pytest.fixture(scope="module")
def server():
    return MultiModelServer(ARCHS, steps_per_slice=2)


def test_server_setup_oversubscribed(server):
    total = sum(t.footprint_bytes() for t in server.runtime.tasks.values())
    budget = server.runtime.pool.capacity * server.runtime.page_size
    assert budget < total  # 150% oversubscription by default
    assert set(server.queues) == {0, 1}


def test_serve_drains_queues_fifo(server):
    reqs = []
    for i in range(3):
        reqs.append(Request(model=0, arrival_s=0.1 * i))
        reqs.append(Request(model=1, arrival_s=0.05 + 0.1 * i))
    for r in reqs:
        server.submit(r)
    stats = server.serve(wall_budget_s=60.0)
    assert stats.served[0] == 3
    assert stats.served[1] == 3
    assert not any(server.queues.values())
    # every request stamped in order: submitted, its slice started, answered
    for r in reqs:
        assert r.submitted_s <= r.started_s <= r.answered_s
    # two steps per slice: the oldest model's first two requests share a slice
    by_slice = {}
    for r in reqs:
        by_slice.setdefault(r.slice, []).append(r)
    assert [len(v) for _, v in sorted(by_slice.items())] == [2, 2, 1, 1]
    assert [v[0].model for _, v in sorted(by_slice.items())] == [0, 1, 0, 1]
    assert all(len({(r.started_s, r.answered_s) for r in v}) == 1 for v in by_slice.values())
    # oversubscribed hosting must have moved real bytes into the pool
    assert stats.migrated_in_bytes > 0


def test_serve_empty_queue_returns_immediately(server):
    stats = server.serve(wall_budget_s=5.0)
    assert sum(stats.served.values()) == 0
    assert all(not q for q in server.queues.values())


def test_unanswered_request_has_only_its_submit_stamp(server):
    req = Request(model=0, arrival_s=0.0)
    server.submit(req)
    stats = server.serve(wall_budget_s=0.0)
    assert sum(stats.served.values()) == 0
    assert req.submitted_s is not None
    assert (req.started_s, req.answered_s, req.slice, req.step) == (None, None, None, None)
    server.queues[0].clear()
