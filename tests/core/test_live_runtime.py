"""Live JAX runtime: MSched must be semantically transparent — multitasked,
memory-oversubscribed execution produces outputs identical to all-resident
execution (the paper's OS-level transparency claim, with real arrays)."""
import jax
import numpy as np
import pytest

from repro.core.runtime import LiveModelTask, LiveRuntime

ARCHS = ["qwen3-1.7b", "llama3.2-3b", "mamba2-1.3b"]


@pytest.fixture(scope="module")
def tasks():
    return [LiveModelTask(i, a, seed=i) for i, a in enumerate(ARCHS)]


def _all_resident_outputs(tasks, steps=8):
    """Each task run standalone with every segment resident."""
    baseline = {}
    for t in tasks:
        for s in t.segments:
            s.device = jax.device_put(s.host)
        baseline[t.task_id] = [t.run_step(i) for i in range(steps)]
        for s in t.segments:
            s.device = None
    return baseline


def test_oversubscribed_outputs_match_baseline(tasks):
    baseline = _all_resident_outputs(tasks)

    total = sum(t.footprint_bytes() for t in tasks)
    rt = LiveRuntime(tasks, hbm_budget_bytes=int(total / 2.0), steps_per_slice=4)
    rt.run(total_slices=6)  # 2 slices x 4 steps per task = 8 steps each

    for t in tasks:
        assert rt.stats.steps[t.task_id] == 8
    # what the oversubscribed runtime produced equals the all-resident run
    for t in tasks:
        served = rt.outputs[t.task_id]
        assert len(served) == 8
        for a, b in zip(baseline[t.task_id], served):
            np.testing.assert_array_equal(a, b)


def test_real_migration_happened(tasks):
    # budget below the summed *parameter* bytes forces real evictions
    total = sum(s.nbytes for t in tasks for s in t.segments)
    for t in tasks:
        for s in t.segments:
            s.device = None
    rt = LiveRuntime(tasks, hbm_budget_bytes=int(total * 0.6), steps_per_slice=2)
    stats = rt.run(total_slices=6)
    assert stats.migrated_in_bytes > 0
    assert stats.migrated_out_bytes > 0
    # proactive scheduling leaves few demand faults
    assert stats.demand_faults <= 2 * len(tasks) * 6
    # Fig. 11: real coordinator wall time stays small
    assert max(stats.switch_wall_s) < 0.5


def test_eviction_keeps_the_host_copy(tasks):
    """An eviction drops the device array and leaves the host array as it
    was; steps served after a segment comes back match the all-resident run."""
    baseline = _all_resident_outputs(tasks)
    segs = [s for t in tasks for s in t.segments]
    hosts = {id(s): (s.host, s.host.tobytes()) for s in segs}
    assert not any(s.host.flags.writeable for s in segs)
    evicted, refetched = set(), set()

    class Watched(LiveRuntime):
        def _sync_residency(self):
            before = {id(s) for s in segs if s.device is not None}
            super()._sync_residency()
            for s in segs:
                if id(s) in before and s.device is None:
                    evicted.add(id(s))
                    host, raw = hosts[id(s)]
                    assert s.host is host and s.host.tobytes() == raw
                elif id(s) in evicted and s.device is not None:
                    refetched.add(id(s))

    # below the summed parameter bytes, so weights are evicted and come back
    total = sum(s.nbytes for s in segs)
    rt = Watched(tasks, hbm_budget_bytes=int(total * 0.6), steps_per_slice=4)
    stats = rt.run(total_slices=6)
    assert evicted and refetched
    assert stats.migrated_out_bytes >= sum(s.nbytes for s in segs if id(s) in evicted)
    for s in segs:
        host, raw = hosts[id(s)]
        assert s.host is host and s.host.tobytes() == raw
    for t in tasks:
        assert len(rt.outputs[t.task_id]) == 8
        for a, b in zip(baseline[t.task_id], rt.outputs[t.task_id]):
            np.testing.assert_array_equal(a, b)
