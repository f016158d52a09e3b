"""Live JAX runtime: MSched must be semantically transparent — multitasked,
memory-oversubscribed execution produces outputs identical to all-resident
execution (the paper's OS-level transparency claim, with real arrays)."""
import jax
import numpy as np
import pytest

from repro.core.runtime import LiveModelTask, LiveRuntime

ARCHS = ["qwen3-1.7b", "llama3.2-3b", "mamba2-1.3b"]
# the three families the benchmark serves: dense with q/k norms, dense
# with scaled residuals, and the SSM stack
FAMILIES = ["qwen3-1.7b", "minicpm-2b", "mamba2-1.3b"]


@pytest.fixture(scope="module")
def tasks():
    return [LiveModelTask(i, a, seed=i) for i, a in enumerate(ARCHS)]


def _resident(task):
    for s in task.segments:
        s.device = jax.device_put(s.host)


def _rows(task, steps, rows):
    """The logits of decode steps ``0 .. steps - 1``, each ``(1, 1, vocab)``,
    from ``run_step`` calls of ``rows`` steps each."""
    task.rows = rows
    outs = [task.run_step(i) for i in range(0, steps, rows)]
    return [out[k : k + 1] for out in outs for k in range(rows)]


def _all_resident_outputs(tasks, rows, steps=8):
    """Each task run standalone with every segment resident, through the
    same batched step program as a runtime's slices of ``rows`` steps."""
    baseline = {}
    for t in tasks:
        _resident(t)
        baseline[t.task_id] = _rows(t, steps, rows)
        for s in t.segments:
            s.device = None
    return baseline


def test_oversubscribed_outputs_match_baseline(tasks):
    baseline = _all_resident_outputs(tasks, rows=4)

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
    baseline = _all_resident_outputs(tasks, rows=4)
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


@pytest.fixture(scope="module")
def family_tasks():
    tasks = {a: LiveModelTask(i, a, seed=i) for i, a in enumerate(FAMILIES)}
    for t in tasks.values():
        _resident(t)
    return tasks


@pytest.mark.parametrize("rows", [4, 32])
@pytest.mark.parametrize("arch", FAMILIES)
def test_batched_step_matches_one_row_step(family_tasks, arch, rows):
    """A slice's batch answers each of its steps as the one-row step does,
    within bf16 rounding (the batch may reorder a reduction)."""
    task = family_tasks[arch]
    one = _rows(task, 64, 1)
    batched = _rows(task, 64, rows)
    assert [b.shape for b in batched] == [(1, 1, task.cfg.vocab_size)] * 64
    for a, b in zip(one, batched):
        assert a.dtype == b.dtype
        np.testing.assert_allclose(b.astype(np.float32), a.astype(np.float32), rtol=2**-7, atol=1e-5)


@pytest.mark.parametrize("arch", FAMILIES)
def test_equal_tokens_give_identical_rows(family_tasks, arch):
    """A token's logits do not depend on the row of the batch it sits in,
    nor on the call: the same input is answered alike every time."""
    task = family_tasks[arch]
    seen = {}
    for step, out in enumerate(_rows(task, 4 * 32, 32)):
        seen.setdefault(1 + step % 13, set()).add(out.tobytes())
    assert len(seen) == 13
    assert all(len(v) == 1 for v in seen.values())


@pytest.mark.parametrize("arch", FAMILIES)
def test_predicted_extents_stay_in_the_task(arch):
    """After 2,000 steps the predicted KV extent still ends at its buffer:
    every page the predictor names belongs to the task's segments or its
    KV buffer."""
    task = LiveModelTask(0, arch)
    rt = LiveRuntime([task], hbm_budget_bytes=2 * task.footprint_bytes(), steps_per_slice=32)
    rt.run(total_slices=63)
    assert rt.stats.steps[0] == 63 * 32 > 2000
    assert rt.stats.step_calls[0] == 63
    space = task.space
    own = space.pages_of([(s.base, s.nbytes) for s in task.segments] + [(task.kv_buf.base, task.kv_buf.size)])
    queue = rt.helpers[0].queue
    assert queue
    for cmd in queue:
        assert space.pages_of(cmd.predicted_extents) <= own
    # the predicted KV extent reaches the whole buffer
    assert space.pages_of_extent((task.kv_buf.base, task.kv_buf.size))[-1] in space.pages_of(queue[-1].predicted_extents)
