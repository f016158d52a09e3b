"""Live multi-task JAX runtime: MSched driving *real* array migrations.

Each task is a real model from the zoo (at its published widths, or its
``.reduced()`` cut for CPU tests) whose parameters are
page-granular segments in a task address space. "HBM" is a budgeted device
pool: resident segments are ``jax.Array``s, evicted segments live as host
numpy copies. On every context switch the MSched coordinator predicts the
next task's working set (template predictor over the decode command stream,
including the growing KV slice), enforces the OPT eviction order, and
migrates segments with real ``jax.device_put`` / host copies.

Correctness contract (tested): step outputs are bit-identical to an
all-resident baseline, because MSched migration is semantically transparent —
exactly the paper's OS-level transparency claim.
"""
from __future__ import annotations

import dataclasses
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np

from repro.configs import get_config
from repro.core.commands import Command, kernel
from repro.core.hbm import HBMPool
from repro.core.memory_manager import Coordinator, TaskHelper
from repro.core.pages import AddressSpace
from repro.core.predictor import TemplatePredictor
from repro.core.profiler import profile_programs
from repro.core.scheduler import RoundRobinPolicy, SchedTask
from repro.core.templates import analyze_traces
from repro.core.timeline import TaskTimeline
from repro.core.hardware import TPU_V5E


@dataclasses.dataclass
class Segment:
    path: str
    base: int
    nbytes: int
    host: np.ndarray  # authoritative host copy when evicted
    device: Optional[jax.Array] = None  # resident copy


class LiveModelTask:
    """A decode job over one model; weights are pageable segments."""

    def __init__(
        self,
        task_id: int,
        arch: str,
        page_size: int = 4096,
        seed: int = 0,
        reduced: bool = True,
    ):
        from repro.models.model import build_model

        self.task_id = task_id
        cfg = get_config(arch)
        self.cfg = cfg.reduced() if reduced else cfg
        self.fns = build_model(self.cfg)
        self.space = AddressSpace(page_size=page_size, base=(task_id + 1) << 44)
        params = self.fns.init(jax.random.PRNGKey(seed))
        self.treedef = jax.tree.structure(params)
        leaves = jax.tree.leaves(params)
        paths = [
            "/".join(str(k) for k in path)
            for path, _ in jax.tree_util.tree_flatten_with_path(params)[0]
        ]
        self.segments: List[Segment] = []
        for path, leaf in zip(paths, leaves):
            host = np.asarray(leaf)
            buf = self.space.malloc(max(host.nbytes, 1), path)
            self.segments.append(Segment(path, buf.base, host.nbytes, host))
        # decode state
        self.tokens = jnp.ones((1, 1), jnp.int32)
        self.pos = 0
        self.kv_buf = self.space.malloc(1 << 20, "kv")
        self._step = jax.jit(lambda p, t: self.fns.forward(p, {"tokens": t}))

    # -- command stream (the helper intercepts these) -----------------------
    def next_commands(self, step_idx: int) -> List[Command]:
        exts = [(s.base, s.nbytes) for s in self.segments]
        exts.append((self.kv_buf.base, min(4096 * (step_idx + 1), self.kv_buf.size)))
        args = tuple(s.base for s in self.segments[:8]) + (
            self.kv_buf.base,
            step_idx + 1,
            4096,
        )
        return [kernel(f"{self.cfg.name}_step", args, 500.0, exts)]

    # -- execution -----------------------------------------------------------
    def run_step(self, rng_step: int) -> np.ndarray:
        params = self.resident_params()
        tok = jnp.asarray([[1 + (rng_step % 13)]], jnp.int32)
        out = self._step(params, tok)
        return np.asarray(out)

    def resident_params(self):
        leaves = []
        for s in self.segments:
            if s.device is None:
                raise RuntimeError(f"segment {s.path} not resident (fault)")
            leaves.append(s.device)
        return jax.tree.unflatten(self.treedef, leaves)

    def footprint_bytes(self) -> int:
        return sum(s.nbytes for s in self.segments) + self.kv_buf.size

    # program interface used by the profiler
    def iteration(self, it: int) -> List[Command]:
        return self.next_commands(it)


@dataclasses.dataclass
class LiveStats:
    """Counters per task id; the totals over tasks are properties."""

    steps: Dict[int, int]
    in_bytes: Dict[int, int]  # host -> device
    out_bytes: Dict[int, int]  # device -> host (evictions)
    faults: Dict[int, int]  # demand faults
    switch_wall_s: List[float]

    @property
    def migrated_in_bytes(self) -> int:
        return sum(self.in_bytes.values())

    @property
    def migrated_out_bytes(self) -> int:
        return sum(self.out_bytes.values())

    @property
    def demand_faults(self) -> int:
        return sum(self.faults.values())


class LiveRuntime:
    """Round-robin multitasking with proactive working-set migration."""

    def __init__(
        self,
        tasks: List[LiveModelTask],
        hbm_budget_bytes: int,
        steps_per_slice: int = 4,
    ):
        self.tasks = {t.task_id: t for t in tasks}
        # the pool pages at the tasks' extent, so they must share one
        (page_size,) = {t.space.page_size for t in tasks}
        self.page_size = page_size
        self.pool = HBMPool(max(1, hbm_budget_bytes // page_size))
        # offline phase: profile + analyze (real MSched flow)
        store = profile_programs(list(tasks), iters=3)
        descriptors = analyze_traces(store)
        self.coordinator = Coordinator(TPU_V5E, self.pool, page_size=page_size)
        self.helpers: Dict[int, TaskHelper] = {}
        for t in tasks:
            h = TaskHelper(t.task_id, t.space, TemplatePredictor(descriptors))
            self.helpers[t.task_id] = h
            self.coordinator.register(h)
        self.steps_per_slice = steps_per_slice
        self.policy = RoundRobinPolicy(quantum_us=1000.0 * steps_per_slice)
        ids = list(self.tasks)
        self.stats = LiveStats(
            dict.fromkeys(ids, 0),
            dict.fromkeys(ids, 0),
            dict.fromkeys(ids, 0),
            dict.fromkeys(ids, 0),
            [],
        )
        self._step_counter = dict.fromkeys(ids, 0)
        # logits of every step the last run() served, per task, in step order
        self.outputs: Dict[int, List[np.ndarray]] = {}

    # -- real data movement ---------------------------------------------------
    def _sync_residency(self) -> None:
        """Make device arrays mirror the pool's residency decisions: a
        segment is on-device iff all of its pages are pool-resident. Every
        eviction runs before any host->device copy, so the device never holds
        the outgoing and the incoming working sets at once."""
        fetch = []
        for task in self.tasks.values():
            tid = task.task_id
            for seg in task.segments:
                pages = task.space.pages_of_extent((seg.base, seg.nbytes))
                resident = all(self.pool.resident(p) for p in pages)
                if resident and seg.device is None:
                    fetch.append((tid, seg))
                elif not resident and seg.device is not None:
                    seg.host = np.asarray(seg.device)  # D2H eviction
                    seg.device = None
                    self.stats.out_bytes[tid] += seg.nbytes
        for tid, seg in fetch:
            seg.device = jax.device_put(seg.host)  # H2D
            self.stats.in_bytes[tid] += seg.nbytes

    def _fault_in(self, task: LiveModelTask) -> None:
        """Demand-paging fallback: any still-missing segment faults in."""
        for seg in task.segments:
            if seg.device is None:
                pages = list(task.space.pages_of_extent((seg.base, seg.nbytes)))
                self.pool.migrate(pages)
                self._sync_residency()
                self.stats.faults[task.task_id] += 1

    # -- main loop -------------------------------------------------------------
    def run(self, total_slices: int = 12) -> LiveStats:
        self.outputs = {tid: [] for tid in self.tasks}
        for _ in range(total_slices):
            sched = {tid: SchedTask(tid) for tid in self.tasks}
            entry = self.policy.next_entry(sched)
            timeline = TaskTimeline([entry] + self.policy.timeline(sched).entries)
            task = self.tasks[entry.task_id]
            helper = self.helpers[entry.task_id]
            # refill the async window
            while len(helper.queue) < 2 * self.steps_per_slice:
                for cmd in task.next_commands(
                    self._step_counter[entry.task_id] + len(helper.queue)
                ):
                    helper.launch(cmd)
            # extended context switch: proactive working-set migration
            t0 = time.perf_counter()
            self.coordinator.on_context_switch(entry.task_id, timeline)
            self._sync_residency()
            self.stats.switch_wall_s.append(time.perf_counter() - t0)
            self._fault_in(task)
            for _ in range(self.steps_per_slice):
                step = self._step_counter[entry.task_id]
                self.outputs[entry.task_id].append(task.run_step(step))
                self._step_counter[entry.task_id] += 1
                self.stats.steps[entry.task_id] += 1
                if helper.queue:
                    helper.pop()
        return self.stats
