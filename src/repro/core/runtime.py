"""Live multi-task JAX runtime: MSched driving *real* array migrations.

Each task is a real model from the zoo (at its published widths, or its
``.reduced()`` cut for CPU tests) whose parameters are
page-granular segments in a task address space. "HBM" is a budgeted device
pool: every segment keeps a read-only host numpy array, and a resident one
also a ``jax.Array`` put from it. On every context switch the MSched
coordinator predicts the next task's working set (template predictor over
the decode command stream, including the growing KV slice), enforces the OPT
eviction order, and migrates segments with real ``jax.device_put``s. An
eviction drops the device array: it equals the host array bit for bit, so
nothing is copied back.

Correctness contract (tested): step outputs are bit-identical to an
all-resident baseline, because MSched migration is semantically transparent —
exactly the paper's OS-level transparency claim.

Every phase of the live path writes a ``jax.profiler.TraceAnnotation`` span
named ``msched.*``, with its counts as the span's stats (``docs/
observability.md``, "Live path spans"). With no profiler running a span
costs about a microsecond, so they are always on.
"""
from __future__ import annotations

import dataclasses
import re
import time
from typing import Dict, List, Optional

import jax
import jax.numpy as jnp
import numpy as np
from jax.profiler import TraceAnnotation

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


def named_step(fns):
    """The jitted one-token decode step of ``fns``'s model, named
    ``decode_step_<arch>`` so that each model's step program carries its
    architecture in the trace's ``XLA Modules`` line."""

    def step(params, tokens):
        return fns.forward(params, {"tokens": tokens})

    step.__name__ = step.__qualname__ = "decode_step_" + re.sub(r"\W", "_", fns.cfg.name)
    return jax.jit(step)


@dataclasses.dataclass
class Segment:
    """One weight leaf of a task. Weights are read-only: the step returns
    only logits and JAX arrays are immutable, so a resident ``device`` copy
    equals ``host`` for as long as it lives, and eviction drops it. A
    writable segment (a KV cache) would need a write-back on eviction,
    which no segment has today."""

    path: str
    base: int
    nbytes: int
    host: np.ndarray  # authoritative, never rewritten by migration
    device: Optional[jax.Array] = None  # resident copy, put from ``host``


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
        self._step = named_step(self.fns)

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
        with TraceAnnotation("msched.step", task=self.task_id, step=rng_step):
            with TraceAnnotation("msched.step.dispatch"):
                params = self.resident_params()
                tok = jnp.asarray([[1 + (rng_step % 13)]], jnp.int32)
                out = self._step(params, tok)
            with TraceAnnotation("msched.step.logits"):
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
    out_bytes: Dict[int, int]  # evicted from the device (dropped, not copied)
    faults: Dict[int, int]  # demand faults
    # host time of each switch, planning through the fetched arrays' arrival
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
        # serial number of the slice run last (the ``msched.slice`` stat)
        self.last_slice = -1
        # logits of every step the last run() served, per task, in step order
        self.outputs: Dict[int, List[np.ndarray]] = {}

    # -- real data movement ---------------------------------------------------
    def _sync_residency(self) -> None:
        """Make device arrays mirror the pool's residency decisions: a
        segment is on-device iff all of its pages are pool-resident. An
        eviction drops the segment's device array, which frees its buffer at
        once (the step has synchronised on its logits), and every eviction
        runs before any host->device copy, so the device never holds the
        outgoing and the incoming working sets at once. Returns once the
        fetched arrays are on the device."""
        fetch, evict = [], []
        for task in self.tasks.values():
            for seg in task.segments:
                pages = task.space.pages_of_extent((seg.base, seg.nbytes))
                resident = all(self.pool.resident(p) for p in pages)
                if resident and seg.device is None:
                    fetch.append((task.task_id, seg))
                elif not resident and seg.device is not None:
                    evict.append((task.task_id, seg))
        if evict:
            nbytes = sum(seg.nbytes for _, seg in evict)
            with TraceAnnotation("msched.evict", nbytes=nbytes, segments=len(evict)):
                for tid, seg in evict:
                    seg.device = None  # read-only: host already holds these bytes
                    self.stats.out_bytes[tid] += seg.nbytes
        if fetch:
            nbytes = sum(seg.nbytes for _, seg in fetch)
            with TraceAnnotation("msched.fetch", nbytes=nbytes, segments=len(fetch)):
                for tid, seg in fetch:
                    seg.device = jax.device_put(seg.host)  # H2D
                    self.stats.in_bytes[tid] += seg.nbytes
                jax.block_until_ready([seg.device for _, seg in fetch])

    def _fault_in(self, task: LiveModelTask) -> None:
        """Demand-paging fallback: any still-missing segment faults in."""
        for seg in task.segments:
            if seg.device is None:
                with TraceAnnotation("msched.fault_service", task=task.task_id, nbytes=seg.nbytes):
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
            self.last_slice += 1
            with TraceAnnotation("msched.slice", task=entry.task_id, slice=self.last_slice):
                self._run_slice(entry.task_id, timeline)
        return self.stats

    def _run_slice(self, tid: int, timeline: TaskTimeline) -> None:
        task = self.tasks[tid]
        helper = self.helpers[tid]
        # refill the async window
        while len(helper.queue) < 2 * self.steps_per_slice:
            for cmd in task.next_commands(self._step_counter[tid] + len(helper.queue)):
                helper.launch(cmd)
        # extended context switch: proactive working-set migration
        in0, out0 = self.stats.migrated_in_bytes, self.stats.migrated_out_bytes
        t0 = time.perf_counter()
        with TraceAnnotation("msched.switch", task=tid) as span:
            with TraceAnnotation("msched.plan") as plan:
                report = self.coordinator.on_context_switch(tid, timeline)
                plan.set_metadata(pages_in=report.populated_pages, pages_out=report.evicted_pages)
            self._sync_residency()
            span.set_metadata(
                in_bytes=self.stats.migrated_in_bytes - in0,
                out_bytes=self.stats.migrated_out_bytes - out0,
            )
        self.stats.switch_wall_s.append(time.perf_counter() - t0)
        self._fault_in(task)
        for _ in range(self.steps_per_slice):
            step = self._step_counter[tid]
            self.outputs[tid].append(task.run_step(step))
            self._step_counter[tid] += 1
            self.stats.steps[tid] += 1
            if helper.queue:
                helper.pop()
